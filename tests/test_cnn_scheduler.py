"""Scheduler contract: admission order, buckets, padding, drain -- no device math.

The whole scheduling policy (serving/scheduler.py) is host bookkeeping, so
everything here runs against a stubbed forward fn: no jax arrays, no jit.
Also holds the single-definition invariant for the admission queue -- both
engines must share the scheduler's FIFO pop instead of keeping a copy.
"""
import dataclasses
import pathlib

import numpy as np
import pytest

from repro.serving.scheduler import (
    Microbatcher,
    RequestQueue,
    pad_batch,
    select_bucket,
)

SRC = pathlib.Path(__file__).resolve().parent.parent / "src"


@dataclasses.dataclass
class Req:
    uid: int


# -- single-definition invariant (like the limb split's) ----------------------

def test_fifo_pop_defined_once():
    """The admission pop exists exactly once in src/ (the scheduler); both
    serving engines import RequestQueue instead of re-implementing it.
    Neither a list-pop nor the scheduler's slice-pop may appear anywhere
    else (engine.py's old ``self.queue.pop(0)`` copy stays deleted)."""
    for needle, owners in ((".pop(0)", []),
                           ("del self._pending[:", ["scheduler.py"])):
        hits = [p for p in SRC.rglob("*.py") if needle in p.read_text()]
        assert [p.name for p in hits] == owners, (needle, hits)


def test_engines_share_scheduler_queue():
    import repro.serving.cnn_engine as cnn_engine
    import repro.serving.engine as engine
    import repro.serving.scheduler as scheduler

    assert engine.RequestQueue is scheduler.RequestQueue
    assert cnn_engine.Microbatcher is scheduler.Microbatcher


# -- queue admission order ----------------------------------------------------

def test_queue_fifo_order_and_ledger():
    t = [0.0]
    q = RequestQueue(clock=lambda: t[0])
    for uid in (3, 1, 4, 15, 9):
        q.submit(Req(uid))
        t[0] += 1.0
    assert len(q) == 5
    first = q.take(2)
    assert [r.uid for r in first] == [3, 1]          # strict submission order
    assert [r.uid for r in q.take(10)] == [4, 15, 9]  # take clamps to pending
    assert q.take(3) == [] and q.drained
    for r in first:
        q.finish(r)
    assert sorted(q.done) == [1, 3]
    # latency = completed - submitted, from the injected clock
    assert q.latency(3) == t[0] - 0.0
    assert q.latency(1) == t[0] - 1.0
    assert q.timing[3].queue_wait is not None


def test_queue_take_zero_is_noop():
    q = RequestQueue()
    q.submit(Req(1))
    assert q.take(0) == [] and len(q) == 1


# -- fixed-shape bucket selection ---------------------------------------------

def test_select_bucket_smallest_fit():
    buckets = (1, 4, 16, 64)
    assert select_bucket(1, buckets) == 1
    assert select_bucket(2, buckets) == 4
    assert select_bucket(4, buckets) == 4
    assert select_bucket(5, buckets) == 16
    assert select_bucket(17, buckets) == 64
    assert select_bucket(1000, buckets) == 64  # overflow drains at max batch
    with pytest.raises(ValueError):
        select_bucket(0, buckets)


def test_pad_batch_zero_pads_to_bucket():
    rows = [np.full((2, 3), i, np.float32) for i in (1, 2)]
    out = pad_batch(rows, 4)
    assert out.shape == (4, 2, 3)
    assert (out[0] == 1).all() and (out[1] == 2).all()
    assert (out[2:] == 0).all()
    with pytest.raises(ValueError):
        pad_batch(rows, 1)


# -- padding/unpadding bookkeeping with a stubbed forward ---------------------

def _stub_forward(seen):
    """Identity-ish stub: records batch shapes, tags each row with its sum."""
    def run(batch):
        seen.append(batch.shape)
        return batch.reshape(batch.shape[0], -1).sum(axis=1, keepdims=True)
    return run


def test_microbatcher_pads_and_unpads():
    mb = Microbatcher(buckets=(1, 4))
    for uid in range(3):
        mb.submit(Req(uid), np.full((2, 2), uid + 1, np.float32))
    seen = []
    done = mb.step(_stub_forward(seen))
    # 3 pending -> bucket 4, one padded row the stub saw but nobody got back
    assert seen == [(4, 2, 2)]
    assert [r.uid for r, _ in done] == [0, 1, 2]
    assert [float(v[0]) for _, v in done] == [4.0, 8.0, 12.0]
    assert mb.real_rows == 3 and mb.padded_rows == 1
    assert mb.padding_fraction == pytest.approx(0.25)
    assert mb.bucket_counts == {1: 0, 4: 1}


def test_microbatcher_bucket_shapes_are_fixed():
    """Every batch the forward fn ever sees is one of the bucket shapes --
    the property that makes steady-state serving all jit cache hits."""
    mb = Microbatcher(buckets=(1, 4))
    seen = []
    run = _stub_forward(seen)
    uid = 0
    for burst in (1, 2, 5, 4, 9, 1):
        for _ in range(burst):
            mb.submit(Req(uid), np.zeros((2,), np.float32))
            uid += 1
        while len(mb.queue):
            mb.step(run)
    assert {s[0] for s in seen} <= {1, 4}
    assert len(mb.queue.done) == uid


def test_microbatcher_rejects_bad_forward():
    mb = Microbatcher(buckets=(2,))
    mb.submit(Req(0), np.zeros((2,), np.float32))
    with pytest.raises(ValueError, match="leading dim"):
        mb.step(lambda b: b[:1])  # stub dropped the padded row on device
    # ... and even then the admitted request is NOT lost (requeued at front)
    assert [r.uid for r in mb.queue.pending] == [0]


def test_step_requeues_admitted_requests_on_forward_failure():
    """A forward that raises (OOM, bad shape) must not lose the admitted
    microbatch: requests go back to the FRONT of the queue in order, step
    counters stay untouched, and the exception propagates.  A retry then
    serves the same requests FIFO."""
    mb = Microbatcher(buckets=(1, 4))
    for uid in range(6):  # first microbatch admits 0..3, leaves 4..5 pending
        mb.submit(Req(uid), np.full((2,), uid, np.float32))
    attempts = []

    def flaky(batch):
        attempts.append(batch.shape)
        if len(attempts) == 1:
            raise RuntimeError("device OOM")
        return batch[:, :1]

    with pytest.raises(RuntimeError, match="OOM"):
        mb.step(flaky)
    # neither lost nor done; FIFO preserved ahead of the un-admitted tail
    assert [r.uid for r in mb.queue.pending] == [0, 1, 2, 3, 4, 5]
    assert mb.queue.done == {}
    # counters untouched by the failed step
    assert (mb.steps, mb.real_rows, mb.padded_rows) == (0, 0, 0)
    assert mb.bucket_counts == {1: 0, 4: 0}
    assert mb.batch_seconds == 0.0
    # admission stamp cleared: queue_wait will reflect the serving admission
    assert all(mb.queue.timing[u].admitted is None for u in range(4))
    # the retry succeeds and serves the SAME requests, oldest first
    done = mb.step(flaky)
    assert [r.uid for r, _ in done] == [0, 1, 2, 3]
    assert [float(v[0]) for _, v in done] == [0.0, 1.0, 2.0, 3.0]
    assert (mb.steps, mb.real_rows) == (1, 4)
    mb.run(flaky)
    assert sorted(mb.queue.done) == list(range(6))
    assert attempts == [(4, 2), (4, 2), (4, 2)]  # tail of 2 pads to bucket 4


def test_microbatcher_step_on_empty_queue():
    mb = Microbatcher(buckets=(1,))
    assert mb.step(lambda b: b) == []
    assert mb.steps == 0


# -- drain-on-run termination -------------------------------------------------

def test_run_drains_and_terminates():
    mb = Microbatcher(buckets=(1, 4))
    for uid in range(11):
        mb.submit(Req(uid), np.zeros((2,), np.float32))
    calls = []
    done = mb.run(_stub_forward(calls), max_steps=100)
    assert sorted(done) == list(range(11))
    assert len(mb.queue) == 0
    # 11 = 4 + 4 + 4(pad 1): three fixed-shape steps, then run() stopped
    assert calls == [(4, 2), (4, 2), (4, 2)]
    # run() on a drained queue is a no-op, not a livelock
    assert mb.run(_stub_forward(calls)) is mb.queue.done
    assert len(calls) == 3


def test_run_at_max_steps_with_pending_raises_not_silently_done():
    """Regression (ISSUE 7): run() used to return ``done`` silently when
    max_steps hit with requests still pending -- callers read that as
    "complete" and the pending tail was effectively lost.  Now it raises,
    with the partial ledger and the stranded uids on the exception."""
    from repro.serving.scheduler import IncompleteRunError

    mb = Microbatcher(buckets=(1,))
    for uid in range(5):
        mb.submit(Req(uid), np.zeros((1,), np.float32))
    with pytest.raises(IncompleteRunError, match="still pending") as ei:
        mb.run(lambda b: b, max_steps=2)
    assert len(mb.queue) == 3 and len(mb.queue.done) == 2
    assert sorted(ei.value.done) == [0, 1]
    assert ei.value.pending_uids == [2, 3, 4]
    # nothing was lost: the remaining steps still serve the tail
    mb.run(lambda b: b)
    assert sorted(mb.queue.done) == list(range(5))


def test_stats_rollup():
    mb = Microbatcher(buckets=(1, 4), clock=_FakeClock().tick)
    for uid in range(5):
        mb.submit(Req(uid), np.zeros((1,), np.float32))
    mb.run(lambda b: b)
    s = mb.stats()
    assert s["requests_done"] == 5
    assert s["steps"] == 2 and s["real_rows"] == 5 and s["padded_rows"] == 0
    assert s["batch_seconds"] > 0
    assert s["latency_mean_s"] > 0 and s["latency_p95_s"] >= s["latency_mean_s"]


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def tick(self):
        self.t += 0.5
        return self.t


def test_bucket_validation():
    with pytest.raises(ValueError):
        Microbatcher(buckets=())
    with pytest.raises(ValueError):
        Microbatcher(buckets=(0, 4))
    assert Microbatcher(buckets=(4, 1, 4)).buckets == (1, 4)


# -- SLO-aware admission (ISSUE 7): deadlines, expiry, the cost model ---------
# Everything below drives an injected fake clock -- deterministic seconds,
# no sleeps -- which is exactly why the engines take ``clock=``.

class _Clock:
    """Manually advanced clock; calling it reads, ``advance`` moves it."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def test_duplicate_uid_rejected_not_overwritten():
    """Regression (ISSUE 7 satellite): ``submit`` used to silently accept a
    duplicate uid, overwriting the first request's timing entry and later
    colliding in the ``done`` ledger (the first result vanished).  Now it
    raises, naming the state the uid is already in."""
    q = RequestQueue()
    first = Req(1)
    q.submit(first)
    with pytest.raises(ValueError, match="duplicate uid 1.*pending"):
        q.submit(Req(1))
    q.take(1)
    q.finish(first)
    with pytest.raises(ValueError, match="duplicate uid 1.*done"):
        q.submit(Req(1))
    assert q.done[1] is first            # the first result survived intact
    clk = _Clock()
    q2 = RequestQueue(clock=clk)
    q2.submit(Req(7), deadline=1.0)
    clk.advance(2.0)
    q2.expire_overdue()
    with pytest.raises(ValueError, match="duplicate uid 7.*expired"):
        q2.submit(Req(7))


def test_edf_take_orders_by_deadline_with_fifo_tiebreak():
    clk = _Clock()
    q = RequestQueue(clock=clk)
    q.submit(Req(0))                     # no deadline: sorts last
    q.submit(Req(1), deadline=10.0)
    q.submit(Req(2), deadline=5.0)
    q.submit(Req(3), deadline=5.0)       # deadline tie with 2 -> FIFO
    assert [r.uid for r in q.take(10, order="edf")] == [2, 3, 1, 0]
    with pytest.raises(ValueError, match="unknown admission order"):
        q.take(1, order="lifo")


def test_slo_class_resolves_budget_at_submit():
    clk = _Clock(100.0)
    q = RequestQueue(clock=clk)
    q.submit(Req(1), slo="interactive")
    assert q.timing[1].deadline == pytest.approx(100.050)
    q.submit(Req(2), slo="batch")        # best-effort class: no deadline
    assert q.timing[2].deadline is None
    q.submit(Req(3), slo="standard", deadline=100.2)  # explicit wins
    assert q.timing[3].deadline == 100.2
    with pytest.raises(ValueError, match="unknown SLO class 'gold'"):
        q.submit(Req(4), slo="gold")
    gold = RequestQueue(clock=clk, slo_budgets={"gold": 2.0})
    gold.submit(Req(1), slo="gold")
    assert gold.timing[1].deadline == pytest.approx(102.0)


def test_expire_overdue_is_a_typed_rejection():
    from repro.serving.scheduler import Expired

    clk = _Clock()
    q = RequestQueue(clock=clk)
    late = Req(0)
    q.submit(late, deadline=1.0, slo=None)
    q.submit(Req(1), deadline=9.0)
    q.submit(Req(2))
    clk.advance(2.0)
    out = q.expire_overdue()
    assert [e.uid for e in out] == [0]
    e = q.expired[0]
    assert isinstance(e, Expired)
    assert (e.deadline, e.expired_at, e.request) == (1.0, 2.0, late)
    assert q.timing[0].expired == 2.0
    # expired is neither pending nor done -- a caller checking ``done``
    # finds the typed result instead of a silently vanished request
    assert [r.uid for r in q.pending] == [1, 2]
    assert 0 not in q.done


def test_microbatcher_step_expires_before_admission():
    """An overdue request is never padded into a batch and served late."""
    clk = _Clock()
    mb = Microbatcher(buckets=(4,), clock=clk)
    mb.submit(Req(0), np.zeros((1,), np.float32), deadline=1.0)
    mb.submit(Req(1), np.zeros((1,), np.float32), slo="batch")
    clk.advance(2.0)
    done = mb.step(lambda b: b)
    assert [r.uid for r, _ in done] == [1]
    assert list(mb.queue.expired) == [0]
    s = mb.stats()
    assert s["requests_expired"] == 1 and s["requests_done"] == 1


def test_service_estimate_borrows_flat_down_linear_up():
    mb = Microbatcher(buckets=(1, 4, 16))
    assert mb.service_estimate(4) is None          # no history at all
    mb.record_service(4, 0.2)
    assert mb.service_estimate(4) == pytest.approx(0.2)
    # downward: a smaller batch still pays the fixed dispatch cost
    assert mb.service_estimate(1) == pytest.approx(0.2)
    # upward: conservative linear scaling in batch rows
    assert mb.service_estimate(16) == pytest.approx(0.8)
    mb.record_service(4, 0.4)                       # window max, p99-flavored
    assert mb.service_estimate(4) == pytest.approx(0.4)


def test_select_batch_trades_padding_against_projected_time():
    clk = _Clock()
    mb = Microbatcher(buckets=(1, 4, 16), clock=clk)
    mb.record_service(1, 0.1)
    mb.record_service(4, 0.2)
    mb.record_service(16, 1.0)
    for uid in range(6):
        mb.submit(Req(uid), np.zeros((1,), np.float32))
    # no deadlines: best real-rows-per-projected-second wins
    # (1: 1/0.1=10/s, 4: 4/0.2=20/s, 16: 6/1.0=6/s)
    assert mb.select_batch() == (4, 4)
    # an urgent deadline rules out every bucket whose projection overruns
    # it: only bucket 1 (0.1s) lands before t=0.15
    mb.submit(Req(99), np.zeros((1,), np.float32), deadline=0.15)
    assert mb.select_batch() == (1, 1)


def test_select_batch_unmeetable_deadline_takes_fastest_bucket():
    """When NO bucket's projection meets the urgent deadline, minimize how
    late it is: fastest projected bucket, not max throughput."""
    clk = _Clock()
    mb = Microbatcher(buckets=(1, 4, 16), clock=clk)
    mb.record_service(1, 0.5)    # bucket 1 measured SLOWER than bucket 4
    mb.record_service(4, 0.2)
    mb.record_service(16, 1.0)
    mb.submit(Req(0), np.zeros((1,), np.float32), deadline=0.05)
    mb.submit(Req(1), np.zeros((1,), np.float32))
    assert mb.select_batch() == (4, 2)


def test_select_batch_without_history_degenerates_to_smallest_fit():
    mb = Microbatcher(buckets=(1, 4, 16))
    for uid in range(3):
        mb.submit(Req(uid), np.zeros((1,), np.float32),
                  deadline=float(uid + 1))
    assert mb.select_batch() == (select_bucket(3, mb.buckets), 3) == (4, 3)


def test_step_admits_urgent_late_submitter_first():
    """EDF through the serve loop: a tight-deadline request submitted LAST
    overtakes the deadline-less backlog when the bucket can't take all."""
    clk = _Clock()
    mb = Microbatcher(buckets=(2,), clock=clk)
    for uid in range(3):
        mb.submit(Req(uid), np.full((1,), uid, np.float32))
    mb.submit(Req(9), np.full((1,), 9, np.float32), deadline=1.0)
    done = mb.step(lambda b: b)
    assert [r.uid for r, _ in done] == [9, 0]       # urgent first, then FIFO
    assert [r.uid for r in mb.queue.pending] == [1, 2]


def test_requeue_after_failure_keeps_deadline_discipline():
    """A failed forward re-queues its admitted requests; the NEXT admission
    re-ranks by deadline, so an urgent request submitted during the failure
    window still overtakes the requeued batch."""
    clk = _Clock()
    mb = Microbatcher(buckets=(2,), clock=clk)
    mb.submit(Req(0), np.zeros((1,), np.float32))
    mb.submit(Req(1), np.zeros((1,), np.float32), deadline=5.0)
    with pytest.raises(RuntimeError, match="boom"):
        mb.step(lambda b: (_ for _ in ()).throw(RuntimeError("boom")))
    assert [r.uid for r in mb.queue.pending] == [1, 0]   # EDF take order
    mb.submit(Req(2), np.zeros((1,), np.float32), deadline=1.0)
    done = mb.step(lambda b: b)
    assert [r.uid for r, _ in done] == [2, 1]
    # deadlines survive the requeue: timing entries were never cleared
    assert mb.queue.timing[1].deadline == 5.0


def test_goodput_counts_only_in_deadline_completions():
    """A request served but finished PAST its deadline is a deadline miss:
    it counts in throughput, not goodput."""
    clk = _FakeClock()                   # +0.5 per reading
    mb = Microbatcher(buckets=(1,), clock=clk.tick)
    # two clock reads happen at submit time; the step's expire check reads
    # 1.5, admission 2.0 and completion 3.5 -- a 2.4 deadline is therefore
    # alive at admission but already gone when the batch finishes
    mb.submit(Req(0), np.zeros((1,), np.float32), deadline=2.4)
    mb.submit(Req(1), np.zeros((1,), np.float32))
    mb.run(lambda b: b)
    assert mb.queue.expired == {}        # 0 was admitted before overdue
    assert mb.queue.timing[0].met_deadline is False
    assert mb.queue.timing[1].met_deadline is None
    s = mb.stats()
    assert s["deadline_misses"] == 1
    assert s["throughput_rps"] > s["goodput_rps"] > 0
    assert s["latency_p50_s"] <= s["latency_p99_s"]


def test_urgency_and_next_deadline():
    clk = _Clock(10.0)
    q = RequestQueue(clock=clk)
    assert q.urgency() == (float("inf"), float("inf"))
    assert q.next_deadline() is None
    q.submit(Req(0))
    assert q.urgency() == (float("inf"), 10.0)
    clk.advance(1.0)
    q.submit(Req(1), deadline=20.0)
    assert q.next_deadline() == 20.0
    assert q.urgency() == (20.0, 10.0)
