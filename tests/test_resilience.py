"""End-to-end resilience: chaos through the REAL engines (DESIGN.md 9.8).

The scheduler/fault suites prove the mechanics with stubs; these run the
actual serving engines under seeded fault plans and hold the two headline
contracts from ISSUE 9:

  * **Conservation** -- under any fault plan,
    ``done + expired + failed == submitted``: no request is ever silently
    lost, whatever mix of retries, bisections, quarantines and health
    transitions the faults provoke.
  * **Exactness** -- a request that succeeds after retries (or on a
    degraded engine) has logits BITWISE identical to a fault-free run,
    for both integer policies.  This is the substrate's batch-invariance
    contract doing resilience work: retries re-batch requests
    arbitrarily, and degraded mode reroutes the plan, but under the
    integer policies neither can move a single bit.

Plus the dispatcher fault-isolation satellite and the grep contract that
serving/retry code never calls ``time.sleep``/``time.monotonic()``.
"""
import dataclasses
import pathlib
import re

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.precision import MatmulPolicy
from repro.models.cnn import cnn_init
from repro.serving.cnn_engine import CNNServeEngine, ImageRequest
from repro.serving.faults import FaultInjector, FaultPlan
from repro.serving.scheduler import (EngineDownError, Failed, RequestQueue,
                                     RetryPolicy)

SRC = pathlib.Path(__file__).resolve().parent.parent / "src"


class _Clock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance_to(self, target: float) -> None:
        self.t = max(self.t, target)


def _cnn_cfg(policy=MatmulPolicy.KOM_INT14, conv_path="im2col"):
    return reduced(get_config("alexnet")).replace(
        policy=policy, conv_path=conv_path)


def _imgs(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(
        (cfg.img_size, cfg.img_size, cfg.in_channels)).astype(np.float32)
        for _ in range(n)]


def _conserved(q: RequestQueue) -> bool:
    return (len(q.done) + len(q.expired) + len(q.failed)
            == q.submitted_count)


# -- CNN engine under chaos: conservation + bitwise exactness ---------------

@pytest.mark.parametrize("policy", [MatmulPolicy.KOM_INT14,
                                    MatmulPolicy.SCHOOLBOOK_INT16])
def test_cnn_chaos_conserves_and_retried_logits_bitwise(policy):
    cfg = _cnn_cfg(policy)
    params = cnn_init(cfg, jax.random.PRNGKey(0))
    n = 8
    imgs = _imgs(cfg, n)

    # fault-free reference
    ref = CNNServeEngine(cfg, params, buckets=(1, 4))
    for uid in range(n):
        ref.submit(ImageRequest(uid=uid, image=imgs[uid]))
    ref_done = ref.run()

    # chaos: every request faults transiently once, uid 3 is poison
    clk = _Clock()
    plan = FaultPlan(seed=1, transient_rate=1.0, transient_fails=1,
                     poison_uids=(3,))
    # max_attempts=6: innocents in a poisoned batch burn attempts before
    # bisection corners the poison; the budget must outlast the split depth
    eng = CNNServeEngine(cfg, params, buckets=(1, 4), clock=clk,
                         faults=plan, advance=clk.advance_to,
                         retry=RetryPolicy(max_attempts=6,
                                           backoff_base=0.001))
    for uid in range(n):
        eng.submit(ImageRequest(uid=uid, image=imgs[uid]))
    done = eng.run()

    q = eng.batcher.queue
    assert _conserved(q)
    assert sorted(done) == [u for u in range(n) if u != 3]
    assert list(q.failed) == [3]
    assert isinstance(q.failed[3], Failed)
    assert q.failed[3].attempts >= 3
    assert eng.stats()["retries"] > 0
    # retried-successful requests: logits bitwise equal to fault-free run
    for uid in done:
        assert np.array_equal(done[uid].logits, ref_done[uid].logits), uid
    assert eng.health == "healthy"   # transient/poison don't degrade


def test_cnn_degraded_mode_stays_bitwise_then_goes_down():
    """OOM ladder: drop the largest bucket, then reroute the plan to the
    materialized fallback (still bitwise under int policies), then down
    with everything failed typed."""
    cfg = _cnn_cfg(conv_path="auto")     # plan-resolved engine
    params = cnn_init(cfg, jax.random.PRNGKey(0))
    n = 4
    imgs = _imgs(cfg, n)

    ref = CNNServeEngine(cfg, params, buckets=(1, 4))
    for uid in range(n):
        ref.submit(ImageRequest(uid=uid, image=imgs[uid]))
    ref_done = ref.run()

    clk = _Clock()
    eng = CNNServeEngine(cfg, params, buckets=(1, 4), clock=clk,
                         retry=RetryPolicy(max_attempts=10,
                                           backoff_base=0.001),
                         advance=clk.advance_to)
    oom = [2]     # two OOMs: bucket 4 dropped, then plan rerouted
    real = eng._run_batch

    def flaky(batch):
        if oom[0]:
            oom[0] -= 1
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")
        return real(batch)

    eng._serve_fn = flaky
    for uid in range(n):
        eng.submit(ImageRequest(uid=uid, image=imgs[uid]))
    done = eng.run()

    assert eng.health == "degraded"
    assert eng.buckets == (1,)                   # largest bucket retired
    assert eng._fallback_plan_active
    assert all(e.path == "im2col" for e in eng.plan.entries)
    assert sorted(done) == list(range(n))
    assert _conserved(eng.batcher.queue)
    # degraded-mode serving is bitwise identical: exact-or-reroute
    for uid in done:
        assert np.array_equal(done[uid].logits, ref_done[uid].logits), uid

    # nothing left to shed: the next OOM downs the engine, typed
    eng.submit(ImageRequest(uid=100, image=imgs[0]))
    oom[0] = 10
    eng.run()
    assert eng.health == "down"
    assert 100 in eng.failed
    assert isinstance(eng.failed[100], Failed)
    assert _conserved(eng.batcher.queue)
    with pytest.raises(EngineDownError, match="down"):
        eng.submit(ImageRequest(uid=101, image=imgs[0]))


def test_cnn_poison_among_staged_payloads_is_bisected_and_quarantined():
    """Payloads staged on the device at submit, and past the staging limit
    at admission: the poison request's batch is bisected, the poison is
    quarantined holding no payload, and every batch-mate is served with
    logits bitwise equal to a fault-free run."""
    cfg = _cnn_cfg()
    params = cnn_init(cfg, jax.random.PRNGKey(0))
    n, poison = 12, 9      # bucket 4: the stage limit is 8, uids 8..11 late
    imgs = _imgs(cfg, n)

    ref = CNNServeEngine(cfg, params, buckets=(4,))
    for uid in range(n):
        ref.submit(ImageRequest(uid=uid, image=imgs[uid]))
    ref_done = ref.run()

    clk = _Clock()
    eng = CNNServeEngine(cfg, params, buckets=(4,), clock=clk,
                         faults=FaultPlan(seed=2, poison_uids=(poison,)),
                         advance=clk.advance_to,
                         retry=RetryPolicy(max_attempts=6, backoff_base=0.001))
    for uid in range(n):
        eng.submit(ImageRequest(uid=uid, image=imgs[uid]))
    done = eng.run()

    q = eng.batcher.queue
    assert _conserved(q)
    assert sorted(done) == [u for u in range(n) if u != poison]
    assert list(q.failed) == [poison]
    assert not hasattr(q.failed[poison].request, "_payload")
    s = eng.stats()
    assert s["bisections"] > 0 and s["quarantined"] == 1
    # every retry of a batch puts its late rows on the device again
    assert s["staged_rows"] == 8 and s["late_rows"] >= 4
    for uid in done:
        assert np.array_equal(done[uid].logits, ref_done[uid].logits), uid


# -- LM engine under chaos ---------------------------------------------------

def test_lm_engine_retries_and_quarantines():
    from repro.models import transformer
    from repro.serving.engine import Request, ServeEngine

    cfg = reduced(get_config("granite-3-2b"))
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, (3,)).astype(np.int32)
               for _ in range(3)]

    ref = ServeEngine(cfg, params, slots=2, max_len=32)
    for uid in range(3):
        ref.submit(Request(uid=uid, prompt=prompts[uid], max_new_tokens=4))
    ref_done = ref.run()

    clk = _Clock()
    plan = FaultPlan(seed=2, transient_rate=1.0, transient_fails=1,
                     poison_uids=(1,))
    eng = ServeEngine(cfg, params, slots=2, max_len=32, clock=clk,
                      faults=plan, advance=clk.advance_to,
                      retry=RetryPolicy(max_attempts=3, backoff_base=0.001))
    for uid in range(3):
        eng.submit(Request(uid=uid, prompt=prompts[uid], max_new_tokens=4))
    done = eng.run()

    assert _conserved(eng.request_queue)
    assert sorted(done) == [0, 2]
    assert list(eng.failed) == [1]
    assert eng.failed[1].attempts >= 3
    assert eng.stats()["retries"] > 0
    # greedy decode: retried requests emit the same tokens as fault-free
    for uid in done:
        assert done[uid].out_tokens == ref_done[uid].out_tokens, uid


def test_lm_engine_oom_halves_slot_cap_then_downs():
    from repro.models import transformer
    from repro.serving.engine import Request, ServeEngine

    cfg = reduced(get_config("granite-3-2b"))
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    clk = _Clock()
    eng = ServeEngine(cfg, params, slots=4, max_len=32, clock=clk,
                      retry=RetryPolicy(max_attempts=20,
                                        backoff_base=0.001),
                      advance=clk.advance_to)
    rng = np.random.default_rng(0)
    eng.submit(Request(uid=0, prompt=rng.integers(
        1, cfg.vocab_size, (3,)).astype(np.int32), max_new_tokens=2))

    oom = [2]
    real = eng._decode

    def flaky(*a, **kw):
        if oom[0]:
            oom[0] -= 1
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")
        return real(*a, **kw)

    eng._decode = flaky
    done = eng.run()
    assert eng.health == "degraded"
    assert eng._slot_cap == 1                   # 4 -> 2 -> 1
    assert sorted(done) == [0]                  # still served, degraded
    assert _conserved(eng.request_queue)

    eng.submit(Request(uid=1, prompt=rng.integers(
        1, cfg.vocab_size, (3,)).astype(np.int32), max_new_tokens=2))
    oom[0] = 10
    eng.run()
    assert eng.health == "down"
    assert 1 in eng.failed
    assert _conserved(eng.request_queue)
    with pytest.raises(EngineDownError):
        eng.submit(Request(uid=2, prompt=np.asarray([1, 2], np.int32)))


# -- dispatcher fault isolation (satellite) ----------------------------------

@dataclasses.dataclass
class Req:
    uid: int


class FakeEngine:
    """One request per step on the real queue; optionally explodes."""

    def __init__(self, clock, explode=None):
        self._rq = RequestQueue(clock=clock)
        self.health = "healthy"
        self.explode = explode
        self.served = []

    def submit(self, req, **kw):
        if self.health == "down":
            raise EngineDownError("down")
        self._rq.submit(req, deadline=kw.get("deadline"))

    def has_work(self):
        return bool(len(self._rq))

    def urgency(self):
        return self._rq.urgency()

    def step(self):
        if self.explode:
            raise self.explode
        for req in self._rq.take(1, order="edf"):
            self._rq.finish(req)
            self.served.append(req.uid)

    def mark_down(self, reason="down"):
        self.health = "down"
        return self._rq.fail_pending(EngineDownError(reason))

    @property
    def request_queue(self):
        return self._rq


def test_dispatcher_contains_engine_failure_without_stranding_others():
    """One engine raising mid-run is marked down (its requests failed
    TYPED); the other engine's requests all serve -- never stranded, never
    crash-looped."""
    from repro.serving.dispatcher import MultiModelDispatcher

    clk = _Clock()
    disp = MultiModelDispatcher()
    bad = FakeEngine(clk, explode=RuntimeError("engine exploded"))
    good = FakeEngine(clk)
    disp.register("bad", bad)
    disp.register("good", good)
    for uid in range(3):
        disp.submit("bad", Req(uid))
        disp.submit("good", Req(100 + uid))

    done = disp.run()
    assert sorted(done["good"]) == [100, 101, 102]
    assert bad.health == "down"
    assert sorted(bad.request_queue.failed) == [0, 1, 2]
    s = disp.stats()
    assert s["health"] == {"bad": "down", "good": "healthy"}
    assert "bad" in s["contained"]
    assert s["requests_done"] == 3 and s["requests_failed"] == 3
    # fleet conservation across engines
    assert s["requests_done"] + s["requests_expired"] \
        + s["requests_failed"] == 6


def test_dispatcher_fatal_errors_still_propagate():
    from repro.serving.dispatcher import MultiModelDispatcher

    clk = _Clock()
    disp = MultiModelDispatcher()
    disp.register("a", FakeEngine(clk, explode=KeyboardInterrupt()))
    disp.submit("a", Req(0))
    with pytest.raises(KeyboardInterrupt):
        disp.step()


def test_dispatcher_skips_down_engine_on_submit_and_dispatch():
    from repro.serving.dispatcher import MultiModelDispatcher

    clk = _Clock()
    disp = MultiModelDispatcher()
    a, b = FakeEngine(clk), FakeEngine(clk)
    disp.register("a", a)
    disp.register("b", b)
    disp.submit("a", Req(0))
    disp.submit("b", Req(1))
    a.mark_down()
    assert disp.next_model() == "b"
    disp.run()
    assert b.served == [1]
    assert sorted(a.request_queue.failed) == [0]


def test_dispatcher_stranded_uids_stay_model_qualified():
    """IncompleteRunError out of a truncated multi-model run names every
    stranded request as model:uid -- uid collisions across models stay
    distinguishable."""
    from repro.serving.dispatcher import MultiModelDispatcher
    from repro.serving.scheduler import IncompleteRunError

    clk = _Clock()
    disp = MultiModelDispatcher()
    disp.register("x", FakeEngine(clk))
    disp.register("y", FakeEngine(clk))
    disp.submit("x", Req(7))
    disp.submit("y", Req(7))      # same uid, different model
    with pytest.raises(IncompleteRunError) as ei:
        disp.run(max_steps=1)
    assert set(ei.value.pending_uids) == {"y:7"}  # x:7 served first step


# -- grep contract: all waiting goes through the injected clock --------------

def test_no_direct_sleep_or_monotonic_calls_in_serving_paths():
    """Retry backoff and fault timing must run on the injected ``clock=``
    (the loadgen warp clock in benchmarks, fake clocks in tests) -- a
    single ``time.sleep``/``time.monotonic()`` CALL in the serving/retry
    path would silently decouple them.  References like the
    ``clock=time.monotonic`` default are fine; calls are not.  Same
    single-definition grep discipline as the scheduler's FIFO-pop test.
    """
    targets = sorted((SRC / "repro" / "serving").glob("*.py"))
    targets.append(SRC.parent / "benchmarks" / "loadgen.py")
    assert len(targets) >= 6
    bad = []
    call = re.compile(r"\btime\.(?:sleep|monotonic)\s*\(")
    for path in targets:
        for i, line in enumerate(path.read_text().splitlines(), 1):
            if call.search(line):
                bad.append(f"{path.name}:{i}: {line.strip()}")
    assert not bad, "direct time.* calls in serving paths:\n" + "\n".join(bad)
