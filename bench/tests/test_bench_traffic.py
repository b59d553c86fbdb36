"""The traffic kinds and the loop that drives them, against a stub engine."""
import json
import types

import numpy as np
import pytest

import loadgen
from conftest import BENCH

poisson_offsets = loadgen.kind("poisson").offsets


def test_poisson_offsets_deterministic_and_same_set():
    a = poisson_offsets(100.0, 20.0, seed=2**33 + 1)
    b = poisson_offsets(100.0, 20.0, seed=2**33 + 1)
    c = poisson_offsets(100.0, 20.0, seed=5)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    # the same gaps in another order (the last one runs to the window's end)
    gaps = lambda off: np.sort(np.diff(np.append(off, 20.0)))
    np.testing.assert_allclose(gaps(a), gaps(c), rtol=1e-9)
    assert len(a) == 2000 and a[0] == 0.0 and a[-1] < 20.0


def test_poisson_mean_rate_and_exponential_shape():
    rate = 80.0
    off = poisson_offsets(rate, 30.0, seed=11)
    gaps = np.diff(off)
    assert len(off) / 30.0 == pytest.approx(rate)
    # exponential: mean 1/rate, coefficient of variation 1
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.02)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.05)


@pytest.mark.parametrize("name", ["offline", "sync1", "poisson"])
def test_traffic_files_are_valid(name):
    loadgen.check(json.loads((BENCH / "traffic" / f"{name}.json").read_text()))


@pytest.mark.parametrize("name", ["zipf", None, "../loadgen"])
def test_unknown_kind_refused(name):
    with pytest.raises(ValueError):
        loadgen.check({"kind": name, "buckets": [1]})


def test_a_new_kind_is_a_new_file(tmp_path, monkeypatch):
    """A kind module dropped beside the others is found by its name alone."""
    (tmp_path / "pairs.py").write_text(
        "def check(traffic):\n"
        "    pass\n"
        "def run(loop, traffic, seconds, seed):\n"
        "    loop.w.t0 = loop.clock()\n"
        "    for _ in range(traffic['pairs']):\n"
        "        loop.submit(loop.clock())\n"
        "        loop.submit(loop.clock())\n"
        "        loop.step()\n"
        "    loop.w.t_end = loop.w.t_last\n")
    monkeypatch.setattr(loadgen, "KINDS_DIR", tmp_path)
    traffic = loadgen.check({"kind": "pairs", "pairs": 3, "buckets": [2]})
    eng, w = drive(traffic)
    assert loadgen.images_done(w) == 6 and w.steps == 3


class StubEngine:
    """Bucketed engine stand-in: serves up to the largest bucket per step."""

    def __init__(self, buckets, step_s=0.002):
        from repro.serving.scheduler import Microbatcher
        self.batcher = Microbatcher(buckets)
        self.step_s = step_s

    @property
    def request_queue(self):
        return self.batcher.queue

    def submit(self, req):
        self.batcher.submit(req, req.image, deadline=req.deadline, slo=req.slo)

    def step(self):
        def fwd(batch):
            t = loadgen.time.monotonic() + self.step_s
            while loadgen.time.monotonic() < t:
                pass
            return batch.reshape(len(batch), -1)[:, :4] * 1.0
        out = []
        for req, row in self.batcher.step(fwd):
            req.logits = row
            out.append(req)
        return out


def req_cls(**kw):
    return types.SimpleNamespace(logits=None, **kw)


def drive(traffic, seconds=0.3, step_s=0.002):
    eng = StubEngine(traffic["buckets"], step_s)
    images = np.arange(8 * 4, dtype=np.float32).reshape(8, 2, 2, 1)
    w = loadgen.drive(eng, traffic, images, seconds, seed=3, request_cls=req_cls,
                      keep=lambda uid: uid < 8,
                      span=lambda name: __import__("contextlib").nullcontext())
    return eng, w


def test_closed_backlog_stays_full_and_window_ends_on_a_step():
    eng, w = drive({"kind": "closed", "clients": 8, "buckets": [4], "slo": "batch"})
    assert w.pending_peak == 8                  # refilled to 8 before every step
    assert set(eng.batcher.bucket_counts) == {4}
    assert w.t_end == max(w.completed.values())  # closed at a step boundary
    assert loadgen.images_done(w) == len(w.due) == 4 * w.steps
    assert loadgen.outstanding(w) == 0
    np.testing.assert_array_equal(w.kept[0], [0, 1, 2, 3])


def test_sync_caller_has_one_request_outstanding():
    eng, w = drive({"kind": "closed", "clients": 1, "buckets": [1],
                    "slo": "interactive"})
    assert w.pending_peak == 1
    assert loadgen.images_done(w) == w.steps == len(w.due)
    lat = loadgen.latencies_ms(w)
    assert min(lat) >= 2.0                      # at least one stub step


def test_poisson_drains_every_arrival_and_times_from_due():
    traffic = {"kind": "poisson", "rate_per_s": 200.0, "buckets": [1, 4],
               "slo": "standard", "budget_s": 0.5}
    eng, w = drive(traffic, seconds=0.5)
    assert loadgen.attempted(w) == 100
    assert loadgen.images_done(w) == 100 and not w.failed
    assert all(w.completed[u] >= w.due[u] for u in w.due)
    assert w.t_end == pytest.approx(w.t0 + 0.5)


def test_expired_requests_count_as_failed_with_their_latency():
    traffic = {"kind": "poisson", "rate_per_s": 400.0, "buckets": [1],
               "slo": "standard", "budget_s": 0.005}
    eng, w = drive(traffic, seconds=0.25, step_s=0.004)
    assert w.failed                               # an overloaded queue expires
    assert loadgen.outstanding(w) == 0
    assert len(loadgen.latencies_ms(w)) == loadgen.attempted(w)
