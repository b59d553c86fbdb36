"""The span hook inside the CNN serving step (``repro.serving.spans``).

Off (no recorder): a served step reads no clock and records nothing.  On:
each step records its phases, nested under the caller's span and in order;
failed forwards leave no span open; compiles and collections become spans.
The forward is stubbed, so only the host path runs.
"""
import gc
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.precision import MatmulPolicy
from repro.models.cnn import ALEXNET, VGG16, cnn_init, cnn_layer_names, cnn_layer_scopes
from repro.serving import spans
from repro.serving.cnn_engine import CNNServeEngine, ImageRequest
from repro.serving.scheduler import RetryPolicy

PHASES = ["batch.admit", "batch.stack", "engine.to_device", "engine.forward",
          "engine.from_device", "batch.finish"]


@pytest.fixture(autouse=True)
def _no_recorder():
    spans.uninstall()
    yield
    spans.uninstall()


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance_to(self, target):
        self.t = max(self.t, target)


def _engine(buckets=(1, 4), forward=None, **kw):
    """A reduced AlexNet engine whose forward is a stub (no compile); the
    stub gets the bucket's rows, a tuple on one device."""
    cfg = reduced(get_config("alexnet")).replace(policy=MatmulPolicy.KOM_INT14)
    eng = CNNServeEngine(cfg, cnn_init(cfg, jax.random.PRNGKey(0)),
                         buckets=buckets, **kw)
    n = cfg.n_classes
    eng._forward = forward or (lambda params, x: jnp.zeros((len(x), n)))
    return eng


def _submit(eng, uids):
    h, c = eng.cfg.img_size, eng.cfg.in_channels
    for u in uids:
        eng.submit(ImageRequest(uid=u, image=np.full((h, h, c), u, np.float32)))


def test_no_recorder_reads_no_clock(monkeypatch):
    eng = _engine()
    calls = []
    fake_time = SimpleNamespace(monotonic_ns=lambda: calls.append(1) or 0)
    monkeypatch.setattr(spans, "time", fake_time)
    _submit(eng, [0, 1, 2])
    with spans.span("engine.step"):
        done = eng.step()
    assert len(done) == 3
    assert calls == []
    assert spans.uninstall() is None            # nothing was installed
    # one shared no-op: nothing allocated per phase
    assert spans.span("batch.admit") is spans.span("engine.forward")


def test_step_records_six_phases_in_order_under_one_parent():
    eng = _engine()
    _submit(eng, [0, 1, 2])
    rec = spans.install(spans.Recorder())
    with spans.span("engine.step"):
        eng.step()
    spans.uninstall()
    step = [i for i, s in enumerate(rec.spans) if s.name == "engine.step"]
    assert len(step) == 1
    children = [s for s in rec.spans if s.parent == step[0]]
    names = [s.name for s in children]
    assert list(dict.fromkeys(names)) == PHASES
    assert all(s.t1 is not None and not s.error for s in rec.spans)
    # children are disjoint, in order, and inside their parent
    parent = rec.spans[step[0]]
    edges = [parent.t0] + [t for s in children for t in (s.t0, s.t1)] + [parent.t1]
    assert edges == sorted(edges)
    assert rec.stack == []


def test_failed_forward_closes_its_spans_and_flags_them():
    """A poison request: the batch fails, is bisected, the culprit retried
    alone until quarantined.  Every span closes; each failed forward is
    flagged, each good one is not."""
    def forward(params, x):
        if bool((np.asarray(x) == 3).any()):
            raise RuntimeError("poison row")
        return jnp.zeros((len(x), 16))

    clock = Clock()
    eng = _engine(buckets=(1, 2, 4), forward=forward, clock=clock,
                  retry=RetryPolicy(max_attempts=2, backoff_base=0.001,
                                    bisect_after=1),
                  advance=clock.advance_to)
    _submit(eng, [0, 1, 2, 3])
    rec = spans.install(spans.Recorder())
    with spans.span("engine.step"):
        done = eng.step()
    spans.uninstall()
    assert sorted(r.uid for r in done) == [0, 1, 2]
    assert list(eng.failed) == [3]
    assert rec.stack == []
    assert all(s.t1 is not None for s in rec.spans)
    fwd = [s for s in rec.spans if s.name == "engine.forward"]
    failed = [s for s in fwd if s.error]
    assert failed and len(fwd) > len(failed)
    # a failed forward never reached the copy back
    assert sum(s.name == "engine.from_device" for s in rec.spans) \
        == len(fwd) - len(failed)
    assert not rec.spans[0].error          # the caller's span caught nothing


def test_new_bucket_shape_adds_one_compile_span():
    jitted = jax.jit(lambda params, x: jnp.stack(x)[:, :16])
    eng = _engine(buckets=(1, 4), forward=jitted)
    _submit(eng, [0])
    eng.step()                                  # bucket 1 compiled here
    rec = spans.install(spans.Recorder())
    _submit(eng, [1])
    eng.step()                                  # steady state: a cache hit
    assert rec.counts["jax.compile"] == 0
    _submit(eng, [2, 3, 4, 5])
    eng.step()                                  # bucket 4: a new shape
    spans.uninstall()
    compiles = [s for s in rec.spans if s.name == "jax.compile"]
    assert len(compiles) == 1 and rec.counts["jax.compile"] == 1
    fwd = [i for i, s in enumerate(rec.spans) if s.name == "engine.forward"]
    assert compiles[0].parent == fwd[-1]        # it ran inside that forward
    assert compiles[0].t0 <= compiles[0].t1


def test_gc_pause_becomes_a_span():
    rec = spans.install(spans.Recorder())
    with spans.span("engine.step"):
        gc.collect()
    spans.uninstall()
    pauses = [s for s in rec.spans if s.name == "python.gc"]
    assert pauses and rec.counts["python.gc"] == len(pauses)
    assert pauses[0].parent == 0
    # uninstalled: collections are no longer recorded
    gc.collect()
    assert rec.counts["python.gc"] == len(pauses)


def test_engine_build_and_warmup_spans():
    cfg = reduced(get_config("alexnet")).replace(policy=MatmulPolicy.KOM_INT14)
    params = cnn_init(cfg, jax.random.PRNGKey(0))
    rec = spans.install(spans.Recorder())
    eng = CNNServeEngine(cfg, params, buckets=(1,))
    eng._forward = lambda p, x: jnp.zeros((len(x), cfg.n_classes))
    eng.warmup()
    spans.uninstall()
    ours = [(i, s) for i, s in enumerate(rec.spans)
            if s.name not in ("python.gc", "jax.compile")]
    assert [s.name for _, s in ours if s.parent is None] == [
        "engine.quantize_weights", "engine.plan", "engine.jit", "engine.warmup"]
    warm = next(i for i, s in ours if s.name == "engine.warmup")
    assert [s.name for _, s in ours if s.parent == warm] == [
        "engine.warmup.b1.first", "engine.warmup.b1.timed"]


def test_layer_names_follow_the_papers():
    assert cnn_layer_names(ALEXNET) == [
        "conv1", "pool1", "conv2", "pool2", "conv3", "conv4", "conv5", "pool5",
        "fc6", "fc7", "fc8"]
    names = cnn_layer_names(VGG16)
    assert names[:6] == ["conv1_1", "conv1_2", "pool1", "conv2_1", "conv2_2",
                         "pool2"]
    assert names[-5:] == ["conv5_3", "pool5", "fc6", "fc7", "fc8"]
    assert cnn_layer_scopes(VGG16)[1] == "l01.conv1_2"
    assert len(set(cnn_layer_scopes(VGG16))) == len(VGG16.layers)


def test_named_scopes_reach_the_lowered_hlo():
    cfg = reduced(get_config("alexnet")).replace(policy=MatmulPolicy.KOM_INT14)
    eng = CNNServeEngine(cfg, cnn_init(cfg, jax.random.PRNGKey(0)), buckets=(1,))
    x = jnp.zeros((1, cfg.img_size, cfg.img_size, cfg.in_channels))
    text = eng._forward.lower(eng.params, x).as_text(debug_info=True)
    for scope in cnn_layer_scopes(cfg):
        if scope.split(".")[1].startswith("pool"):
            continue        # a pool fused into its conv's epilogue has no ops
        assert f"/{scope}/" in text, scope


def test_collections_inside_the_hook_keep_every_span_whole():
    """A collection triggered by the hook's own allocation records its span
    first; the span being opened must still close (gen-0 threshold 1 makes
    nearly every allocation collect)."""
    eng = _engine()
    _submit(eng, [0, 1, 2])
    rec = spans.install(spans.Recorder())
    old = gc.get_threshold()
    gc.set_threshold(1, 1, 1)
    try:
        with spans.span("engine.step"):
            eng.step()
    finally:
        gc.set_threshold(*old)
        spans.uninstall()
    assert rec.counts["python.gc"] > 0
    assert all(s.t1 is not None for s in rec.spans)
    ours = [s.name for s in rec.spans if s.name != "python.gc"]
    assert list(dict.fromkeys(ours)) == ["engine.step"] + PHASES
