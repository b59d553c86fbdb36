"""Images staged on the device at submit, batches assembled in the forward.

A :class:`CNNServeEngine` puts each image on the device, flat, when it is
submitted (or, past ``2 * max(buckets)`` pending, when it is admitted), and
its jitted forward stacks the bucket's rows.  The logits must be bitwise
those of ``cnn_forward`` on the host-stacked batch; the staging limit must
hold; no step after ``warmup`` may compile; a request that leaves the queue
by any door keeps no payload; a mesh engine takes the same path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.precision import MatmulPolicy
from repro.models.cnn import cnn_forward, cnn_init
from repro.serving import spans
from repro.serving.cnn_engine import CNNServeEngine, ImageRequest
from repro.serving.scheduler import RetryPolicy


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


def _cfg(arch="alexnet"):
    return reduced(get_config(arch)).replace(policy=MatmulPolicy.KOM_INT14)


def _images(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal(
        (n, cfg.img_size, cfg.img_size, cfg.in_channels)).astype(np.float32)


def _stub_engine(buckets=(1, 4), forward=None, **kw):
    """A reduced AlexNet engine whose forward is a stub (no compile)."""
    cfg = _cfg()
    eng = CNNServeEngine(cfg, cnn_init(cfg, jax.random.PRNGKey(0)),
                         buckets=buckets, **kw)
    eng._forward = forward or (
        lambda params, x: jnp.zeros((len(x), cfg.n_classes)))
    return eng


def _submit(eng, uids, **kw):
    h, c = eng.cfg.img_size, eng.cfg.in_channels
    for u in uids:
        eng.submit(ImageRequest(uid=u, image=np.full((h, h, c), u, np.float32),
                                **kw))


def _staged_pending(eng) -> int:
    return sum(isinstance(r._payload, jax.Array)
               for r in eng.request_queue.pending)


@pytest.mark.parametrize("bucket", [1, 4, 16])
@pytest.mark.parametrize("arch", ["alexnet", "vgg16"])
def test_served_logits_bitwise_equal_the_host_stacked_forward(arch, bucket):
    """A full batch, then a partial one whose pad slots hold the shared
    zero image: each row bitwise equal to ``cnn_forward`` on the batch
    stacked and zero-padded on the host."""
    cfg = _cfg(arch)
    eng = CNNServeEngine(cfg, cnn_init(cfg, jax.random.PRNGKey(0)),
                         buckets=(bucket,))
    n = (3 * bucket + 1) // 2
    imgs = _images(cfg, n, seed=bucket)
    for uid in range(n):
        eng.submit(ImageRequest(uid=uid, image=imgs[uid]))
    done = eng.run()
    assert sorted(done) == list(range(n))
    assert eng.batcher.padded_rows == 2 * bucket - n
    assert eng.stats()["staged_rows"] == n

    ref = jax.jit(lambda p, x: cnn_forward(p, cfg, x, plan=eng.plan))
    for lo in range(0, n, bucket):
        rows = imgs[lo:lo + bucket]
        pad = np.zeros((bucket - len(rows),) + rows.shape[1:], np.float32)
        want = np.asarray(ref(eng.params, jnp.asarray(
            np.concatenate([rows, pad]))))
        for i in range(len(rows)):
            np.testing.assert_array_equal(done[lo + i].logits, want[i],
                                          err_msg=f"{arch} request {lo + i}")


def test_staging_limit_holds_and_every_request_is_served():
    """A backlog of three times the limit, then a closed loop of as many
    callers as the limit: the backlog past the limit is staged late, the
    loop's refills at submit; at no time are more pending rows staged."""
    eng = _stub_engine(buckets=(1, 4))
    limit = eng.stage_limit
    assert limit == 8
    _submit(eng, range(3 * limit))
    assert _staged_pending(eng) == limit == eng.stats()["staged_rows"]
    served = 0
    while eng.has_work():
        served += len(eng.step())
        assert _staged_pending(eng) <= limit
    assert eng.stats()["late_rows"] == 2 * limit
    _submit(eng, range(3 * limit, 4 * limit))
    uid = 4 * limit
    while eng.has_work():
        done = eng.step()
        served += len(done)
        if uid < 6 * limit:                 # refill as a closed loop does
            _submit(eng, range(uid, uid + len(done)))
            uid += len(done)
        assert _staged_pending(eng) <= limit
    s = eng.stats()
    assert served == s["images_done"] == uid
    assert (s["staged_rows"], s["late_rows"]) == (4 * limit, 2 * limit)


def test_no_compile_after_warmup_whatever_the_row_count():
    cfg = _cfg()
    eng = CNNServeEngine(cfg, cnn_init(cfg, jax.random.PRNGKey(0)),
                         buckets=(1, 4))
    eng.warmup()
    imgs = _images(cfg, 3 * eng.stage_limit)
    uid = 0
    rec = spans.install(spans.Recorder())
    for n in (1, 2, 3, 4, len(imgs) - 10):   # the last: past the limit
        for _ in range(n):
            eng.submit(ImageRequest(uid=uid, image=imgs[uid]))
            uid += 1
        eng.run()
    spans.uninstall()
    assert rec.counts["jax.compile"] == 0
    s = eng.stats()
    assert s["images_done"] == uid and s["late_rows"] > 0
    assert rec.counts["engine.stage"] == s["staged_rows"]
    assert rec.counts["engine.stage_late"] == s["late_rows"]
    late = [sp for sp in rec.spans if sp.name == "engine.stage_late"]
    stack = {i for i, sp in enumerate(rec.spans) if sp.name == "batch.stack"}
    assert all(sp.parent in stack for sp in late)


def _expire_overdue(eng, clock):
    _submit(eng, [0, 1], deadline=1.0)
    clock.advance_to(2.0)
    assert eng.step() == []
    return eng.expired


def _expire_mid_retry(eng, clock):
    def forward(params, x):
        raise RuntimeError("transient")
    eng._forward = forward
    _submit(eng, [0], deadline=0.5)     # alone: retried, never bisected
    assert eng.step() == []
    return eng.expired


def _quarantine(eng, clock):
    def forward(params, x):
        if bool((np.asarray(x) == 1).any()):
            raise RuntimeError("poison row")
        return jnp.zeros((len(x), eng.cfg.n_classes))
    eng._forward = forward
    _submit(eng, [0, 1])
    assert list(eng.run()) == [0]
    return eng.failed


def _mark_down(eng, clock):
    _submit(eng, range(3 * eng.stage_limit))
    eng.mark_down("test")
    return eng.failed


@pytest.mark.parametrize("door", [_expire_overdue, _expire_mid_retry,
                                  _quarantine, _mark_down],
                         ids=lambda f: f.__name__.strip("_"))
def test_expired_or_failed_request_holds_no_payload(door):
    clock = Clock()
    eng = _stub_engine(buckets=(1, 2), clock=clock, advance=clock.advance_to,
                       retry=RetryPolicy(max_attempts=2, backoff_base=1.0,
                                         backoff_cap=1.0, bisect_after=1))
    ledger = door(eng, clock)
    assert ledger
    for res in ledger.values():
        assert not hasattr(res.request, "_payload"), res.uid
    assert len(eng.request_queue) == 0


def test_mesh_engine_stages_and_matches_single_device():
    from repro.launch.mesh import make_host_mesh

    cfg = _cfg().replace(conv_path="im2col")
    params = cnn_init(cfg, jax.random.PRNGKey(0))
    imgs = _images(cfg, 3)
    mesh = CNNServeEngine(cfg, params, buckets=(4,), mesh=make_host_mesh(1, 1))
    solo = CNNServeEngine(cfg, params, buckets=(4,))
    for uid, img in enumerate(imgs):
        mesh.submit(ImageRequest(uid=uid, image=img))
        solo.submit(ImageRequest(uid=uid, image=img))
    assert _staged_pending(mesh) == len(imgs)
    rec = spans.install(spans.Recorder())
    dm = mesh.run()
    spans.uninstall()
    assert "engine.stage_late" not in rec.counts
    assert rec.counts["engine.forward"] == 1
    s = mesh.stats()
    assert (s["staged_rows"], s["late_rows"]) == (len(imgs), 0)
    ds = solo.run()
    assert sorted(dm) == sorted(ds) == list(range(len(imgs)))
    for uid in dm:
        np.testing.assert_array_equal(dm[uid].logits, ds[uid].logits)
