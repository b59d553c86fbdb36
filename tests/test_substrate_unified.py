"""The KOM substrate contract: one limb core, cached quantization state.

Deterministic (hypothesis-free) versions of the core exactness properties,
the single-definition invariant for the balanced digit split, and the
quantize-once guarantee for CNN weights through both conv paths.
"""
import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.precision import MatmulPolicy
from repro.core.substrate import (
    QWeight,
    balanced_split,
    dequantize_weight,
    kom_qmax,
    limb_dot_general,
    limb_partials,
    limb_recombine,
    pass_count,
    policy_int_spec,
    prequant_dot_general,
    quantize_weight,
    wide_int_dot,
)
from repro.models.cnn import (
    ALEXNET,
    VGG16,
    cnn_forward,
    cnn_init,
    cnn_quantize_params,
)

rng = np.random.default_rng(0)
SRC = pathlib.Path(__file__).resolve().parent.parent / "src"


# -- one implementation of the limb split -------------------------------------

def test_balanced_split_defined_once():
    """The balanced digit trick exists exactly once in src/ (the substrate);
    every kernel imports it instead of redefining it."""
    needle = "((x + half) & (beta - 1)) - half"
    hits = [p for p in SRC.rglob("*.py") if needle in p.read_text()]
    assert [p.name for p in hits] == ["substrate.py"], hits


def test_kernels_import_shared_limb_core():
    import importlib

    import repro.core.substrate as substrate
    conv_mod = importlib.import_module("repro.kernels.conv2d.conv2d")
    gemm_mod = importlib.import_module("repro.kernels.kom_matmul.kom_matmul")

    # Both Pallas kernels accumulate partials and recombine once via the
    # SHARED schedule -- neither re-implements it (nor the digit split).
    assert conv_mod.limb_partials is substrate.limb_partials
    assert conv_mod.limb_recombine is substrate.limb_recombine
    assert gemm_mod.limb_partials is substrate.limb_partials
    assert gemm_mod.limb_recombine is substrate.limb_recombine
    assert not hasattr(conv_mod, "_split_limbs")
    assert not hasattr(gemm_mod, "_split_limbs")
    assert not hasattr(conv_mod, "limb_dot_general")  # per-tap recombine gone


# -- deterministic exactness (hypothesis-free core coverage) ------------------

@pytest.mark.parametrize("variant,bb", [("karatsuba", 7), ("schoolbook", 8)])
def test_limb_dot_exact(variant, bb):
    qm = kom_qmax(bb)
    a = rng.integers(-qm, qm + 1, (24, 48)).astype(np.int32)
    b = rng.integers(-qm, qm + 1, (48, 16)).astype(np.int32)
    with jax.enable_x64(True):  # int64 recombine, bit-exact mode
        out = np.asarray(limb_dot_general(
            jnp.array(a), jnp.array(b), variant=variant, base_bits=bb,
            recombine_dtype=jnp.int64))
    ref = a.astype(np.int64) @ b.astype(np.int64)
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("bound", [127, 1152, 8127])
def test_wide_int_dot_exact(bound):
    """Operands past int8 (the Winograd planes reach 18h = 1152) contract
    exactly through three int8 passes and an int32 recombine."""
    k = 64
    a = rng.integers(-bound, bound + 1, (8, k)).astype(np.int32)
    b = rng.integers(-bound, bound + 1, (k, 16)).astype(np.int32)
    out = np.asarray(wide_int_dot(jnp.array(a), jnp.array(b)))
    assert out.dtype == np.int32
    np.testing.assert_array_equal(out, a.astype(np.int64) @ b.astype(np.int64))


def test_limb_partials_recombine_roundtrip():
    qm = kom_qmax(7)
    a = rng.integers(-qm, qm + 1, (8, 32)).astype(np.int32)
    b = rng.integers(-qm, qm + 1, (32, 8)).astype(np.int32)
    with jax.enable_x64(True):
        parts = limb_partials(jnp.array(a), jnp.array(b))
        out = np.asarray(limb_recombine(*parts, base_bits=7, dtype=jnp.int64))
    np.testing.assert_array_equal(out, a.astype(np.int64) @ b.astype(np.int64))


def test_balanced_split_reconstructs():
    for bb in (5, 6, 7, 8):
        qm = kom_qmax(bb)
        x = jnp.array(rng.integers(-qm, qm + 1, (64,)).astype(np.int32))
        hi, lo = balanced_split(x, bb)
        half = 1 << (bb - 1)
        assert int(jnp.max(jnp.abs(lo))) <= half
        np.testing.assert_array_equal(
            np.asarray(hi) * (1 << bb) + np.asarray(lo), np.asarray(x))
        if bb <= 7:  # guard bit: Karatsuba digit sums fit s8
            s = np.asarray(hi) + np.asarray(lo)
            assert s.min() >= -128 and s.max() <= 127


def test_guard_bit_enforced():
    qm = kom_qmax(8)
    a = jnp.full((2, 2), qm, jnp.int32)
    with pytest.raises(ValueError):
        limb_dot_general(a, a, base_bits=8, variant="karatsuba")
    with pytest.raises(ValueError):
        limb_partials(a, a, variant="strassen")


def test_pass_model():
    assert pass_count("karatsuba") == 3
    assert pass_count("schoolbook") == 4
    assert pass_count(6) == 6
    assert policy_int_spec(MatmulPolicy.KOM_INT14) == ("karatsuba", 7)
    assert policy_int_spec(MatmulPolicy.SCHOOLBOOK_INT16) == ("schoolbook", 8)
    assert policy_int_spec(MatmulPolicy.BF16X3) is None


# -- cached per-channel weight quantization -----------------------------------

def test_quantize_weight_per_channel():
    w = rng.standard_normal((48, 24)).astype(np.float32)
    w[:, 3] *= 50.0  # one hot channel must not wreck the others' resolution
    qw = quantize_weight(jnp.array(w))
    assert qw.values.dtype == jnp.int16 and qw.scale.shape == (24,)
    err = np.abs(np.asarray(dequantize_weight(qw)) - w)
    # per-channel: every column's error bounded by ITS OWN half-scale
    assert (err <= 0.5 * np.asarray(qw.scale)[None, :] * (1 + 1e-4) + 1e-8).all()
    # a per-tensor scale could not achieve this on the cold channels
    cold = np.abs(w[:, :3]).max() / kom_qmax(7)
    assert float(qw.scale[0]) < cold * 2


def test_prequant_dot_matches_float():
    x = jnp.array(rng.standard_normal((6, 48)), jnp.float32)
    w = rng.standard_normal((48, 24)).astype(np.float32)
    out = prequant_dot_general(x, quantize_weight(jnp.array(w)))
    ref = np.asarray(x) @ w
    rel = np.abs(np.asarray(out) - ref).max() / np.abs(ref).max()
    assert rel < 2e-3, rel


def test_prequant_3d_per_row_batch_invariance():
    """Deterministic twin of the hypothesis property: non-2D activations on
    a last-dim contraction get per-ROW scales over all leading axes (no
    silent per-tensor fallback), so batch entries cannot couple and callers
    need not pre-flatten.  Bitwise."""
    x = rng.standard_normal((3, 5, 16)).astype(np.float32)
    x *= rng.uniform(1e-3, 1e3, (3, 5, 1)).astype(np.float32)  # wild rows
    qw = quantize_weight(jnp.array(
        rng.standard_normal((16, 8)).astype(np.float32)))
    dn3 = (((2,), (0,)), ((), ()))
    full = np.asarray(prequant_dot_general(jnp.array(x), qw, dn3))
    for i in range(3):
        solo = np.asarray(prequant_dot_general(jnp.array(x[i:i + 1]), qw, dn3))
        np.testing.assert_array_equal(full[i], solo[0])
    # identical to the pre-flattened 2D call: same rows, same scales
    flat = np.asarray(prequant_dot_general(jnp.array(x.reshape(-1, 16)), qw))
    np.testing.assert_array_equal(full, flat.reshape(3, 5, 8))


def test_prequant_dot_refuses_differentiation():
    """The cached-weight path is inference-only: grad raises loudly instead
    of returning silent zeros for the whole upstream network."""
    qw = quantize_weight(jnp.ones((4, 4)))
    with pytest.raises(NotImplementedError, match="inference-only"):
        jax.grad(lambda a: prequant_dot_general(a, qw).sum())(jnp.ones((2, 4)))


def test_cnn_weights_quantized_once(monkeypatch):
    """Weight quantization runs at model build, never during forward."""
    import repro.models.cnn as cnn_mod

    calls = []
    real = cnn_mod.quantize_weight
    monkeypatch.setattr(cnn_mod, "quantize_weight",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    cfg = dataclasses.replace(ALEXNET, img_size=67,
                              policy=MatmulPolicy.KOM_INT14)
    params = cnn_init(cfg, jax.random.PRNGKey(0))
    qp = cnn_quantize_params(params, cfg)
    n_weights = sum(1 for p in params if "w" in p)
    assert len(calls) == n_weights == 8  # 5 conv + 3 fc
    # cached per-output-channel scales are materialized on the pytree
    conv0, fc0 = qp[0]["w"], qp[-1]["w"]
    assert isinstance(conv0, QWeight) and conv0.scale.shape == (96,)
    assert isinstance(fc0, QWeight) and fc0.scale.shape == (1000,)
    # two forwards: zero further weight-quantization calls
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 67, 67, 3))
    cnn_forward(qp, cfg, x)
    cnn_forward(qp, cfg, x)
    assert len(calls) == n_weights
    # re-quantizing already-quantized params is a no-op
    assert cnn_quantize_params(qp, cfg)[0]["w"] is conv0
    # float policies keep raw float params
    assert cnn_quantize_params(
        params, dataclasses.replace(cfg, policy=MatmulPolicy.FP32)) is params


@pytest.mark.parametrize("cfg,sz", [(ALEXNET, 67), (VGG16, 32)])
@pytest.mark.parametrize("path", ["im2col", "systolic"])
def test_cnn_cached_kom_matches_f32(cfg, sz, path):
    """Reduced AlexNet/VGG16 under cached-KOM vs the f32 reference, through
    both conv paths -- the acceptance gate for the unified substrate."""
    small = dataclasses.replace(cfg, img_size=sz,
                                policy=MatmulPolicy.KOM_INT14, conv_path=path)
    params = cnn_init(small, jax.random.PRNGKey(0))
    qp = cnn_quantize_params(params, small)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, sz, sz, 3))
    kom = cnn_forward(qp, small, x)
    fp = cnn_forward(params,
                     dataclasses.replace(small, policy=MatmulPolicy.FP32,
                                         conv_path="im2col"), x)
    corr = np.corrcoef(np.asarray(kom).ravel(), np.asarray(fp).ravel())[0, 1]
    assert corr > 0.99, (cfg.name, path, corr)


# -- serving: prequantized engine ---------------------------------------------

@pytest.mark.slow
def test_serve_engine_prequantizes_int_policies():
    from repro.configs import get_config, reduced
    from repro.models import transformer
    from repro.serving.engine import Request, ServeEngine

    cfg = reduced(get_config("granite-3-2b")).replace(
        policy=MatmulPolicy.KOM_INT14)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, slots=1, max_len=16)
    is_q = lambda x: isinstance(x, QWeight)
    n_q = sum(map(is_q, jax.tree.leaves(eng.params, is_leaf=is_q)))
    assert n_q >= 6  # attn qkvo + mlp + lm_head quantized once at build
    eng.submit(Request(uid=0, prompt=np.array([3, 5], np.int32),
                       max_new_tokens=2))
    done = eng.run(max_steps=20)
    assert len(done[0].out_tokens) == 2
