"""Hypothesis property tests for the limb substrate (core/substrate.py).

The deterministic versions of these live in test_substrate_unified.py; here
hypothesis drives the operand ranges, base bits and pass schedules.  The
core claims:

  * ``limb_recombine(limb_partials(a, b)) == a * b`` EXACTLY (int64
    recombine) for every variant and every legal base_bits -- the 3-pass
    Karatsuba schedule loses nothing vs the 4-pass schoolbook one;
  * ``balanced_split`` round-trips (``hi * 2^b + lo == x``) with both
    digits in the balanced range and the Karatsuba guard-bit property.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.substrate import (
    balanced_split,
    kom_qmax,
    limb_dot_general,
    limb_partials,
    limb_recombine,
)

# legal (variant, base_bits) pairs: karatsuba digit sums need the guard bit
SCHEDULES = st.one_of(
    st.tuples(st.just("karatsuba"), st.integers(2, 7)),
    st.tuples(st.just("schoolbook"), st.integers(2, 8)),
)


def _ints(rng, qm, shape):
    return rng.integers(-qm, qm + 1, shape).astype(np.int32)


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2**31 - 1), SCHEDULES)
def test_limb_partials_recombine_is_exact_product(seed, schedule):
    """recombine(partials(a, b)) == a*b bit-exactly, elementwise case:
    (1,1)x(1,1) matmuls ARE scalar products over the full |x| <= qmax range."""
    variant, bb = schedule
    rng = np.random.default_rng(seed)
    qm = kom_qmax(bb)
    a = jnp.array(_ints(rng, qm, (1, 1)))
    b = jnp.array(_ints(rng, qm, (1, 1)))
    with jax.enable_x64(True):
        parts = limb_partials(a, b, variant=variant, base_bits=bb)
        out = int(limb_recombine(*parts, base_bits=bb, dtype=jnp.int64)[0, 0])
    assert out == int(a[0, 0]) * int(b[0, 0])


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), SCHEDULES,
       st.integers(1, 12), st.integers(1, 48), st.integers(1, 12))
def test_limb_dot_general_exact_over_shapes(seed, schedule, m, k, n):
    """The full dot_general schedule stays exact over random shapes/ranges:
    int32 partials cannot overflow for k <= 48 at any legal base_bits."""
    variant, bb = schedule
    rng = np.random.default_rng(seed)
    qm = kom_qmax(bb)
    a = _ints(rng, qm, (m, k))
    b = _ints(rng, qm, (k, n))
    with jax.enable_x64(True):
        out = np.asarray(limb_dot_general(
            jnp.array(a), jnp.array(b), variant=variant, base_bits=bb,
            recombine_dtype=jnp.int64))
    np.testing.assert_array_equal(out, a.astype(np.int64) @ b.astype(np.int64))


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), SCHEDULES)
def test_karatsuba_equals_schoolbook(seed, schedule):
    """Both pass schedules recombine to the same integers (3 passes lose
    nothing vs 4), whatever base_bits each is legal at."""
    _, bb = schedule
    bb = min(bb, 7)  # compare at a base both schedules support
    rng = np.random.default_rng(seed)
    qm = kom_qmax(bb)
    a = jnp.array(_ints(rng, qm, (4, 8)))
    b = jnp.array(_ints(rng, qm, (8, 4)))
    with jax.enable_x64(True):
        kara = np.asarray(limb_dot_general(
            a, b, variant="karatsuba", base_bits=bb,
            recombine_dtype=jnp.int64))
        school = np.asarray(limb_dot_general(
            a, b, variant="schoolbook", base_bits=bb,
            recombine_dtype=jnp.int64))
    np.testing.assert_array_equal(kara, school)


@settings(max_examples=50, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(2, 8), st.integers(1, 256))
def test_balanced_split_roundtrip(seed, bb, size):
    """hi * 2^b + lo == x over the whole legal range, digits balanced, and
    (for bb <= 7) the Karatsuba digit sums inside s8."""
    rng = np.random.default_rng(seed)
    qm = kom_qmax(bb)
    x = _ints(rng, qm, (size,))
    hi, lo = balanced_split(jnp.array(x), bb)
    hi, lo = np.asarray(hi), np.asarray(lo)
    half = 1 << (bb - 1)
    np.testing.assert_array_equal(hi * (1 << bb) + lo, x)
    assert lo.min() >= -half and lo.max() <= half - 1   # balanced low digit
    assert hi.min() >= -(half - 1) and hi.max() <= half - 1
    if bb <= 7:
        s = hi + lo
        assert s.min() >= -128 and s.max() <= 127, (bb, s.min(), s.max())


@settings(max_examples=30, deadline=None)
@given(st.integers(2, 8))
def test_balanced_split_edge_magnitudes(bb):
    """The extreme magnitudes +-qmax themselves round-trip (the guard-bit
    boundary is where unbalanced digit schemes break first)."""
    qm = kom_qmax(bb)
    x = jnp.array([qm, -qm, 0, 1, -1], jnp.int32)
    hi, lo = balanced_split(x, bb)
    np.testing.assert_array_equal(
        np.asarray(hi).astype(np.int64) * (1 << bb) + np.asarray(lo),
        np.asarray(x))


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(2, 5), st.integers(1, 6),
       st.integers(1, 24))
def test_prequant_3d_batch_invariance_bitwise(seed, b, t, k):
    """prequant_dot_general quantizes per ROW over ALL leading axes: a
    (B, T, k) activation stack served whole is BITWISE equal to serving each
    batch entry alone -- callers need not pre-flatten, and no entry's
    logits depend on its batch-mates (the serving invariance contract)."""
    from repro.core.substrate import prequant_dot_general, quantize_weight

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((b, t, k)).astype(np.float32)
    # wildly different row magnitudes: a per-tensor fallback would couple them
    x *= rng.uniform(1e-3, 1e3, (b, t, 1)).astype(np.float32)
    w = quantize_weight(jnp.array(
        rng.standard_normal((k, 8)).astype(np.float32)))
    dn3 = (((2,), (0,)), ((), ()))
    full = np.asarray(prequant_dot_general(jnp.array(x), w, dn3))
    for i in range(b):
        solo = np.asarray(prequant_dot_general(jnp.array(x[i:i + 1]), w, dn3))
        np.testing.assert_array_equal(full[i], solo[0])
    # and the 3D result equals the pre-flattened 2D call (same scales/rows)
    flat = np.asarray(prequant_dot_general(
        jnp.array(x.reshape(-1, k)), w)).reshape(b, t, 8)
    np.testing.assert_array_equal(full, flat)
