"""Pallas TPU kernel: integer Winograd F(2x2, 3x3) on the KOM limb substrate.

Ahmad & Pasha ("Fast Algorithms for CNNs on FPGAs", PAPERS.md) cut a 3x3
convolution's multiply count ~2.25x with Winograd F(2x2, 3x3): each 4x4
input tile produces a 2x2 output tile from SIXTEEN pointwise multiplies
instead of 4*9 = 36 direct MACs.  On the KOM substrate every wide multiply
costs 3-4 narrow MXU passes, so the two optimizations COMPOUND: the
pointwise (tile x Cin x Cout) contractions run as ``limb_partials``-style
int32 accumulations and the transform work is integer adds.

The transforms live entirely in the quantized-limb INTEGER domain:

    BT = [[1, 0, -1,  0],     G2 = 2*G = [[2,  0, 0],    AT = [[1, 1,  1,  0],
          [0, 1,  1,  0],                 [1,  1, 1],           [0, 1, -1, -1]]
          [0, -1, 1,  0],                 [1, -1, 1],
          [0, 1,  0, -1]]                 [0,  0, 2]]

``G2 = 2G`` clears the 1/2 entries of the canonical F(2x2, 3x3) weight
transform, so EVERY matrix is small-integer ({-1, 0, 1, 2}) and

    AT [ (G2 g G2t) .*. (BT d B) ] A  ==  4 * correlate(d, g)      (exact)

-- the engine computes exactly 4x the direct convolution in integers and
folds the 1/4 into the per-channel dequant scale (``wscale * 0.25``, an
exact f32 exponent shift, so dequantized outputs are BITWISE equal to the
direct paths').

Exactness architecture (the bitwise differential vs implicit/im2col):

* **Tile-granular activation scales.**  All int conv paths quantize an
  eligible layer's activations with ONE scale per 4x4 Winograd tile
  (:func:`tile_scale_grid`), shared via :func:`winograd_scale_eligible` --
  the 4 patches inside a tile then see the very same quantized integers the
  Winograd engine transforms, and the three paths' raw integer outputs
  coincide exactly.
* **Transform after split.**  Quantized ints are split into balanced limbs
  FIRST; the linear B/G transforms apply per limb plane, exactly.  The
  transformed planes are no longer balanced digits of anything (|V| <= 4h,
  |U| <= 9h, h = 2^(b-1)), so the pointwise passes run through
  :func:`~repro.core.substrate.limb_partials_presplit` as wide dots (three
  int8 MXU passes each: the MXU has no int16 pass), and BOTH weight planes
  ship to the kernel (re-splitting U would change the integers).
* **Inverse transform before the single recombine.**  The exact int32
  pointwise partials are pushed through the integer At.m.A inverse per limb
  plane; by linearity the result is exactly 4x the direct path's per-limb
  partials, and ONE ``limb_recombine`` per tile (PR 3's single-recombine
  contract, grep-tested) converts to f32 -- a pure x4 exponent shift of the
  direct recombine, bitwise after the 0.25 dequant fold.
* **Growth bound.**  :func:`winograd_accum_bound` = 4x the direct
  ``int_accum_bound(3, 3, cin)``; under it every int32 -> f32 conversion
  point holds the true integer (intermediate int32 adds are mod-2^32
  wrap-safe and provably in range anyway).  Layers past the bound REROUTE
  to the implicit GEMM -- exact-or-reroute, never wrap.

Off-TPU the same dataflow runs as a bitwise lax mirror
(:func:`stream_conv_winograd`), mirroring the implicit engine's strategy:
f32 sub-chunked dots whose worst-case partial sums stay exactly
representable (< 2^24), batched over the 16 Winograd points.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.substrate import (
    balanced_split,
    limb_partials_presplit,
    limb_recombine,
)

from .conv2d import (LANES, int_accum_bound, load_window, stage_rows,
                     staging_dtype, staging_shape)

# The integer F(2x2, 3x3) transform matrices (correlation convention).
BT = ((1, 0, -1, 0), (0, 1, 1, 0), (0, -1, 1, 0), (0, 1, 0, -1))
G2 = ((2, 0, 0), (1, 1, 1), (1, -1, 1), (0, 0, 2))
AT = ((1, 1, 1, 0), (0, 1, -1, -1))

#: Two G2 = 2*G factors: the integer engine computes 4x the convolution.
WINOGRAD_OUTPUT_SCALE = 4

_INT_VARIANTS = ("karatsuba", "schoolbook")

#: Largest integer f32 represents exactly (the mirror's chunk budget).
_F32_EXACT = 1 << 24


# ---------------------------------------------------------------------------
# Growth bound + eligibility.
# ---------------------------------------------------------------------------

def winograd_accum_bound(cin: int, *, variant: str, base_bits: int) -> int:
    """Worst-case |int32| at any int32 -> f32 conversion point of the engine.

    The transformed per-limb partials equal exactly 4x the direct path's
    (the At[..]A identity is linear in each limb plane), so the direct
    bound scales by :data:`WINOGRAD_OUTPUT_SCALE`:

        4 * int_accum_bound(3, 3, cin) = 36 * limb_term_bound * cin

    (karatsuba b=7: 216 * cin * h^2 -> cin <= 2427; schoolbook b=8:
    72 * cin * h^2 -> cin <= 1820).  The bound also dominates every
    in-range intermediate: the kernel's karatsuba sum-pass dot is
    <= 144 h^2 cin, the inverse transform's row sums <= 3 * 72 h^2 cin --
    all <= the bound whenever it holds, and int32 add/sub chains are
    mod-2^32 wrap-safe in between regardless.
    """
    return WINOGRAD_OUTPUT_SCALE * int_accum_bound(
        3, 3, cin, variant=variant, base_bits=base_bits)


def winograd_scale_eligible(kh: int, kw: int, stride: int, cin: int, *,
                            variant: str, base_bits: int) -> bool:
    """True iff the layer runs the SHARED tile-granular activation scales.

    The one predicate every int conv path (winograd, implicit, im2col)
    consults, so their quantization -- hence their raw integers -- match
    bitwise on exactly the layers the Winograd engine can serve.  Padding
    mode is NOT part of the predicate: the scale grid is computed from the
    layer's own padded input and zero padding never raises a tile max.
    """
    return (variant in _INT_VARIANTS and kh == 3 and kw == 3 and stride == 1
            and winograd_accum_bound(cin, variant=variant,
                                     base_bits=base_bits) < 2**31)


# ---------------------------------------------------------------------------
# Shared tile-granular activation scale plan.
# ---------------------------------------------------------------------------

def tile_scale_grid(xp: jax.Array, qmax: int, th: int, tw: int) -> jax.Array:
    """Per-4x4-tile activation scales from the padded input: (n, th, tw).

    ``xp`` is the layer's padded NHWC input with the tile grid anchored at
    its origin (tile (ty, tx) covers padded rows 2ty..2ty+3).  The channel
    abs-max image is zero-padded out to the (2*th+2, 2*tw+2) footprint the
    tile grid needs -- zeros never raise a max, so every path gets the SAME
    scales regardless of how much extra zero padding its own layout wants
    (odd-width layers, halo row blocks).  Per-sample, per-tile: a request's
    scales never depend on its batch-mates.
    """
    cmax = jnp.max(jnp.abs(xp.astype(jnp.float32)), axis=3)  # (n, Hp, Wp)
    need_h, need_w = 2 * th + 2, 2 * tw + 2
    pad_h = max(need_h - cmax.shape[1], 0)
    pad_w = max(need_w - cmax.shape[2], 0)
    if pad_h or pad_w:
        cmax = jnp.pad(cmax, ((0, 0), (0, pad_h), (0, pad_w)))
    amax = lax.reduce_window(
        cmax, -jnp.inf, lax.max,
        window_dimensions=(1, 4, 4),
        window_strides=(1, 2, 2),
        padding="VALID",
    )[:, :th, :tw]
    return jnp.maximum(amax, 1e-12) / qmax


def tile_scales_upsampled(s: jax.Array, ho: int, wo: int) -> jax.Array:
    """Tile scales (n, th, tw) -> per-output-position scales (n, ho, wo).

    Output position (y, x) belongs to tile (y//2, x//2); the direct paths
    (implicit, im2col) quantize each patch with ITS tile's scale so the
    quantized integers agree with the Winograd tiles exactly.
    """
    s = jnp.repeat(jnp.repeat(s, 2, axis=1), 2, axis=2)
    return s[:, :ho, :wo]


# ---------------------------------------------------------------------------
# Integer transforms.
# ---------------------------------------------------------------------------

def _lincomb(coefs, arrs):
    """sum_i coefs[i] * arrs[i] with {-1, 0, 1, 2} coefficients, exact."""
    acc = None
    for c, v in zip(coefs, arrs):
        if c == 0:
            continue
        t = v if c == 1 else (-v if c == -1 else v * c)
        acc = t if acc is None else acc + t
    return acc


def _transform(M, g):
    """M . g . Mt over a q x q nested list of equal-shape arrays (exact).

    Returns a p x p nested list.  Lists, not a stacked point axis, so the
    Pallas kernel can keep every Winograd point a plain 2-D tile.
    """
    p, q = len(M), len(M[0])
    left = [[_lincomb(M[i], [g[a][b] for a in range(q)]) for b in range(q)]
            for i in range(p)]
    return [[_lincomb(M[j], [left[i][b] for b in range(q)]) for j in range(p)]
            for i in range(p)]


def _stack2(g):
    return jnp.stack([jnp.stack(r) for r in g])


def winograd_transform_2d(M, g: jax.Array) -> jax.Array:
    """M . g . Mt over the two leading point-grid axes of ``g`` (exact)."""
    q = len(M[0])
    return _stack2(_transform(M, [[g[a, b] for b in range(q)]
                                  for a in range(q)]))


def winograd_weight_planes(w_vals: jax.Array,
                           base_bits: int) -> tuple[jax.Array, jax.Array]:
    """G2 . g_limb . G2t per balanced limb plane: 2 x (4, 4, cin, cout).

    The quantized weight ints split FIRST (balanced digits, |.| <= h), then
    each plane transforms exactly (|U| <= 9h, int16-safe).  U = uh*beta + ul
    by linearity, but (uh, ul) are NOT balanced digits of U -- both planes
    must reach the contraction as-is (re-splitting would change integers).
    """
    wh, wl = balanced_split(w_vals.astype(jnp.int32), base_bits)
    uh = winograd_transform_2d(G2, wh)
    ul = winograd_transform_2d(G2, wl)
    return uh.astype(jnp.int16), ul.astype(jnp.int16)


def winograd_input_planes(q4, base_bits: int):
    """BT . d_limb . B per balanced limb plane of the 4x4 tile points.

    ``q4``: 4x4 nested list of quantized tile ints (equal shapes).  Returns
    (vh, vl), two 4x4 nested lists of int32 (|V| <= 4h per plane).
    """
    split = [[balanced_split(q, base_bits) for q in row] for row in q4]
    return (_transform(BT, [[hl[0] for hl in row] for row in split]),
            _transform(BT, [[hl[1] for hl in row] for row in split]))


def winograd_inverse(m_hh, m_mid, m_ll, *, base_bits: int):
    """At . m . A per limb plane (exact int32), then ONE f32 recombine.

    ``m_*``: 4x4 nested lists of int32 pointwise partials.  Returns a 2x2
    nested list of f32 -- exactly 4x the direct path's recombined raw
    output (the shared single ``limb_recombine`` call site of this engine,
    kernel AND mirror).
    """
    y = [_transform(AT, m) for m in (m_hh, m_mid, m_ll)]
    return [[limb_recombine(y[0][i][j], y[1][i][j], y[2][i][j],
                            base_bits=base_bits, dtype=jnp.float32)
             for j in range(2)] for i in range(2)]


# ---------------------------------------------------------------------------
# The bitwise lax mirror (off-TPU serving path).
# ---------------------------------------------------------------------------

#: |U| per Winograd point is w_u * w_v * h with G2 row weights (2, 3, 3, 2):
#: 4h at the corners, 6h on the edges, 9h only at the four center points.
_G2_ROW_WEIGHT = (2, 3, 3, 2)


def _point_groups() -> list[tuple[int, list[int]]]:
    """The 16 Winograd points grouped by their |U| bound weight w_u * w_v:
    [(4, corners), (6, edges), (9, centers)] in flat-index order."""
    groups: dict[int, list[int]] = {}
    for u in range(4):
        for v in range(4):
            w = _G2_ROW_WEIGHT[u] * _G2_ROW_WEIGHT[v]
            groups.setdefault(w, []).append(4 * u + v)
    return sorted(groups.items())


def _mirror_schedule(kdim: int,
                     base_bits: int) -> list[tuple[list[int], list]]:
    """The mirror's exact-f32 chunk plan: per point group, the Cin chunk
    boundaries keeping every worst-case partial sum < 2^24.

    The per-term bound is POINTWISE: |V| <= 4h everywhere, but |U| is
    w_u * w_v * h with G2 row weights (2, 3, 3, 2), so corner points chunk
    at 2^24 // (16 h^2) (usually no chunking at all), edge points at
    2^24 // (24 h^2), and only the four center points pay the worst-case
    2^24 // (36 h^2) schedule -- a ~1/3 dot-work saving over chunking all
    sixteen at the center bound, for the SAME integers.
    """
    half = 1 << (base_bits - 1)
    plan = []
    for w, pts in _point_groups():
        safe_k = max(_F32_EXACT // (4 * w * half * half), 1)
        # Balanced chunks (ceil-split under safe_k) instead of safe_k-sized
        # chunks with a ragged tail: same exactness bound, better GEMM
        # shapes (512 at safe_k=170 runs 4x128, not 170+170+170+2).
        n_chunks = -(-kdim // safe_k)
        size = -(-kdim // n_chunks)
        chunks = [(c0, min(c0 + size, kdim))
                  for c0 in range(0, kdim, size)]
        plan.append((pts, chunks))
    return plan


def winograd_mirror_operands(uh: jax.Array, ul: jax.Array, *,
                             base_bits: int) -> tuple:
    """Pre-slice the transformed weight planes into the exact per-group,
    per-chunk f32 operands the mirror's dots consume.

    The plane values (|U| <= 9h <= 1152) are exact f32 integers, so this
    is a pure layout change -- same integers as slicing int16 planes
    inside the graph.  Doing it ONCE per cached weight (the ops wrapper
    memoizes per QWeight) moves the weight transform, the group gathers,
    and the chunk copies out of the per-call graph: with the weight as a
    jit *argument* (serving; the bench harness) XLA cannot constant-fold
    them, and they dominate the mirror's wall on deep-Cin layers.
    """
    kdim, cout = uh.shape[-2], uh.shape[-1]
    b_h = uh.reshape(16, kdim, cout).astype(jnp.float32)
    b_l = ul.reshape(16, kdim, cout).astype(jnp.float32)
    ops = []
    for pts, chunks in _mirror_schedule(kdim, base_bits):
        idx = jnp.asarray(pts, jnp.int32)
        gb_h, gb_l = b_h[idx], b_l[idx]
        for c0, c1 in chunks:
            ops.append((gb_h[:, c0:c1, :], gb_l[:, c0:c1, :]))
    return tuple(ops)


def _winograd_partials_f32(vh, vl, uh, ul, *, variant, base_bits,
                           w_ops=None):
    """The pointwise limb passes as exact f32 GEMMs, batched over 16 points.

    Mirrors ``_limb_partials_f32``'s strategy (XLA:CPU has no fast integer
    GEMM): each pass runs as f32 dots over Cin sub-chunks small enough that
    every worst-case partial sum is an exactly-representable f32 integer,
    per the pointwise-bound plan of :func:`_mirror_schedule`.  The mid
    partial always uses the 4-dot cross schedule: for karatsuba the
    kernel's (Vh+Vl)(Uh+Ul) - hh - ll computes the SAME integer, so the
    int32 results coincide bitwise whatever the pass schedule.  ``w_ops``
    (:func:`winograd_mirror_operands`) supplies the weight-side operands
    pre-sliced; ``uh``/``ul`` are sliced in-graph when it is None.
    """
    del variant  # same integers either way; the cross schedule chunks wider
    kdim = vh.shape[-1]
    spatial = vh.shape[2:-1]
    m = 1
    for d in spatial:
        m *= d
    a_h, a_l = vh.reshape(16, m, kdim), vl.reshape(16, m, kdim)
    if w_ops is None:
        cout = uh.shape[-1]
        w_ops = winograd_mirror_operands(uh, ul, base_bits=base_bits)
    else:
        cout = w_ops[0][0].shape[-1]
    dnums = (((2,), (1,)), ((0,), (0,)))
    dotf = lambda a, b: lax.dot_general(
        a.astype(jnp.float32), b, dnums,
        precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(jnp.int32)
    point_hh: list = [None] * 16
    point_mid: list = [None] * 16
    point_ll: list = [None] * 16
    op_i = 0
    for pts, chunks in _mirror_schedule(kdim, base_bits):
        idx = jnp.asarray(pts, jnp.int32)
        ga_h, ga_l = a_h[idx], a_l[idx]
        hh = mid = ll = jnp.zeros((), jnp.int32)
        for c0, c1 in chunks:
            c_h, c_l = ga_h[..., c0:c1], ga_l[..., c0:c1]
            d_h, d_l = w_ops[op_i]
            op_i += 1
            hh = hh + dotf(c_h, d_h)
            ll = ll + dotf(c_l, d_l)
            mid = mid + dotf(c_h, d_l) + dotf(c_l, d_h)
        for gi, p in enumerate(pts):
            point_hh[p] = hh[gi]
            point_mid[p] = mid[gi]
            point_ll[p] = ll[gi]
    shape = spatial + (cout,)
    nest = lambda pts: [[pts[4 * u + v].reshape(shape) for v in range(4)]
                        for u in range(4)]
    return nest(point_hh), nest(point_mid), nest(point_ll)


def stream_conv_winograd(xp, w_vals, s_tile, *, th, tw, variant, base_bits,
                         qmax, w_ops=None):
    """The lax mirror of the Winograd kernel, bitwise.

    ``xp``: padded NHWC input covering the (2*th+2, 2*tw+2) tile footprint;
    ``w_vals``: integer (3, 3, cin, cout) weight values; ``s_tile``:
    (n, th, tw) tile scales.  ``w_ops`` optionally carries the weight side
    pre-transformed and pre-sliced (:func:`winograd_mirror_operands`, the
    ops wrapper's per-QWeight memo) -- ``w_vals`` is untouched then.
    Returns the RAW 4x-scaled f32 output (n, 2*th, 2*tw, cout) -- dequant
    (x0.25 fold), slicing, bias all happen in the ops wrapper's core.
    """
    n, _, _, cin = xp.shape
    cout = w_vals.shape[-1]
    # Gather the 16 point planes: point (u, v) of tile (ty, tx) is padded
    # pixel (2*ty + u, 2*tx + v).
    s = s_tile[..., None]
    q4 = [
        [jnp.clip(jnp.round(lax.slice(
            xp, (0, u, v, 0),
            (n, u + 2 * (th - 1) + 1, v + 2 * (tw - 1) + 1, cin),
            (1, 2, 2, 1)) / s), -qmax, qmax).astype(jnp.int32)
         for v in range(4)]
        for u in range(4)
    ]  # 4x4 points of (n, th, tw, cin)
    vh, vl = (_stack2(v) for v in winograd_input_planes(q4, base_bits))
    # Pin the transformed planes: without the barrier XLA refuses the
    # materialization and re-runs gather+quantize+transform once per Cin
    # chunk of the partials below (pure scheduling, same integers).
    vh, vl = lax.optimization_barrier((vh, vl))
    if w_ops is None:
        uh, ul = winograd_weight_planes(w_vals, base_bits)
    else:
        uh = ul = None
    m_hh, m_mid, m_ll = _winograd_partials_f32(
        vh, vl, uh, ul, variant=variant, base_bits=base_bits, w_ops=w_ops)
    raw4 = _stack2(winograd_inverse(m_hh, m_mid, m_ll, base_bits=base_bits))
    # (2, 2, n, th, tw, cout) -> (n, 2*th, 2*tw, cout)
    return raw4.transpose(2, 3, 0, 4, 1, 5).reshape(n, 2 * th, 2 * tw, cout)


# ---------------------------------------------------------------------------
# The Pallas kernel.
# ---------------------------------------------------------------------------

def _winograd_kernel(x0_ref, x1_ref, uh_ref, ul_ref, ascale_ref, wscale_ref,
                     o_ref, xs_ref, *, bt, tw, variant, base_bits, qmax):
    # Dual row-block binding (index maps i and i+1): 4*bt padded rows cover
    # the 2*bt + 2 rows the bt tile-rows' 4x4 footprints need.  Both halves
    # are staged in one VMEM buffer so the stride-2 point gathers are
    # strided ref loads (Mosaic has no strided vector slice).
    stage_rows(xs_ref, x0_ref, x1_ref)  # (4*bt, Wp, cin)
    cin = x0_ref.shape[-1]
    s = ascale_ref[0, 0][..., None]  # (bt, tw, 1)
    # Every Winograd point is a 2-D (bt*tw, cin) tile: the MXU contracts
    # 2-D operands, and tw % 8 == 0 (the wrapper pads it) keeps the
    # flatten a whole-tile shape cast.
    q4 = [
        [jnp.clip(jnp.round(load_window(xs_ref, u, bt, v, tw, 2) / s),
                  -qmax, qmax).astype(jnp.int32).reshape(bt * tw, cin)
         for v in range(4)]
        for u in range(4)
    ]
    vh, vl = winograd_input_planes(q4, base_bits)
    # 16 pointwise contractions over the FULL Cin (the growth bound
    # guarantees a single wrap-free int32 group -- no K tiling, no folds).
    # The transformed planes outgrow int8 and the MXU has no int16 pass,
    # so each plane product runs as three int8 passes (wide_int_dot).
    parts = [[limb_partials_presplit(
        vh[u][v], vl[u][v], uh_ref[u, v].astype(jnp.int32),
        ul_ref[u, v].astype(jnp.int32), variant=variant, wide=True)
        for v in range(4)] for u in range(4)]
    m_hh, m_mid, m_ll = ([[parts[u][v][k] for v in range(4)]
                          for u in range(4)] for k in range(3))
    raw4 = winograd_inverse(m_hh, m_mid, m_ll, base_bits=base_bits)
    # Fused dequant epilogue: tile scale x (per-channel scale / 4); the
    # 0.25 fold is an exact exponent shift, so this equals the direct
    # paths' fl(raw * (s * wscale)) bitwise.  Output point (a, b) of every
    # tile lands on rows 2*ty + a, cols 2*tx + b: strided stores.
    t = s * wscale_ref[...]  # (bt, tw, bc)
    bc = t.shape[-1]
    for a in range(2):
        for b in range(2):
            o_ref[0, pl.ds(a, bt, stride=2), pl.ds(b, tw, stride=2), :] = (
                raw4[a][b].reshape(bt, tw, bc) * t)


def conv2d_winograd_raw(
    x: jax.Array,
    uh: jax.Array,
    ul: jax.Array,
    *,
    th: int,
    tw: int,
    block: tuple[int, int] = (4, 128),
    variant: str = "karatsuba",
    base_bits: int = 7,
    qmax: int = 0,
    ascale: jax.Array | None = None,
    wscale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """x: pre-padded NHWC f32; uh/ul: (4, 4, Cin, Cout) int16 weight planes.

    ``block = (bt, bc)``: tile-row / Cout tile sizes.  Requirements (the
    ops wrapper arranges them): th % bt == 0, Cout % bc == 0, one spare
    halo row block (x rows == (th/bt + 1) * 2*bt), width >= 2*tw + 2,
    ``ascale`` (N, th, tw) tile scales, ``wscale`` (1, Cout) per-channel
    scales ALREADY folded by 0.25.  Returns (N, 2*th, 2*tw, Cout) f32,
    dequantized.
    """
    n, h, wdim, cin = x.shape
    cout = uh.shape[-1]
    bt, bc = block
    bc = min(bc, cout)
    assert bc <= LANES, f"bc={bc}: strided output stores take <= {LANES} lanes"
    assert th % bt == 0, (th, bt)
    assert cout % bc == 0, (cout, bc)
    assert wdim >= 2 * tw + 2, (wdim, tw)
    n_row_blocks = th // bt
    assert h >= (n_row_blocks + 1) * 2 * bt, "need one spare halo block"
    nin_blocks = h // (2 * bt)
    assert ascale is not None and ascale.shape == (n, th, tw)
    assert wscale is not None and wscale.shape == (1, cout)
    # Batch INNERMOST: for a fixed (row block, cout block) the int16 weight
    # planes' block indices are constant across all N batch steps, so Pallas
    # keeps them resident instead of re-fetching them per image -- weight
    # traffic amortizes over the batch (conv_hbm_bytes models row_blocks
    # without the xN factor to match).  The kernel body reads no program_id,
    # so the iteration order is otherwise free.
    grid = (n_row_blocks, cout // bc, n)
    kernel = functools.partial(
        _winograd_kernel, bt=bt, tw=tw, variant=variant,
        base_bits=base_bits, qmax=qmax)
    in_specs = [
        pl.BlockSpec((1, 2 * bt, wdim, cin), lambda i, j, b: (b, i, 0, 0)),
        pl.BlockSpec(
            (1, 2 * bt, wdim, cin),
            lambda i, j, b, nb=nin_blocks: (b, jnp.minimum(i + 1, nb - 1),
                                            0, 0),
        ),
        pl.BlockSpec((4, 4, cin, bc), lambda i, j, b: (0, 0, 0, j)),
        pl.BlockSpec((4, 4, cin, bc), lambda i, j, b: (0, 0, 0, j)),
        # Tile scales as (n, th/bt, bt, tw): the block's last two dims are
        # then the array's full (bt, tw), which the TPU tiling rule admits.
        pl.BlockSpec((1, 1, bt, tw), lambda i, j, b: (b, i, 0, 0)),
        pl.BlockSpec((1, bc), lambda i, j, b: (0, j)),
    ]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 2 * bt, 2 * tw, bc),
                               lambda i, j, b: (b, i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, 2 * th, 2 * tw, cout),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM(staging_shape(4 * bt, wdim, cin),
                                   staging_dtype(x.dtype))],
        interpret=interpret,
    )(x, x, uh, ul,
      ascale.astype(jnp.float32).reshape(n, n_row_blocks, bt, tw),
      wscale.astype(jnp.float32))
