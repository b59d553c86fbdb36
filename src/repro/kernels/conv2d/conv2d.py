"""Pallas TPU kernel: the systolic conv engine (paper Figs. 1-3) on the MXU.

Direct NHWC convolution: each grid step owns a (bh x WO x bc) output tile and
streams `KH*KW` shifted input views through the MXU, contracting over Cin --
exactly the paper's systolic dataflow with the MAC cells replaced by MXU
passes.  Halo rows are obtained by binding *two* row-blocks of the same input
operand (index maps i and i+1), so no overlapping-BlockSpec support is
needed and the halo never round-trips through HBM.

Variants:
  native     -- dots in the input dtype (bf16/f32) -> one f32 accumulator.
  karatsuba  -- inputs are pre-quantized integers; the 3-pass limb
                decomposition (the paper's multiplier).
  schoolbook -- same integer path with the 4-pass schedule.

Single-recombine contract (DESIGN.md section 7.3): the integer variants keep
THREE int32 partial accumulators (acc_hh / acc_mid / acc_ll) across all
KH*KW taps via the shared :func:`repro.core.substrate.limb_partials` and call
:func:`repro.core.substrate.limb_recombine` exactly ONCE per output tile, in
the epilogue -- the same dataflow as the KOM GEMM kernel's VMEM scratch
accumulators, and the TPU analogue of the FPGA design's partial-product
registers.  (The old per-tap ``limb_dot_general`` paid kh*kw recombines per
tile AND summed the taps in f32, silently losing bit-exactness once partial
sums passed 2^24 -- the deep-Cin VGG layers.)

Overflow bound: each int32 accumulator element sums kh*kw*cin digit-product
terms.  :func:`int_accum_bound` gives the worst case (the Karatsuba mid
accumulator dominates at 6*half^2 per term); the ops wrapper checks it fits
int31 and falls back to the im2col-GEMM otherwise, so the kernel itself only
asserts.

The dequant scale (per-sample x per-channel) is fused into the kernel
epilogue, immediately after the single recombine.  Bias add + activation are
fused one level up, in the ops wrapper's jit scope (one user-level call, one
XLA epilogue fusion): folding them into the kernel body itself would let the
backend contract the dequant multiply and the bias add into an FMA, breaking
the bitwise fused==unfused contract (see _conv_kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.substrate import limb_partials, limb_recombine

_CIN_DNUMS = (((2,), (0,)), ((), ()))  # (bh, WO, Cin) x (Cin, bc)


def limb_term_bound(variant: str, base_bits: int) -> int:
    """Worst-case |contribution| of ONE contraction term to the widest int32
    partial accumulator: the Karatsuba mid term is bounded by 6*half^2
    (|(Ah+Al)(Bh+Bl)| <= 4*half^2 plus the subtracted p_hh and p_ll),
    schoolbook's by 2*half^2 (Ah*Bl + Al*Bh); hh/ll terms are at most
    half^2.  The ONE definition every overflow model derives from
    (``int_accum_bound``, the implicit kernel's ``max_cin_block`` /
    ``recombine_schedule`` / wrap-free assert)."""
    half = 1 << (base_bits - 1)
    return (6 if variant == "karatsuba" else 2) * half * half


def int_accum_bound(kh: int, kw: int, cin: int, *, variant: str,
                    base_bits: int) -> int:
    """Worst-case |value| of the widest int32 partial accumulator element.

    Balanced digits lie in [-half, half-1], half = 2^(base_bits-1); one
    term contributes at most :func:`limb_term_bound`.  The systolic path
    accumulates kh*kw*cin such terms in int32, so callers must keep this
    below 2^31 (the ops wrapper falls back to the implicit GEMM when a
    layer shape violates it; every systolic-routed layer of AlexNet/VGG16/
    VGG19 satisfies it -- the deepest, 3x3 cin=512, with ~19x headroom).
    """
    return limb_term_bound(variant, base_bits) * kh * kw * cin


#: Lanes of one vreg: a staged channel chunk is at most this wide.
LANES = 128


def staging_shape(rows: int, width: int, channels: int) -> tuple:
    """Shape of the VMEM buffer :func:`stage_rows` fills.

    (nc, rows, width, lc) with nc * lc == channels: Mosaic lowers a
    strided load only from a buffer at most one lane tile wide, so wider
    channel counts are split into 128-lane chunks.
    """
    if channels <= LANES:
        return (1, rows, width, channels)
    assert channels % LANES == 0, (
        f"channels={channels}: a staged block wider than {LANES} lanes must "
        f"be a multiple of {LANES}")
    return (channels // LANES, rows, width, LANES)


def staging_dtype(dtype):
    """32-bit twin of ``dtype``: Mosaic strided loads need 32-bit data."""
    return jnp.float32 if jnp.issubdtype(dtype, jnp.floating) else jnp.int32


def stage_rows(xs_ref, x0_ref, x1_ref) -> None:
    """Copy the dual row-block binding (blocks i and i+1) into ``xs_ref``.

    Every conv kernel binds its input twice so a row block plus its halo
    is resident; staging both halves in one buffer turns every tap into a
    ref load (:func:`load_window`) -- Mosaic has no strided vector slice.
    """
    nc, lc = xs_ref.shape[0], xs_ref.shape[-1]
    r = x0_ref.shape[1]
    for c in range(nc):
        lanes = pl.ds(c * lc, lc)
        xs_ref[c, pl.ds(0, r)] = x0_ref[0, :, :, lanes].astype(xs_ref.dtype)
        xs_ref[c, pl.ds(r, r)] = x1_ref[0, :, :, lanes].astype(xs_ref.dtype)


def load_window(xs_ref, r0: int, nr: int, c0: int, ncols: int,
                stride: int) -> jax.Array:
    """Rows r0 + stride*i and columns c0 + stride*j of the staged buffer,
    all channels: (nr, ncols, channels)."""
    parts = [xs_ref[c, pl.ds(r0, nr, stride=stride),
                    pl.ds(c0, ncols, stride=stride), :]
             for c in range(xs_ref.shape[0])]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


def _conv_kernel(
    *refs, kh, kw, stride, bh, wo, variant, base_bits, has_scale,
):
    it = iter(refs)
    x0_ref, x1_ref, w_ref = next(it), next(it), next(it)
    scale_ref = next(it) if has_scale else None
    o_ref, xs_ref = next(it), next(it)
    bc = o_ref.shape[-1]

    # Two row-blocks give bh*stride*2 input rows: enough for the halo since
    # bh*stride >= (kh - stride) is checked at call time.
    stage_rows(xs_ref, x0_ref, x1_ref)

    def taps():
        for dy in range(kh):
            for dx in range(kw):
                yield (load_window(xs_ref, dy, bh, dx, wo, stride),
                       w_ref[dy, dx])  # (bh, wo, Cin), (Cin, bc)

    if variant == "native":
        out = jnp.zeros((bh, wo, bc), jnp.float32)
        for rows, wtap in taps():
            out = out + jax.lax.dot_general(
                rows, wtap, _CIN_DNUMS, preferred_element_type=jnp.float32
            )
    else:
        # Three int32 partial accumulators held across ALL kh*kw taps -- the
        # partial-product registers.  |acc| < 2^31 by int_accum_bound.
        acc_hh = jnp.zeros((bh, wo, bc), jnp.int32)
        acc_mid = jnp.zeros((bh, wo, bc), jnp.int32)
        acc_ll = jnp.zeros((bh, wo, bc), jnp.int32)
        for rows, wtap in taps():
            p_hh, p_mid, p_ll = limb_partials(
                rows, wtap, _CIN_DNUMS, variant=variant, base_bits=base_bits
            )
            acc_hh = acc_hh + p_hh
            acc_mid = acc_mid + p_mid
            acc_ll = acc_ll + p_ll
        # The ONE recombine per output tile (grep-tested single call site).
        out = limb_recombine(
            acc_hh, acc_mid, acc_ll, base_bits=base_bits, dtype=jnp.float32
        )

    # Kernel epilogue: the dequant scale rides the single recombine's output.
    # Bias/activation deliberately live one level up (the ops wrapper, same
    # jit scope): an in-kernel mul+add gets contracted into an FMA by the
    # backend (even across lax.optimization_barrier), which would skip the
    # dequant multiply's own rounding and drift the fused logits one ulp off
    # the unfused pipeline -- the bitwise fused==unfused differential
    # contract (DESIGN.md section 7.3).  The pallas output materialization
    # is what pins fl(raw*scale) before the bias add.
    if has_scale:
        out = out * scale_ref[0]            # (1, bc) broadcasts over (bh, wo, bc)
    o_ref[0] = out


def conv2d_systolic_raw(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int = 1,
    out_h: int | None = None,
    block_h: int = 8,
    block_c: int = 128,
    variant: str = "native",
    base_bits: int = 7,
    scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """x: (N, H, W, Cin) pre-padded; w: (KH, KW, Cin, Cout).

    ``variant``: "native" | "karatsuba" | "schoolbook".

    ``scale`` (N, Cout, optional) is the per-sample x per-channel dequant
    product, multiplied in the kernel epilogue right after the single
    recombine.  Requirements (the ops wrapper arranges them):
      * out_h (output rows to produce; default derived from H) divisible by
        block_h,
      * H >= (out_h/block_h + 1) * block_h * stride  (one spare halo block),
      * Cout divisible by block_c,
      * integer variants: int_accum_bound(kh, kw, cin) < 2^31.
    Returns (N, out_h, WO, Cout) f32 (un-dequantized unless ``scale`` given).
    """
    n, h, wdim, cin = x.shape
    kh, kw, _, cout = w.shape
    if variant != "native":
        bound = int_accum_bound(kh, kw, cin, variant=variant,
                                base_bits=base_bits)
        assert bound < 2**31, (
            f"int32 accumulator overflow: worst case {bound} >= 2^31 for "
            f"kh*kw*cin={kh * kw * cin}; route this layer through im2col"
        )
    ho = out_h if out_h is not None else (h - kh) // stride + 1
    wo = (wdim - kw) // stride + 1
    bh = block_h
    bc = min(block_c, cout)
    assert ho % bh == 0, (ho, bh)
    assert cout % bc == 0, (cout, bc)
    assert bh * stride >= kh - stride, "halo: need block_h*stride >= kh-stride"
    n_row_blocks = ho // bh
    assert h >= (n_row_blocks + 1) * bh * stride, "need one spare halo block"
    grid = (n, n_row_blocks, cout // bc)
    kernel = functools.partial(
        _conv_kernel,
        kh=kh, kw=kw, stride=stride, bh=bh, wo=wo,
        variant=variant, base_bits=base_bits,
        has_scale=scale is not None,
    )
    row_rows = bh * stride
    nin_blocks = h // row_rows
    in_specs = [
        pl.BlockSpec(
            (1, row_rows, wdim, cin), lambda i, j, c: (i, j, 0, 0)
        ),
        pl.BlockSpec(
            (1, row_rows, wdim, cin),
            lambda i, j, c, nb=nin_blocks: (i, jnp.minimum(j + 1, nb - 1), 0, 0),
        ),
        pl.BlockSpec((kh, kw, cin, bc), lambda i, j, c: (0, 0, 0, c)),
    ]
    operands = [x, x, w]  # x bound twice: row-blocks i and i+1 form the halo
    if scale is not None:
        assert scale.shape == (n, cout), (scale.shape, (n, cout))
        # (n, 1, cout) so the block's last two dims are (full 1, bc): the
        # TPU (8, 128) tiling rule refuses a (1, bc) block of (n, cout).
        in_specs.append(pl.BlockSpec((1, 1, bc), lambda i, j, c: (i, 0, c)))
        operands.append(scale.astype(jnp.float32).reshape(n, 1, cout))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, wo, bc), lambda i, j, c: (i, j, 0, c)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, cout), jnp.float32),
        scratch_shapes=[pltpu.VMEM(staging_shape(2 * row_rows, wdim, cin),
                                   staging_dtype(x.dtype))],
        interpret=interpret,
    )(*operands)
