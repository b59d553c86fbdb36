"""Pallas TPU kernel: implicit-GEMM convolution on the KOM substrate.

The materialized im2col path (``core/systolic.conv2d_im2col``) pays a
KH*KW x HBM blowup before the GEMM ever runs: every input element is
written into the patch matrix once per tap that reads it (~9x for a 3x3
layer).  This kernel runs the *same GEMM* -- M = N*HO*WO patch rows,
K = KH*KW*Cin, N = Cout -- without the patch matrix ever existing in HBM:
the grid tiles (M, Cout, K) and each A-block's patch rows are gathered
straight from the padded NHWC input via BlockSpec index maps (the dual
row-block halo binding the systolic kernel introduced), with the per-tap
shift/stride slicing done on the VMEM-resident block.

Grid: ``(N, HO/bm, Cout/bc, Cin/bk)`` -- the K axis of the GEMM is walked
as ``bk``-channel chunks with the KH*KW taps unrolled inside each step, so
one grid step contracts a ``(kh*kw*bk)``-term slice of K.  Like the KOM
GEMM kernel, the integer variants accumulate the three limb partial
products in int32 VMEM scratch across K steps and recombine on the last
step.

Per-K-block recombine schedule: a single int32 accumulation across all of
K is only exact while ``int_accum_bound(kh, kw, cin) < 2^31`` -- the bound
that forces the systolic engine to give up on deep-Cin layers.  Here the
schedule folds the int32 partials into an f32 group accumulator every
``fold_every`` K steps (:func:`recombine_schedule`), each group sized so
its worst-case int32 accumulation cannot wrap.  Layers under the bound get
``fold_every = nk`` -- exactly one recombine, PR 3's single-recombine
contract, bitwise equal to the materialized im2col GEMM.  Layers over the
bound become a short, deterministic sequence of exact group sums -- the
first KOM path with no practical depth limit, which is where the
``int_accum_bound`` reroutes now land.

Activation quantization is per PATCH (one scale per output position), the
same granularity the materialized path gets from per-row activation quant
on the patch matrix -- it happens in-kernel, on the gathered VMEM rows, so
neither the patch matrix nor its quantized twin is ever written to HBM.
The per-patch x per-channel dequant scale multiplies in the kernel
epilogue right after the last fold; bias/activation stay one level up in
the ops wrapper (the fused==unfused bitwise placement, DESIGN.md
section 7.3/7.4).

Float variants stream the same dataflow: ``native`` does f32 dots into one
f32 accumulator; ``bf16x3``/``bf16x6`` run the multi-pass bf16 emulation
schedules per tap -- the bf16 policies no longer materialize patches
either.

Fused dataflow epilogue (DESIGN.md section 7.7): with ``pool=(pw, ps)``
the kernel maxpools the dequantized output tile in VMEM before the HBM
writeback -- the grid's row axis then walks POOLED row blocks, each
kernel invocation dequantizing ``bm + (pw - ps)`` conv rows (the pool
window's overhang past the block comes from the same dual row-block halo
binding that feeds the conv taps, so windows straddling the row-block
seam are exact).  Rows past the true conv height are masked to -inf
before the max.  With ``cell_scale`` given, the input is the PREVIOUS
layer's ``pool_quant`` handoff: already-quantized int16 pixels plus their
per-pixel (cell-upsampled) scales; each tap then skips quantization
entirely -- exact int dot, immediate recombine, and a fused multiply by
the tap's scale plane into the f32 accumulator (K-step outer, taps
inner, the accumulation order the lax mirror reproduces bitwise).
``pipeline=True`` declares the grid's spatial axes parallel and the K
axis arbitrary via ``dimension_semantics``, letting Mosaic double-buffer
the next K step's A/B tile DMAs behind the current limb passes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.karatsuba import float_split
from repro.core.substrate import limb_partials, limb_recombine

from .conv2d import (LANES, int_accum_bound, limb_term_bound, load_window,
                     stage_rows, staging_dtype, staging_shape)

_CIN_DNUMS = (((2,), (0,)), ((), ()))  # (bm, WO, bk) x (bk, bc)

INT_VARIANTS = ("karatsuba", "schoolbook")

#: bf16 emulation pass schedules: limb-index pairs per variant (DESIGN.md
#: section 2.2; same schedules as karatsuba.bf16xn_dot_general).
_BF16_PAIRS = {
    "bf16x3": (2, ((0, 0), (0, 1), (1, 0))),
    "bf16x6": (3, ((0, 0), (0, 1), (1, 0), (0, 2), (1, 1), (2, 0))),
}


def max_cin_block(kh: int, kw: int, *, variant: str, base_bits: int) -> int:
    """Largest bk whose single K-step (kh*kw*bk terms) cannot wrap int32."""
    return max((2**31 - 1) // (limb_term_bound(variant, base_bits) * kh * kw),
               1)


def recombine_schedule(kh: int, kw: int, cin: int, block_cin: int, *,
                       variant: str, base_bits: int) -> int:
    """K steps between int32 -> f32 partial folds (``fold_every``).

    When the whole contraction fits int32 (``int_accum_bound < 2^31``) the
    schedule is a SINGLE fold on the last K step -- the one-recombine
    contract, bitwise equal to the materialized im2col GEMM.  Deeper layers
    fold every ``floor((2^31-1) / (per_term*kh*kw*block_cin))`` steps, so
    each group's worst-case int32 accumulation is provably wrap-free and
    the result is a short deterministic sum of exact group recombines.
    """
    nk = -(-cin // block_cin)
    if int_accum_bound(kh, kw, cin, variant=variant,
                       base_bits=base_bits) < 2**31:
        return nk
    every = (2**31 - 1) // (limb_term_bound(variant, base_bits)
                            * kh * kw * block_cin)
    if every < 1:
        raise ValueError(
            f"block_cin={block_cin} too wide for wrap-free int32 groups at "
            f"kh*kw={kh * kw}: need block_cin <= "
            f"{max_cin_block(kh, kw, variant=variant, base_bits=base_bits)}")
    return min(every, nk)


def group_spans(cin: int, block_cin: int, fold_every: int) -> tuple:
    """Channel spans [(c0, c1), ...] of the recombine groups.

    Group boundaries sit at ``fold_every`` K-step (= ``block_cin``-channel)
    multiples -- the host mirror in the ops wrapper contracts each span in
    one exact int32 pass, reproducing the kernel's fold points bitwise.
    """
    step = fold_every * block_cin
    return tuple((c0, min(c0 + step, cin)) for c0 in range(0, cin, step))


def _implicit_kernel(
    *refs, kh, kw, stride, bm, wo, variant, base_bits, qmax, nk, fold_every,
    has_scale, handoff, pool, out_rows,
):
    it = iter(refs)
    x0_ref, x1_ref, w_ref = next(it), next(it), next(it)
    if handoff:
        s0_ref, s1_ref = next(it), next(it)
        wscale_ref = next(it)
        ascale_refs = None
    else:
        s0_ref = s1_ref = None
        if has_scale:
            ascale_refs = (next(it),) if pool is None else (next(it), next(it))
            wscale_ref = next(it)
        else:
            ascale_refs, wscale_ref = None, None
    o_ref = next(it)
    integer = variant in INT_VARIANTS
    if integer and not handoff:
        acc_hh, acc_mid, acc_ll, acc_f = next(it), next(it), next(it), next(it)
    else:
        acc_f = next(it)
    xs_ref = next(it)
    ss_ref = next(it) if handoff else None
    # Conv rows this invocation must produce: the pool fusion's window
    # overhang past the row block ((pw - ps) extra rows) reads the SAME
    # dual halo binding the conv taps already need.
    bm_eff = bm if pool is None else bm + (pool[0] - pool[1])
    k = pl.program_id(3)
    row_block = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_f[...] = jnp.zeros_like(acc_f)
        if integer and not handoff:
            acc_hh[...] = jnp.zeros_like(acc_hh)
            acc_mid[...] = jnp.zeros_like(acc_mid)
            acc_ll[...] = jnp.zeros_like(acc_ll)

    # Dual row-block binding (index maps i and i+1): 2*bm*stride input rows
    # cover the bm output rows plus the kh-stride halo, staged in one VMEM
    # buffer so each tap is a (strided) ref load.
    stage_rows(xs_ref, x0_ref, x1_ref)  # (2*bm*s, Wp, bk)
    if handoff:
        rr = s0_ref.shape[1]
        ss_ref[pl.ds(0, rr)] = s0_ref[0]
        ss_ref[pl.ds(rr, rr)] = s1_ref[0]  # (2*bm*s, Wp)

    def taps():
        for dy in range(kh):
            for dx in range(kw):
                yield dy, dx, load_window(
                    xs_ref, dy, bm_eff, dx, wo, stride,
                ), w_ref[dy, dx]  # (bm_eff, wo, bk), (bk, bc)

    if handoff:
        # Pre-quantized handoff input (pool_quant producer upstream): the
        # pixels arrive as ints with per-pixel cell scales, so each tap is
        # an exact int dot recombined IMMEDIATELY and folded into acc_f
        # through the tap's scale plane -- K-step outer, taps inner, the
        # f32 accumulation order the lax mirror reproduces bitwise.
        for dy, dx, rows, wtap in taps():
            q = rows.astype(jnp.int32)
            stap = ss_ref[pl.ds(dy, bm_eff, stride=stride),
                          pl.ds(dx, wo, stride=stride)]  # (bm_eff, wo)
            p_hh, p_mid, p_ll = limb_partials(
                q, wtap, _CIN_DNUMS, variant=variant, base_bits=base_bits)
            rec = limb_recombine(p_hh, p_mid, p_ll,
                                 base_bits=base_bits, dtype=jnp.float32)
            acc_f[...] += stap[..., None] * rec
    elif variant == "native":
        for _, _, rows, wtap in taps():
            acc_f[...] += jax.lax.dot_general(
                rows, wtap, _CIN_DNUMS, preferred_element_type=jnp.float32)
    elif variant in _BF16_PAIRS:
        terms, pairs = _BF16_PAIRS[variant]
        for _, _, rows, wtap in taps():
            als, bls = float_split(rows, terms), float_split(wtap, terms)
            for i, j in pairs:
                acc_f[...] += jax.lax.dot_general(
                    als[i], bls[j], _CIN_DNUMS,
                    preferred_element_type=jnp.float32)
    else:
        # Per-PATCH quantization of the gathered rows, in VMEM: the same
        # scale granularity the materialized path gets from per-row quant on
        # the patch matrix, with neither matrix ever written to HBM.
        s = _scale_rows(ascale_refs, bm_eff)[..., None]  # (bm_eff, wo, 1)
        for _, _, rows, wtap in taps():
            q = jnp.clip(jnp.round(rows / s), -qmax, qmax).astype(jnp.int32)
            p_hh, p_mid, p_ll = limb_partials(
                q, wtap, _CIN_DNUMS, variant=variant, base_bits=base_bits)
            acc_hh[...] += p_hh
            acc_mid[...] += p_mid
            acc_ll[...] += p_ll

        # The per-K-block recombine schedule: fold the exact int32 partials
        # into the f32 group accumulator every `fold_every` steps (and on
        # the last).  Single-group layers hit this exactly once (grep-tested
        # call site, alongside the handoff path's per-tap recombine above).
        @pl.when(jnp.logical_or((k + 1) % fold_every == 0, k == nk - 1))
        def _fold():
            acc_f[...] += limb_recombine(
                acc_hh[...], acc_mid[...], acc_ll[...],
                base_bits=base_bits, dtype=jnp.float32)
            acc_hh[...] = jnp.zeros_like(acc_hh)
            acc_mid[...] = jnp.zeros_like(acc_mid)
            acc_ll[...] = jnp.zeros_like(acc_ll)

    @pl.when(k == nk - 1)
    def _emit():
        out = acc_f[...]
        if handoff:
            # Activation scales were folded per tap; only the per-channel
            # weight scale remains.
            out = out * wscale_ref[...]
        elif has_scale:
            # Dequant epilogue: per-patch x per-channel scale product, the
            # same two f32 multiplies (s_row*s_col, then raw*t) as the
            # materialized GEMM's dequant -- bias/activation live one level
            # up (ops wrapper) for the bitwise fused==unfused contract.
            t = _scale_rows(ascale_refs, bm_eff)[..., None] * wscale_ref[...]
            out = out * t
        if pool is not None:
            pw, ps = pool
            bm_p, wo_p = bm // ps, (wo - pw) // ps + 1
            # Conv rows past the true output height hold row-padding
            # garbage; mask them to -inf so they can never win the max
            # (pooled rows made ONLY of masked rows are sliced off by the
            # wrapper).
            ridx = row_block * bm + jax.lax.broadcasted_iota(
                jnp.int32, out.shape, 0)
            # The window taps are strided loads from the (now spent) f32
            # accumulator: Mosaic has no strided vector slice.
            acc_f[...] = jnp.where(ridx < out_rows, out, -jnp.inf)
            pooled = None
            for py in range(pw):
                for px in range(pw):
                    m = acc_f[pl.ds(py, bm_p, stride=ps),
                              pl.ds(px, wo_p, stride=ps), :]
                    pooled = m if pooled is None else jnp.maximum(pooled, m)
            out = pooled
        o_ref[0] = out


def _scale_rows(ascale_refs, bm_eff):
    """First bm_eff per-patch scale rows from the (dual, under pool) binding."""
    if len(ascale_refs) == 1:
        return ascale_refs[0][0, 0]
    s = jnp.concatenate([ascale_refs[0][0, 0], ascale_refs[1][0, 0]], axis=0)
    return jax.lax.slice(s, (0, 0), (bm_eff, s.shape[1]))


def conv2d_implicit_raw(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int = 1,
    out_h: int | None = None,
    block: tuple[int, int, int] = (8, 128, 512),
    variant: str = "native",
    base_bits: int = 7,
    qmax: int = 0,
    ascale: jax.Array | None = None,
    wscale: jax.Array | None = None,
    fold_every: int | None = None,
    true_cin: int | None = None,
    pool: tuple[int, int] | None = None,
    out_rows: int | None = None,
    cell_scale: jax.Array | None = None,
    pipeline: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """x: (N, H, W, Cin) pre-padded NHWC; w: (KH, KW, Cin, Cout).

    ``block = (bm, bc, bk)``: output-row / Cout / Cin-chunk tile sizes.
    Integer variants take pre-split operands: ``w`` integer-valued
    (int16 container), ``ascale`` (N, out_h, WO) per-patch activation
    scales, ``wscale`` (1, Cout) per-channel weight scales, ``qmax`` the
    clip range.  Requirements (the ops wrapper arranges them): out_h % bm
    == 0, Cout % bc == 0, Cin % bk == 0, bm*stride >= kh-stride, one spare
    halo row block, and for integer variants fold_every*kh*kw*bk wrap-free
    (:func:`recombine_schedule`).  ``true_cin``: the layer's REAL channel
    count when the caller zero-padded Cin up to a bk multiple -- padded
    channels contribute exact zeros, so the wrap-free model must not count
    them.  Returns (N, out_h, WO, Cout) f32.

    ``pool=(pw, ps)`` fuses a VALID pw x pw / stride-ps maxpool into the
    epilogue: the return becomes (N, (out_h/bm)*(bm/ps), (WO-pw)//ps+1,
    Cout) with ``out_rows`` (the TRUE conv height before row padding)
    masking padded conv rows out of the max.  Needs bm % ps == 0 and the
    window overhang inside the dual halo: (bm+(pw-ps)-1)*stride + kh <=
    2*bm*stride.  ``cell_scale`` (N, H, W) switches the input to a
    pre-quantized handoff (int values in ``x``, per-pixel scales here;
    ``ascale`` must be None).  ``pipeline`` marks the K grid axis
    arbitrary/spatial axes parallel so Mosaic double-buffers the next K
    step's DMAs behind the current passes (TPU-only knob; harmless
    elsewhere).
    """
    n, h, wdim, cin = x.shape
    kh, kw, _, cout = w.shape
    if true_cin is None:
        true_cin = cin
    bm, bc, bk = block
    bc = min(bc, cout)
    bk = min(bk, cin)
    integer = variant in INT_VARIANTS
    handoff = cell_scale is not None
    ho = out_h if out_h is not None else (h - kh) // stride + 1
    wo = (wdim - kw) // stride + 1
    assert ho % bm == 0, (ho, bm)
    assert cout % bc == 0, (cout, bc)
    assert cin % bk == 0, (cin, bk)
    assert bm * stride >= kh - stride, "halo: need bm*stride >= kh-stride"
    if handoff:
        assert integer and ascale is None
        assert cell_scale.shape == (n, h, wdim), cell_scale.shape
        assert wscale is not None and wscale.shape == (1, cout)
    nk = cin // bk
    if integer and not handoff:
        if fold_every is None:
            fold_every = recombine_schedule(kh, kw, true_cin, bk,
                                            variant=variant,
                                            base_bits=base_bits)
        # Worst-case terms per group: a group spans fold_every*bk channel
        # slots, but only real (non-zero-padded) channels can contribute.
        group_terms = min(fold_every * bk, true_cin)
        assert limb_term_bound(variant, base_bits) * kh * kw * group_terms \
            < 2**31, "recombine group too deep for wrap-free int32 accumulation"
    else:
        # Handoff recombines every tap immediately: one K step (kh*kw*bk
        # terms) is the whole int32 accumulation window.
        fold_every = nk
        if handoff:
            assert limb_term_bound(variant, base_bits) * kh * kw * bk < 2**31
    bm_eff, bm_p, wo_p = bm, bm, wo
    if pool is not None:
        pw, ps = pool
        assert bm % ps == 0, (bm, ps)
        assert bc <= LANES, f"bc={bc}: pooled strided loads take <= {LANES}"
        bm_eff = bm + (pw - ps)
        bm_p, wo_p = bm // ps, (wo - pw) // ps + 1
        assert out_rows is not None and 0 < out_rows <= ho
        assert (bm_eff - 1) * stride + kh <= 2 * bm * stride, \
            "pool overhang must fit the dual row-block halo"
    n_row_blocks = ho // bm
    row_rows = bm * stride
    assert h >= (n_row_blocks + 1) * row_rows, "need one spare halo block"
    nin_blocks = h // row_rows
    grid = (n, n_row_blocks, cout // bc, nk)
    kernel = functools.partial(
        _implicit_kernel,
        kh=kh, kw=kw, stride=stride, bm=bm, wo=wo, variant=variant,
        base_bits=base_bits, qmax=qmax, nk=nk, fold_every=fold_every,
        has_scale=ascale is not None, handoff=handoff, pool=pool,
        out_rows=out_rows,
    )
    in_specs = [
        pl.BlockSpec((1, row_rows, wdim, bk), lambda b, i, j, c: (b, i, 0, c)),
        pl.BlockSpec(
            (1, row_rows, wdim, bk),
            lambda b, i, j, c, nb=nin_blocks: (b, jnp.minimum(i + 1, nb - 1), 0, c),
        ),
        pl.BlockSpec((kh, kw, bk, bc), lambda b, i, j, c: (0, 0, c, j)),
    ]
    operands = [x, x, w]  # x bound twice: row blocks i and i+1 form the halo
    if handoff:
        # Per-pixel scales ride the same dual row-block halo binding as x.
        in_specs.append(pl.BlockSpec(
            (1, row_rows, wdim), lambda b, i, j, c: (b, i, 0)))
        in_specs.append(pl.BlockSpec(
            (1, row_rows, wdim),
            lambda b, i, j, c, nb=nin_blocks: (b, jnp.minimum(i + 1, nb - 1), 0),
        ))
        in_specs.append(pl.BlockSpec((1, bc), lambda b, i, j, c: (0, j)))
        sc = cell_scale.astype(jnp.float32)
        operands += [sc, sc, wscale.astype(jnp.float32)]
    elif ascale is not None:
        assert ascale.shape == (n, ho, wo), (ascale.shape, (n, ho, wo))
        assert wscale is not None and wscale.shape == (1, cout)
        # Scales as (n, ho/bm, bm, wo): the block's last two dims are then
        # the array's full (bm, wo), which the TPU tiling rule admits for
        # any bm.
        in_specs.append(pl.BlockSpec((1, 1, bm, wo),
                                     lambda b, i, j, c: (b, i, 0, 0)))
        if pool is not None:
            # The pool overhang's conv rows need the NEXT row block's
            # per-patch scales too -- dual-bind like x.
            in_specs.append(pl.BlockSpec(
                (1, 1, bm, wo),
                lambda b, i, j, c, nb=n_row_blocks: (
                    b, jnp.minimum(i + 1, nb - 1), 0, 0),
            ))
        in_specs.append(pl.BlockSpec((1, bc), lambda b, i, j, c: (0, j)))
        asc = ascale.astype(jnp.float32).reshape(n, n_row_blocks, bm, wo)
        operands += ([asc, asc] if pool is not None else [asc])
        operands.append(wscale.astype(jnp.float32))
    if integer and not handoff:
        scratch = [pltpu.VMEM((bm_eff, wo, bc), jnp.int32) for _ in range(3)]
    else:
        scratch = []
    scratch.append(pltpu.VMEM((bm_eff, wo, bc), jnp.float32))
    scratch.append(pltpu.VMEM(staging_shape(2 * row_rows, wdim, bk),
                              staging_dtype(x.dtype)))
    if handoff:
        scratch.append(pltpu.VMEM((2 * row_rows, wdim), jnp.float32))
    kwargs = {}
    if pipeline and not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, bm_p, wo_p, bc), lambda b, i, j, c: (b, i, 0, j)),
        out_shape=jax.ShapeDtypeStruct(
            (n, n_row_blocks * bm_p, wo_p, cout), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
        **kwargs,
    )(*operands)
