"""Jitted public wrappers for the Pallas conv kernels.

Two engines share this module:

* :func:`conv2d_systolic` -- the direct systolic engine (whole-Cin taps,
  int16 activation streams, per-SAMPLE scales).  Handles SAME/VALID padding
  via the substrate's shared plan, the spare halo row-block, output-channel
  padding and the fused dequantization/bias/activation epilogue.
* :func:`conv2d_implicit` -- the implicit-GEMM engine (K-tiled over
  KH*KW*Cin, per-PATCH scales, the per-K-block recombine schedule).  The
  patch matrix never exists in HBM; off-TPU the same dataflow runs as a
  bitwise-identical streamed lax mirror (:func:`_stream_conv_int`) instead
  of interpret-mode Pallas, so CPU CI and serving measure the real
  streaming schedule rather than the interpreter.

Weights may arrive as a cached :class:`~repro.core.substrate.QWeight`
(quantized once, per-output-channel scales) on either engine; a float HWIO
weight is quantized on the fly with the SAME per-output-channel granularity
(:func:`~repro.core.substrate.quantize_weight`), so float-weight and
QWeight calls agree bitwise.

The int32 accumulator overflow bound (:func:`~repro.kernels.conv2d.conv2d.
int_accum_bound`) is checked here: a layer whose kh*kw*cin is too deep for
exact whole-contraction int32 accumulation reroutes from the systolic
engine to :func:`conv2d_implicit`, whose per-K-block recombine schedule is
wrap-free at any depth.

Tile schedules (block_h/block_c, bm/bc/bk) default to the VMEM-aware
autotuner (:mod:`repro.core.tuning`): a persistent per-layer-shape cache
consulted at trace time, so ``cnn_forward`` and ``CNNServeEngine`` pick up
tuned tiles for every conv layer without plumbing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.karatsuba import bf16xn_dot_general
from repro.core.substrate import (
    QActivation,
    QWeight,
    balanced_split,
    conv_pads,
    dequantize_weight,
    kom_qmax,
    limb_recombine,
    quantize_symmetric,
    quantize_weight,
)

from .conv2d import LANES, conv2d_systolic_raw, int_accum_bound
from .implicit_gemm import (
    INT_VARIANTS,
    conv2d_implicit_raw,
    group_spans,
    recombine_schedule,
)
from .winograd import (
    WINOGRAD_OUTPUT_SCALE,
    conv2d_winograd_raw,
    stream_conv_winograd,
    tile_scale_grid,
    tile_scales_upsampled,
    winograd_accum_bound,
    winograd_mirror_operands,
    winograd_scale_eligible,
    winograd_weight_planes,
)

_NHWC_DNUMS = (((3,), (0,)), ((), ()))  # (n, ho, wo, ck) x (ck, bc)


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


#: Output columns are padded to a multiple of this: the kernels flatten a
#: (rows, wo, cin) tap to (rows*wo, cin) for the MXU, and Mosaic only
#: lowers that shape cast when wo is a whole number of sublane tiles.
_WO_ALIGN = 8


def _plan(h, w, kh, kw, stride, padding, block_h):
    """Shared SAME/VALID plan + row padding for the spare halo block.

    Returns (ho, wo, ho_pad, wo_pad, pads): the true output size, the
    padded one the kernel computes (rows to the row block, columns to
    :data:`_WO_ALIGN`) and the input pads that produce it.  Extra rows and
    columns are zeros whose outputs are sliced off.
    """
    ho, wo, pads = conv_pads(h, w, kh, kw, stride, padding)
    # Round HO up to the row-block, then pad rows so a spare halo block exists.
    ho_pad = -(-ho // block_h) * block_h
    rows_needed = (ho_pad // block_h + 1) * block_h * stride
    h_padded = h + pads[0][0] + pads[0][1]
    extra_rows = max(rows_needed - h_padded, 0)
    wo_pad = -(-wo // _WO_ALIGN) * _WO_ALIGN
    w_padded = w + pads[1][0] + pads[1][1]
    extra_cols = max((wo_pad - 1) * stride + kw - w_padded, 0)
    pads = ((pads[0][0], pads[0][1] + extra_rows),
            (pads[1][0], pads[1][1] + extra_cols))
    return ho, wo, ho_pad, wo_pad, pads


def _resolve_block(kind, **key):
    from repro.core.tuning import resolve_block  # lazy: tuning imports kernels
    return resolve_block(kind, **key)


@functools.partial(
    jax.jit,
    static_argnames=("stride", "padding", "block_h", "block_c", "variant",
                     "base_bits", "interpret"),
)
def _conv2d_systolic_core(
    x: jax.Array,
    w,
    *,
    stride: int,
    padding: str,
    block_h: int | None,
    block_c: int | None,
    variant: str,
    base_bits: int,
    interpret: bool | None,
) -> jax.Array:
    """The jitted body of :func:`conv2d_systolic`, WITHOUT the epilogue."""
    if interpret is None:
        interpret = _default_interpret()
    n, h, wdim, cin = x.shape
    kh, kw, _, cout = w.shape
    if block_h is None or block_c is None:
        th, tc = _resolve_block("systolic", kh=kh, kw=kw, stride=stride, h=h,
                                cin=cin, cout=cout, variant=variant,
                                base_bits=base_bits)
        block_h = block_h if block_h is not None else th
        block_c = block_c if block_c is not None else tc
    block_h = min(block_h, 32)
    while block_h * stride < kh - stride:  # halo feasibility
        block_h *= 2
    ho, wo, ho_pad, _, pads = _plan(h, wdim, kh, kw, stride, padding, block_h)
    scale = None
    if variant != "native":
        w_vals, w_scale = w.values, w.scale  # cached: no requantization
        # Per-SAMPLE activation scales (axis 0): each image's quantization is
        # independent of its batch-mates, so a request's output is identical
        # whatever microbatch it rides in (the engines' batch-invariance
        # contract, DESIGN.md section 9.3).  The per-sample x per-channel
        # dequant product is folded into the kernel epilogue as an (n, cout)
        # operand.
        qx = quantize_symmetric(x, base_bits=base_bits, axis=0)
        x = qx.values.astype(jnp.int16)
        w = w_vals.astype(jnp.int16)
        ws = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32).reshape(-1),
                              (cout,))
        scale = qx.scale.reshape(n, 1) * ws[None, :]  # (n, cout)
    xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
    bc = min(block_c, cout)
    pc = (-cout) % bc
    if pc:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, pc)))
        if scale is not None:
            scale = jnp.pad(scale, ((0, 0), (0, pc)))
    out = conv2d_systolic_raw(
        xp, w,
        stride=stride, out_h=ho_pad, block_h=block_h, block_c=bc,
        variant=variant, base_bits=base_bits, scale=scale,
        interpret=interpret,
    )
    return out[:, :ho, :wo, :cout]


def conv2d_systolic(
    x: jax.Array,
    w,
    *,
    stride: int = 1,
    padding: str = "SAME",
    block_h: int | None = None,
    block_c: int | None = None,
    variant: str = "native",
    base_bits: int = 7,
    bias: jax.Array | None = None,
    activation: str | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """NHWC conv through the Pallas systolic engine, epilogue fused.

    variant='native': dots in input dtype.  variant='karatsuba' (alias
    'kom') / 'schoolbook': narrow limb passes on the shared substrate with
    THREE int32 partial accumulators across all taps and a single recombine
    in the kernel epilogue (the paper's conv layer, end to end).  Integer
    variants symmetric-quantize the activations per SAMPLE per call; ``w``
    may be a float HWIO array -- quantized HERE, outside the jitted core,
    with the SAME per-output-channel granularity as a cached QWeight, so
    float-weight and QWeight calls agree bitwise -- or a QWeight (cached
    int16 values + per-output-channel scales, quantized once).  The dequant
    scale rides the kernel epilogue; optional ``bias`` (Cout,) and
    ``activation`` ("relu") apply in the caller's regime over the jitted
    core's materialized output (bitwise fused==unfused, DESIGN.md section
    7.3) -- no extra HBM round-trips under an outer jit.

    ``block_h``/``block_c`` default to the autotuner's per-layer-shape
    schedule (:func:`repro.core.tuning.resolve_block`).

    Layers too deep for exact whole-contraction int32 accumulation
    (int_accum_bound >= 2^31, e.g. kh*kw*cin beyond ~87k for int14) reroute
    to :func:`conv2d_implicit`, whose per-K-block recombine schedule keeps
    every partial group wrap-free at any depth.
    """
    if variant == "kom":
        variant = "karatsuba"
    kh, kw, cin = w.shape[0], w.shape[1], w.shape[2]
    if variant != "native":
        if isinstance(w, QWeight):
            base_bits = w.base_bits  # cached weights carry their digit base
        else:
            w = quantize_weight(w, base_bits=base_bits)
        if int_accum_bound(kh, kw, cin, variant=variant,
                           base_bits=base_bits) >= 2**31:
            # Exact whole-contraction int32 accumulation impossible at this
            # depth: stream the patches through the implicit GEMM, whose
            # per-K-block recombine schedule is wrap-free at any depth.
            return conv2d_implicit(x, w, stride=stride, padding=padding,
                                   variant=variant, base_bits=base_bits,
                                   bias=bias, activation=activation,
                                   interpret=interpret)
    elif isinstance(w, QWeight):
        raise TypeError("variant='native' expects a float weight, not QWeight")
    out = _conv2d_systolic_core(
        x, w, stride=stride, padding=padding, block_h=block_h,
        block_c=block_c, variant=variant, base_bits=base_bits,
        interpret=interpret)
    if bias is not None:
        out = out + bias
    if activation == "relu":
        out = jax.nn.relu(out)
    elif activation is not None:
        raise ValueError(f"unknown activation: {activation!r}")
    return out


# ---------------------------------------------------------------------------
# Implicit GEMM: streamed patches, per-patch scales, K-tiled contraction.
# ---------------------------------------------------------------------------

def _patch_scales(xp: jax.Array, kh: int, kw: int, stride: int,
                  qmax: int) -> jax.Array:
    """Per-PATCH activation scales from the padded input, no patch matrix.

    The materialized path quantizes each im2col row with
    ``max|row| / qmax``; the same number is the windowed max of the
    per-pixel channel max -- a reduce_window over |x|, kh*kw times cheaper
    in HBM than materializing the rows.  (fp max is exact whatever the
    reduction order, so this is bitwise the patch-row amax.)
    """
    cmax = jnp.max(jnp.abs(xp.astype(jnp.float32)), axis=3)  # (n, Hp, Wp)
    amax = lax.reduce_window(
        cmax, -jnp.inf, lax.max,
        window_dimensions=(1, kh, kw),
        window_strides=(1, stride, stride),
        padding="VALID",
    )  # (n, HO', WO')
    return jnp.maximum(amax, 1e-12) / qmax


#: Largest integer f32 represents exactly -- the per-dot partial-sum budget
#: of the mirror's f32-digit GEMM strategy.
_F32_EXACT = 1 << 24


def _limb_partials_f32(q, wtap, *, variant, base_bits):
    """The narrow limb passes as f32 GEMMs -- bitwise-equal, host-fast.

    XLA:CPU has no fast integer GEMM (an int8 dot runs ~7x slower than the
    same-shape f32 Eigen contraction), so the mirror runs each pass as an
    f32 dot over K sub-chunks small enough that every WORST-CASE partial
    sum is an exactly-representable f32 integer (< 2^24: karatsuba digit
    sums bound products by 4*half^2, plain digits by half^2).  Converted
    back to int32 and summed, the totals are bit-identical to the MXU int8
    passes in any order -- same digits, same integers, different ALU.
    ``Precision.HIGHEST`` keeps accelerators from downcasting the f32 dot
    (tf32/bf16 would break integer exactness).
    """
    half = 1 << (base_bits - 1)
    per = (4 if variant == "karatsuba" else 1) * half * half
    safe_k = max(_F32_EXACT // per, 1)
    kdim = q.shape[-1]
    ah, al = balanced_split(q, base_bits)
    bh, bl = balanced_split(wtap, base_bits)
    dotf = lambda a, b: lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32), _NHWC_DNUMS,
        precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(jnp.int32)
    hh = mid = ll = jnp.zeros((), jnp.int32)
    for c0 in range(0, kdim, safe_k):
        sl = slice(c0, min(c0 + safe_k, kdim))
        a_h, a_l, b_h, b_l = ah[..., sl], al[..., sl], bh[sl], bl[sl]
        p_hh = dotf(a_h, b_h)
        p_ll = dotf(a_l, b_l)
        if variant == "karatsuba":
            p_mid = dotf(a_h + a_l, b_h + b_l) - p_hh - p_ll
        else:
            p_mid = dotf(a_h, b_l) + dotf(a_l, b_h)
        hh, mid, ll = hh + p_hh, mid + p_mid, ll + p_ll
    return hh, mid, ll


def _stream_conv_int(xp, w_vals, ascale, spans, *, stride, ho, wo, variant,
                     base_bits, qmax):
    """The lax mirror of the integer implicit-GEMM kernel, bitwise.

    Same dataflow, same numbers: per-tap strided slices of the padded
    input, per-patch quantization of the gathered rows, exact int32 limb
    accumulation within each recombine group (``spans``, the kernel's fold
    boundaries), one f32 ``limb_recombine`` per group, groups summed in
    order.  Int accumulation order inside a group is irrelevant (exact), so
    the f32-digit sub-chunked dots (:func:`_limb_partials_f32`) equal the
    kernel's int8 grid steps bitwise.
    """
    kh, kw = w_vals.shape[:2]
    n = xp.shape[0]
    s4 = ascale[..., None]  # (n, ho, wo, 1)
    acc = None
    for c0, c1 in spans:
        p_hh = p_mid = p_ll = jnp.zeros((n, ho, wo, w_vals.shape[-1]),
                                        jnp.int32)
        for dy in range(kh):
            for dx in range(kw):
                rows = lax.slice(
                    xp,
                    (0, dy, dx, c0),
                    (n, dy + (ho - 1) * stride + 1,
                     dx + (wo - 1) * stride + 1, c1),
                    (1, stride, stride, 1),
                )
                q = jnp.clip(jnp.round(rows / s4), -qmax, qmax
                             ).astype(jnp.int32)
                hh, mid, ll = _limb_partials_f32(
                    q, w_vals[dy, dx, c0:c1],
                    variant=variant, base_bits=base_bits)
                p_hh, p_mid, p_ll = p_hh + hh, p_mid + mid, p_ll + ll
        g = limb_recombine(p_hh, p_mid, p_ll, base_bits=base_bits,
                           dtype=jnp.float32)
        acc = g if acc is None else acc + g
    return acc


def _cell_scales(grid, hp, wp):
    """Upsample the (n, th, tw) tile scale grid to per-PIXEL scales.

    Pixel (py, px) of the padded input takes the scale of 4x4/s2 tile
    ``(min(py//2, th-1), min(px//2, tw-1))`` -- every pixel sits inside its
    tile's amax window (the windows overlap by 2), so quantizing with the
    cell scale can never clip past qmax.  This is the handoff analogue of
    :func:`~repro.kernels.conv2d.winograd.tile_scales_upsampled`, which
    upsamples to per-OUTPUT-position scales instead.
    """
    th, tw = grid.shape[1], grid.shape[2]
    ri = jnp.minimum(jnp.arange(hp) // 2, th - 1)
    ci = jnp.minimum(jnp.arange(wp) // 2, tw - 1)
    return grid[:, ri][:, :, ci]


@functools.partial(jax.jit, static_argnames=("base_bits",))
def handoff_quantize(x: jax.Array, *, base_bits: int) -> QActivation:
    """Quantize an activation ONCE per pixel for a 3x3/s1/SAME int consumer.

    THE producer half of the ``pool_quant`` handoff (DESIGN.md section
    7.7), shared verbatim by the fused epilogue and the unfused reference
    pipeline so the bitwise contract is definitional: SAME-pad for the
    consumer's 3x3/s1 conv, build the consumer's 4x4/s2 tile-granular
    scale grid (PR 6's scale plan -- computable from this tensor alone),
    round each cell scale UP to a power of two, and round/clip each
    PADDED pixel with its cell's scale.  Padding pixels quantize to
    exactly 0, so storing the padded int tensor equals re-padding an
    unpadded one with integer zeros.

    Power-of-two scales are what make the consumer's per-tap
    scale-and-accumulate FMA-immune: a multiply by 2^e is EXACT in f32,
    so ``fl(s*rec + acc)`` equals ``fl(fl(s*rec) + acc)`` whether or not
    a backend contracts the multiply-add -- without this, the kernel and
    its lax mirror drift an ulp apart at XLA:CPU's whim.  The cost is at
    most one extra doubling of the quantization step vs the raw tile
    scale, priced into the ``pool_quant`` exactness note (the fusion is
    requant-gated in the planner precisely because it changes the
    quantization recipe).
    """
    qmax = kom_qmax(base_bits)
    n, h, w, c = x.shape
    _, _, pads = conv_pads(h, w, 3, 3, 1, "SAME")
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), pads[0], pads[1], (0, 0)))
    grid = tile_scale_grid(xp, qmax, -(-h // 2), -(-w // 2))
    # Round up to 2^e: frexp gives grid = m * 2^e with m in [0.5, 1), so
    # 2^e is the smallest power of two >= grid.
    _, e = jnp.frexp(grid)
    grid = jnp.ldexp(jnp.float32(1.0), e)
    cs = _cell_scales(grid, xp.shape[1], xp.shape[2])
    q = jnp.clip(jnp.round(xp / cs[..., None]), -qmax, qmax).astype(jnp.int16)
    return QActivation(values=q, scale=grid, base_bits=base_bits, h=h, w=w)


def _stream_conv_handoff(qp, cs, w_vals, *, bk, variant, base_bits):
    """The lax mirror of the handoff-input implicit kernel, bitwise.

    The input arrives pre-quantized (int16 pixels + per-pixel cell
    scales), so there is nothing to quantize and nothing to fold: each
    (K-chunk, tap) contributes one exact int32 limb dot, recombined
    immediately and scaled by the tap's slice of the cell-scale plane.
    The f32 accumulation order -- K-chunk outer, taps inner -- is the
    kernel's grid order, reproduced here term by term.
    """
    kh, kw = w_vals.shape[:2]
    n, hp, wp, cin = qp.shape
    ho, wo = hp - kh + 1, wp - kw + 1
    acc = None
    for c0 in range(0, cin, bk):
        c1 = min(c0 + bk, cin)
        for dy in range(kh):
            for dx in range(kw):
                rows = lax.slice(qp, (0, dy, dx, c0),
                                 (n, dy + ho, dx + wo, c1))
                hh, mid, ll = _limb_partials_f32(
                    rows.astype(jnp.int32), w_vals[dy, dx, c0:c1],
                    variant=variant, base_bits=base_bits)
                rec = limb_recombine(hh, mid, ll, base_bits=base_bits,
                                     dtype=jnp.float32)
                stap = lax.slice(cs, (0, dy, dx), (n, dy + ho, dx + wo))
                g = stap[..., None] * rec
                acc = g if acc is None else acc + g
    return acc


def _stream_conv_float(xp, w, *, stride, ho, wo, variant):
    """Float mirror: per-tap streamed dots (native f32 or bf16xN passes)."""
    kh, kw = w.shape[:2]
    n = xp.shape[0]
    out = jnp.zeros((n, ho, wo, w.shape[-1]), jnp.float32)
    for dy in range(kh):
        for dx in range(kw):
            rows = lax.slice(
                xp,
                (0, dy, dx, 0),
                (n, dy + (ho - 1) * stride + 1,
                 dx + (wo - 1) * stride + 1, xp.shape[3]),
                (1, stride, stride, 1),
            )
            wtap = w[dy, dx]
            if variant == "native":
                out = out + lax.dot_general(
                    rows, wtap, _NHWC_DNUMS,
                    preferred_element_type=jnp.float32)
            else:
                out = out + bf16xn_dot_general(
                    rows, wtap, _NHWC_DNUMS,
                    passes=3 if variant == "bf16x3" else 6)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("stride", "padding", "variant", "base_bits",
                     "block", "fold_every", "use_pallas", "interpret",
                     "pool", "k_pipeline"),
)
def _conv2d_implicit_core(
    x,
    w,
    *,
    stride: int,
    padding: str,
    variant: str,
    base_bits: int,
    block: tuple[int, int, int] | None,
    fold_every: int | None,
    use_pallas: bool | None,
    interpret: bool | None,
    pool: tuple[int, int, str] | None = None,
    k_pipeline: bool = True,
) -> jax.Array:
    """The jitted body of :func:`conv2d_implicit`, WITHOUT the epilogue.

    The jit boundary here is load-bearing: it materializes fl(raw * scale)
    before the caller's bias add (the CPU mirror's analogue of the Pallas
    kernel-output materialization), so XLA cannot contract the dequant
    multiply and the bias add into one FMA -- which would skip the
    multiply's own rounding and break the bitwise fused==unfused contract
    (XLA:CPU contracts mul+add even across lax.optimization_barrier).

    ``pool=(pw, ps, ppad)`` maxpools the dequantized output INSIDE this
    scope, before the boundary -- in the kernel's VMEM epilogue on TPU
    (VALID pools whose row blocks divide by ps; anything else falls back
    to a reduce_window on the kernel output in the same jit scope), a
    reduce_window in the mirror.  fp max is exact selection, so pooling
    here then bias/relu outside equals bias/relu then pool bitwise (the
    bias is per-channel constant over a window and relu is monotone) --
    the ordering DESIGN.md section 7.7 documents.  ``x`` may be a
    :class:`QActivation` handoff (pre-quantized pixels + cell scale grid)
    from an upstream ``pool_quant`` epilogue.
    """
    if variant == "kom":
        variant = "karatsuba"
    integer = variant in INT_VARIANTS
    handoff_in = isinstance(x, QActivation)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = _default_interpret()
    n, h, wdim, cin = x.shape
    kh, kw, _, cout = w.shape
    if isinstance(w, QWeight):
        if integer:
            base_bits = w.base_bits
        else:
            w = dequantize_weight(w)
    qmax = kom_qmax(base_bits)
    if integer:
        if isinstance(w, QWeight):
            w_vals, w_scale = w.values, w.scale
        else:
            qw = quantize_weight(w, base_bits=base_bits)  # per-output-channel
            w_vals, w_scale = qw.values, qw.scale
        w_vals = w_vals.astype(jnp.int16)
        w_scale = jnp.broadcast_to(
            jnp.asarray(w_scale, jnp.float32).reshape(-1), (cout,))
    else:
        w_vals, w_scale = jnp.asarray(w, jnp.float32), None
    if block is None:
        bm, bc, bk = _resolve_block("implicit", kh=kh, kw=kw, stride=stride,
                                    h=h, cin=cin, cout=cout, variant=variant,
                                    base_bits=base_bits)
    else:
        bm, bc, bk = block
    bk = min(bk, cin)
    if integer and fold_every is None:
        fold_every = recombine_schedule(kh, kw, cin, bk, variant=variant,
                                        base_bits=base_bits)
    if not handoff_in:
        x = x.astype(jnp.float32)

    kernel_pool = None
    if pool is not None and use_pallas and pool[2] == "VALID" \
            and bm % pool[1] == 0 \
            and (bm + pool[0] - pool[1] - 1) * stride + kh <= 2 * bm * stride:
        kernel_pool = (pool[0], pool[1])

    if not use_pallas:
        ho, wo, pads = conv_pads(h, wdim, kh, kw, stride, padding)
        if handoff_in:
            # Pre-quantized handoff: the producer already SAME-padded and
            # quantized; contract the ints with per-(K-chunk, tap)
            # recombine-and-scale -- the kernel's accumulation order.
            cs = _cell_scales(x.scale, h + 2, wdim + 2)
            raw = _stream_conv_handoff(
                x.values, cs, w_vals, bk=bk, variant=variant,
                base_bits=base_bits)
            out = raw * w_scale
        elif integer:
            xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
            if winograd_scale_eligible(kh, kw, stride, cin, variant=variant,
                                       base_bits=base_bits):
                # Winograd-eligible layers share the tile-granular scale
                # plan across ALL int paths (the cross-path bitwise
                # contract, DESIGN.md section 7.5).
                s_tile = tile_scale_grid(xp, qmax, -(-ho // 2), -(-wo // 2))
                ascale = tile_scales_upsampled(s_tile, ho, wo)
            else:
                ascale = _patch_scales(xp, kh, kw, stride, qmax)[:, :ho, :wo]
            raw = _stream_conv_int(
                xp, w_vals, ascale, group_spans(cin, bk, fold_every),
                stride=stride, ho=ho, wo=wo, variant=variant,
                base_bits=base_bits, qmax=qmax)
            # Same dequant expression as the kernel epilogue / materialized
            # GEMM: t = s_patch * s_channel, then raw * t.
            out = raw * (ascale[..., None] * w_scale)
        else:
            xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
            out = _stream_conv_float(xp, w_vals, stride=stride, ho=ho, wo=wo,
                                     variant=variant)
    else:
        while bm * stride < kh - stride:  # halo feasibility
            bm *= 2
        ho, wo, ho_pad, wo_pad, pads = _plan(h, wdim, kh, kw, stride,
                                             padding, bm)
        ascale = cs = wsc = None
        if handoff_in:
            # The producer's padded int tensor only needs the spare-halo
            # row padding and the column alignment on top; integer zero
            # pixels (scale 0) contribute exact zero to every partial and
            # are sliced away.
            extra = (max((ho_pad // bm + 1) * bm * stride - (h + 2), 0),
                     wo_pad - wo)
            xp = jnp.pad(x.values, ((0, 0), (0, extra[0]), (0, extra[1]),
                                    (0, 0)))
            cs = jnp.pad(_cell_scales(x.scale, h + 2, wdim + 2),
                         ((0, 0), (0, extra[0]), (0, extra[1])))
        else:
            xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
            if integer:
                if winograd_scale_eligible(kh, kw, stride, cin,
                                           variant=variant,
                                           base_bits=base_bits):
                    s_tile = tile_scale_grid(xp, qmax, -(-ho_pad // 2),
                                             -(-wo_pad // 2))
                    ascale = tile_scales_upsampled(s_tile, ho_pad, wo_pad)
                else:
                    ascale = _patch_scales(xp, kh, kw, stride,
                                           qmax)[:, :ho_pad, :wo_pad]
        pk = (-cin) % bk
        if pk:  # zero channels contribute exact zeros to every partial
            xp = jnp.pad(xp, ((0, 0), (0, 0), (0, 0), (0, pk)))
            w_vals = jnp.pad(w_vals, ((0, 0), (0, 0), (0, pk), (0, 0)))
        bc = min(bc, cout)
        pc = (-cout) % bc
        if pc:
            w_vals = jnp.pad(w_vals, ((0, 0), (0, 0), (0, 0), (0, pc)))
            if w_scale is not None:
                w_scale = jnp.pad(w_scale, ((0, pc),))
        if integer:
            wsc = w_scale.reshape(1, -1)
        raw = conv2d_implicit_raw(
            xp, w_vals, stride=stride, out_h=ho_pad, block=(bm, bc, bk),
            variant=variant, base_bits=base_bits, qmax=qmax,
            ascale=ascale, wscale=wsc, fold_every=fold_every,
            true_cin=cin, cell_scale=cs, pool=kernel_pool, out_rows=ho,
            pipeline=k_pipeline, interpret=interpret,
        )
        if kernel_pool is not None:
            pw_, ps_ = kernel_pool
            hp = (ho - pw_) // ps_ + 1
            wp = (wo - pw_) // ps_ + 1
            return raw[:, :hp, :wp, :cout]
        out = raw[:, :ho, :wo, :cout]
    if pool is not None:
        # Mirror / fallback pooling, same jit scope (same HBM boundary as
        # the kernel epilogue): max over identical f32 values is exact
        # selection, bitwise however it is evaluated.
        pw_, ps_, ppad = pool
        out = lax.reduce_window(out, -jnp.inf, lax.max,
                                (1, pw_, pw_, 1), (1, ps_, ps_, 1),
                                padding=ppad)
    return out


def conv2d_implicit(
    x,
    w,
    *,
    stride: int = 1,
    padding: str = "SAME",
    variant: str = "native",
    base_bits: int = 7,
    bias: jax.Array | None = None,
    activation: str | None = None,
    block: tuple[int, int, int] | None = None,
    fold_every: int | None = None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
    pool: tuple | None = None,
    quantize_next: int | None = None,
    k_pipeline: bool = True,
):
    """NHWC conv as an implicit GEMM: the patch matrix never exists in HBM.

    ``variant``: "native" (f32 dots), "bf16x3"/"bf16x6" (multi-pass bf16
    emulation) or "karatsuba"/"schoolbook" (the KOM limb substrate,
    ``base_bits`` digits).  Integer variants quantize activations per PATCH
    (one scale per output position -- the materialized path's per-row
    granularity) in VMEM and the weight per OUTPUT CHANNEL (cached
    :class:`QWeight` or on-the-fly ``quantize_weight``, bitwise-identical
    forms); the dequant scale rides the core's epilogue, ``bias``/
    ``activation`` apply here, OUTSIDE the core's jit scope, so the dequant
    multiply's rounding is pinned by the core's output materialization --
    bitwise fused==unfused (DESIGN.md sections 7.3/7.4).

    Any kernel size, stride, Cin and Cout are supported; layers whose
    whole-K int32 accumulation would wrap (``int_accum_bound >= 2^31``) run
    the per-K-block recombine schedule instead of being rerouted -- this
    path has no depth limit.  ``block=(bm, bc, bk)`` overrides the
    autotuned tile schedule; ``fold_every`` overrides the recombine
    schedule (tests only).

    On TPU the core is the Pallas kernel
    (:func:`~repro.kernels.conv2d.implicit_gemm.conv2d_implicit_raw`);
    off-TPU (or ``use_pallas=False``) the SAME dataflow runs as a streamed
    lax program with identical group boundaries -- bitwise equal for the
    integer variants, so CPU CI/serving exercise the real schedule at XLA
    speed instead of interpret-mode Pallas.

    Fused dataflow (DESIGN.md section 7.7): ``pool=(pw, ps[, ppad])``
    folds the FOLLOWING maxpool into the epilogue (pool inside the core,
    bias/relu on the pooled tensor here -- bitwise equal to pooling after
    bias/relu because max is exact selection and relu monotone);
    ``quantize_next=b`` then hands the result to the next 3x3/s1/SAME int
    layer as a :class:`QActivation` via the shared :func:`handoff_quantize`.
    A QActivation ``x`` is the consumer side: pre-quantized pixels + cell
    scales, contracted with per-(K-chunk, tap) recombine-and-scale.
    ``k_pipeline`` toggles the kernel's double-buffered K-step DMA
    pipelining (planner-visible; no-op off-TPU).
    """
    v = "karatsuba" if variant == "kom" else variant
    handoff_in = isinstance(x, QActivation)
    if handoff_in:
        if v not in INT_VARIANTS:
            raise ValueError(
                "QActivation input requires an integer limb variant")
        if not isinstance(w, QWeight):
            raise ValueError(
                "QActivation input requires a cached QWeight (the handoff "
                "is a serving-path contract)")
        if (w.shape[0], w.shape[1], stride, padding) != (3, 3, 1, "SAME"):
            raise ValueError(
                "QActivation was quantized for a 3x3/s1/SAME consumer; got "
                f"k={w.shape[0]}x{w.shape[1]} s{stride} {padding}")
        if x.base_bits != w.base_bits:
            raise ValueError(
                f"handoff base_bits {x.base_bits} != weight base_bits "
                f"{w.base_bits}: producer and consumer must share a policy")
    if v in INT_VARIANTS and not isinstance(w, QWeight):
        # Quantize float weights HERE, outside the jitted core, so an
        # on-the-fly call is bitwise identical to the cached-QWeight call
        # (inside the jit, XLA rewrites the /qmax division to a reciprocal
        # multiply and the scales drift an ulp from quantize_weight's).
        w = quantize_weight(w, base_bits=base_bits)
    pool_t = None
    if pool is not None:
        pool_t = (int(pool[0]), int(pool[1]),
                  pool[2] if len(pool) > 2 else "VALID")
    out = _conv2d_implicit_core(
        x, w, stride=stride, padding=padding, variant=variant,
        base_bits=base_bits, block=block, fold_every=fold_every,
        use_pallas=use_pallas, interpret=interpret, pool=pool_t,
        k_pipeline=k_pipeline)
    if bias is not None:
        out = out + bias
    if activation == "relu":
        out = jax.nn.relu(out)
    elif activation is not None:
        raise ValueError(f"unknown activation: {activation!r}")
    if quantize_next is not None:
        out = handoff_quantize(out, base_bits=int(quantize_next))
    return out


# ---------------------------------------------------------------------------
# Winograd F(2x2, 3x3): integer transforms over the limb substrate.
# ---------------------------------------------------------------------------

#: Per-QWeight memo of the mirror's pre-transformed, pre-sliced weight
#: operands, keyed on the weight array's identity (pinned by the stored
#: strong reference).  Bounded FIFO: on-the-fly float-weight calls create a
#: fresh QWeight per call and must not grow this without limit.
_MIRROR_OPS_CACHE: dict = {}
_MIRROR_OPS_CAP = 16


def _winograd_mirror_ops_cached(w: QWeight):
    """winograd_mirror_operands(G2-transformed w), memoized per QWeight.

    Serving and the bench harness pass the SAME cached QWeight every call
    with the weight as a jit argument, where XLA cannot constant-fold the
    weight transform + group/chunk copies (~30 ms/call at Cin=512 on CPU,
    more than the pointwise dots themselves).  Under an outer jit the
    values are tracers -- no identity to memo on -- so the transform stays
    in-graph and the result is unchanged either way (exact integer ops).
    """
    if isinstance(w.values, jax.core.Tracer):
        return None
    key = (id(w.values), int(w.base_bits))
    hit = _MIRROR_OPS_CACHE.get(key)
    if hit is not None and hit[0] is w.values:
        return hit[1]
    uh, ul = winograd_weight_planes(w.values, w.base_bits)
    ops = winograd_mirror_operands(uh, ul, base_bits=w.base_bits)
    while len(_MIRROR_OPS_CACHE) >= _MIRROR_OPS_CAP:
        _MIRROR_OPS_CACHE.pop(next(iter(_MIRROR_OPS_CACHE)))
    _MIRROR_OPS_CACHE[key] = (w.values, ops)
    return ops


@functools.partial(
    jax.jit,
    static_argnames=("padding", "variant", "base_bits", "block",
                     "use_pallas", "interpret"),
)
def _conv2d_winograd_core(
    x: jax.Array,
    w: QWeight,
    *,
    padding: str,
    variant: str,
    base_bits: int,
    block: tuple[int, int] | None,
    use_pallas: bool | None,
    interpret: bool | None,
    w_ops=None,
) -> jax.Array:
    """The jitted body of :func:`conv2d_winograd`, WITHOUT the epilogue.

    Same load-bearing jit boundary as the implicit core: fl(raw * scale) is
    materialized before the caller's bias add, pinning the dequant
    multiply's rounding (bitwise fused==unfused).
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = _default_interpret()
    n, h, wdim, cin = x.shape
    kh, kw, _, cout = w.shape
    qmax = kom_qmax(base_bits)
    w_vals = w.values
    w_scale = jnp.broadcast_to(
        jnp.asarray(w.scale, jnp.float32).reshape(-1), (cout,))
    # The engine computes exactly 4x the convolution (two G2 = 2G factors);
    # the 1/4 folds into the per-channel dequant scale -- an exact f32
    # exponent shift, so outputs match the direct paths bitwise.
    wscale4 = w_scale * jnp.float32(1.0 / WINOGRAD_OUTPUT_SCALE)
    ho, wo, pads = conv_pads(h, wdim, kh, kw, 1, padding)
    th, tw = -(-ho // 2), -(-wo // 2)
    x = x.astype(jnp.float32)

    if not use_pallas:
        xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
        s_tile = tile_scale_grid(xp, qmax, th, tw)
        # The mirror gathers the full (2*th+2, 2*tw+2) tile footprint;
        # extra zero rows/cols beyond the layer's own pads contribute
        # nothing (zero pixels quantize to zero).
        eh = max(2 * th + 2 - xp.shape[1], 0)
        ew = max(2 * tw + 2 - xp.shape[2], 0)
        if eh or ew:
            xp = jnp.pad(xp, ((0, 0), (0, eh), (0, ew), (0, 0)))
        raw4 = stream_conv_winograd(
            xp, w_vals, s_tile, th=th, tw=tw, variant=variant,
            base_bits=base_bits, qmax=qmax, w_ops=w_ops)
        # Same dequant expression as the kernel epilogue: t = s * wscale4,
        # then raw4 * t.
        t = tile_scales_upsampled(s_tile, 2 * th, 2 * tw)[..., None] * wscale4
        out = (raw4 * t)[:, :ho, :wo, :]
    else:
        if block is None:
            bt, bc = _resolve_block(
                "winograd", kh=kh, kw=kw, stride=1, h=h, cin=cin, cout=cout,
                variant=variant, base_bits=base_bits)
        else:
            bt, bc = block
        th_pad = -(-th // bt) * bt
        # Tile columns to whole sublane tiles (the kernel flattens each
        # point to (bt*tw, cin)); the extra tiles read zeros and are
        # sliced off.
        tw_pad = -(-tw // _WO_ALIGN) * _WO_ALIGN
        # One spare halo row block plus the full tile-column footprint.
        rows_needed = (th_pad // bt + 1) * 2 * bt
        cols_needed = 2 * tw_pad + 2
        h_padded = h + pads[0][0] + pads[0][1]
        w_padded = wdim + pads[1][0] + pads[1][1]
        pads = ((pads[0][0], pads[0][1] + max(rows_needed - h_padded, 0)),
                (pads[1][0], pads[1][1] + max(cols_needed - w_padded, 0)))
        xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
        s_tile = tile_scale_grid(xp, qmax, th_pad, tw_pad)
        uh, ul = winograd_weight_planes(w_vals, base_bits)
        # Whole 128-lane chunks past one lane tile (the kernel stages its
        # input in such chunks); zero channels add exact zeros.
        pk = (-cin) % LANES if cin > LANES else 0
        if pk:
            xp = jnp.pad(xp, ((0, 0), (0, 0), (0, 0), (0, pk)))
            uh = jnp.pad(uh, ((0, 0), (0, 0), (0, pk), (0, 0)))
            ul = jnp.pad(ul, ((0, 0), (0, 0), (0, pk), (0, 0)))
        bc = min(bc, cout)
        pc = (-cout) % bc
        wsc = wscale4
        if pc:
            uh = jnp.pad(uh, ((0, 0), (0, 0), (0, 0), (0, pc)))
            ul = jnp.pad(ul, ((0, 0), (0, 0), (0, 0), (0, pc)))
            wsc = jnp.pad(wsc, ((0, pc),))
        out = conv2d_winograd_raw(
            xp, uh, ul, th=th_pad, tw=tw_pad, block=(bt, bc),
            variant=variant, base_bits=base_bits, qmax=qmax,
            ascale=s_tile, wscale=wsc.reshape(1, -1), interpret=interpret,
        )[:, :ho, :wo, :cout]
    return out


def conv2d_winograd(
    x: jax.Array,
    w,
    *,
    stride: int = 1,
    padding: str = "SAME",
    variant: str = "karatsuba",
    base_bits: int = 7,
    bias: jax.Array | None = None,
    activation: str | None = None,
    block: tuple[int, int] | None = None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """NHWC conv through integer Winograd F(2x2, 3x3), epilogue fused.

    Integer limb variants ONLY (the transforms live in the quantized-limb
    domain; float policies have no limbs to transform and raise).  ``w``
    may be a float HWIO weight -- quantized here, outside the jitted core,
    with the cached-QWeight granularity -- or a :class:`QWeight`.

    Exact-or-reroute: non-3x3 kernels, strides != 1 and layers past
    :func:`winograd_accum_bound` reroute to :func:`conv2d_implicit` (which
    shares the tile-granular activation scales on eligible shapes), so a
    whole-network ``conv_path="winograd"`` configuration stays exact on
    every layer.  Eligible layers are BITWISE equal to the implicit and
    materialized im2col paths (DESIGN.md section 7.5).

    ``block=(bt, bc)``: tile-row-block / Cout tile sizes, defaulting to the
    autotuner's schedule.  Off-TPU (or ``use_pallas=False``) the dataflow
    runs as the bitwise streamed lax mirror instead of interpret-mode
    Pallas.
    """
    v = "karatsuba" if variant == "kom" else variant
    if v not in INT_VARIANTS:
        raise ValueError(
            f"conv2d_winograd cannot run variant {variant!r}: the Winograd "
            "transforms live in the quantized-limb integer domain -- float "
            "policies have no limb planes to transform; use the implicit or "
            "im2col path")
    kh, kw, cin = w.shape[0], w.shape[1], w.shape[2]
    if isinstance(w, QWeight):
        base_bits = w.base_bits
    else:
        w = quantize_weight(w, base_bits=base_bits)
    if (kh, kw) != (3, 3) or stride != 1 or winograd_accum_bound(
            cin, variant=v, base_bits=base_bits) >= 2**31:
        # Exact-or-reroute: shapes the F(2x2, 3x3) engine cannot serve
        # exactly stream through the implicit GEMM instead (wrap-free at
        # any depth, any kernel/stride).
        return conv2d_implicit(x, w, stride=stride, padding=padding,
                               variant=v, base_bits=base_bits,
                               bias=bias, activation=activation,
                               use_pallas=use_pallas, interpret=interpret)
    mirror = not (use_pallas if use_pallas is not None
                  else jax.default_backend() == "tpu")
    w_ops = _winograd_mirror_ops_cached(w) if mirror else None
    out = _conv2d_winograd_core(
        x, w, padding=padding, variant=v, base_bits=base_bits,
        block=block, use_pallas=use_pallas, interpret=interpret,
        w_ops=w_ops)
    if bias is not None:
        out = out + bias
    if activation == "relu":
        out = jax.nn.relu(out)
    elif activation is not None:
        raise ValueError(f"unknown activation: {activation!r}")
    return out
