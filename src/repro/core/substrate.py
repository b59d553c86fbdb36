"""The KOM multiplier substrate: one limb core for every consumer.

The paper's contribution is a *single* multiplier primitive -- the 3-pass
Karatsuba-Ofman decomposition -- reused uniformly across every conv/FC layer
of AlexNet/VGG16/VGG19.  This module is that primitive's one home on TPU:

  * **Limb splitting** (:func:`balanced_split`, :func:`split_limbs`): the
    balanced base-2^b digit trick, defined exactly once in the repo.  The
    Pallas GEMM and conv kernels, ``kom_dot_general`` and the quantized
    linear paths all import it from here (DESIGN.md section 2.1).
  * **Pass scheduling** (:func:`limb_partials` / :func:`limb_recombine` /
    :func:`limb_dot_general`): the 3-pass Karatsuba and 4-pass schoolbook
    schedules over any ``dot_general`` dimension numbers, usable both as a
    plain jnp function and inside a Pallas kernel body (partial products can
    be accumulated in VMEM scratch and recombined once at the last K step).
  * **Quantization state** (:class:`QTensor`, :class:`QWeight`,
    :func:`quantize_symmetric`, :func:`quantize_weight`): dynamic per-tensor
    activation scales, and *cached* per-output-channel weight scales produced
    once at model build time (DESIGN.md section 7.2).
  * **Conv dispatch** (:func:`select_conv_path`, :func:`conv2d`): one entry
    point that picks the im2col-GEMM or Pallas systolic path from the layer
    shape -- kernel size, stride, Cout lane alignment -- instead of a
    per-call-site boolean (DESIGN.md section 7.1).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Literal, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

Variant = Literal["karatsuba", "schoolbook"]

#: MXU passes per wide multiply, the TPU analogue of the paper's LUT counts.
PASS_COUNTS = {"karatsuba": 3, "schoolbook": 4}

# Standard 2D matmul dimension numbers: (m,k) x (k,n) -> (m,n).
MATMUL_DNUMS = (((1,), (0,)), ((), ()))

#: Integer MatmulPolicy values -> (limb variant, base_bits).  Keyed by the
#: enum's string value so this module never imports ``precision`` (which
#: imports us).
INT_POLICY_SPECS = {
    "kom_int14": ("karatsuba", 7),
    "schoolbook_int16": ("schoolbook", 8),
}


def policy_int_spec(policy) -> Optional[tuple[str, int]]:
    """(variant, base_bits) for integer-KOM policies, None for float ones."""
    return INT_POLICY_SPECS.get(getattr(policy, "value", policy))


def systolic_exact(policy) -> bool:
    """True iff the systolic conv engine implements ``policy`` exactly.

    That is the integer limb policies plus fp32 (native f32 dots).  The one
    definition shared by :func:`conv2d`'s dispatch/refusal and the serve
    launcher's arg-parse-time guard -- the policy set must not fork.
    """
    return (policy_int_spec(policy) is not None
            or getattr(policy, "value", policy) == "fp32")


#: Policies the implicit-GEMM conv engine implements exactly: the limb
#: policies run on the shared substrate (per-PATCH activation scales), fp32
#: runs native f32 dots, bf16x3/bf16x6 run their multi-pass emulation
#: schedules per tap.  Only native_bf16 (whose bf16 accumulation is an
#: XLA-convolution-level choice) stays on the materialized im2col path.
IMPLICIT_POLICIES = frozenset(
    {"kom_int14", "schoolbook_int16", "fp32", "bf16x3", "bf16x6"})


def implicit_supported(policy) -> bool:
    """True iff the implicit-GEMM conv engine implements ``policy`` exactly."""
    return getattr(policy, "value", policy) in IMPLICIT_POLICIES


def path_supports_policy(path: str, policy) -> bool:
    """True iff conv engine ``path`` runs ``policy`` exactly (no downgrade).

    THE path x policy capability table -- :func:`validate_path_policy`
    (and through it ``conv2d``'s explicit-path refusals, the serve
    launcher's arg-parse-time guards, and the planner's candidate pruning
    and artifact checks) all consult this one definition.
    """
    if path in ("auto", "im2col"):
        return True
    if path == "systolic":
        return systolic_exact(policy)
    if path == "implicit":
        return implicit_supported(policy)
    if path == "winograd":
        return policy_int_spec(policy) is not None
    raise ValueError(f"unknown conv path: {path!r}")


#: Epilogue fusion levels a plan entry may record (DESIGN.md section 7.7).
#: "bias_relu" is the PR-3 default (dequant+bias+relu in one write);
#: "none" models the unfused three-round-trip epilogue; "pool" folds the
#: following 2x2/s2 (or 3x3/s2) maxpool into the conv's epilogue before the
#: HBM writeback; "pool_quant" additionally quantizes the pooled tile with
#: the NEXT layer's tile-granular scale grid, handing the downstream conv a
#: :class:`QActivation` (int16 values + scale grid) instead of f32.
FUSIONS = ("none", "bias_relu", "pool", "pool_quant")


def path_supports_fusion(path: str, fusion: str) -> bool:
    """True iff conv engine ``path`` implements epilogue level ``fusion``.

    THE path x fusion capability table, the fusion analogue of
    :func:`path_supports_policy` -- ``conv2d``'s kwarg guards, the
    planner's candidate axis and ``planner --check``'s artifact
    validation all consult this one definition.  Every engine fuses
    dequant+bias+relu ("bias_relu", and trivially "none"); only the
    implicit-GEMM engine pools (and hands off quantized activations) in
    its epilogue -- its dual row-block halo binding is what resolves pool
    windows straddling row-block seams (DESIGN.md section 7.7).
    """
    if fusion not in FUSIONS:
        raise ValueError(f"unknown fusion: {fusion!r}")
    if path in ("auto", "im2col", "systolic", "winograd"):
        return fusion in ("none", "bias_relu")
    if path == "implicit":
        return True
    raise ValueError(f"unknown conv path: {path!r}")


def validate_path_policy(path: str, policy) -> None:
    """Raise ValueError when an EXPLICIT ``path`` cannot run ``policy`` exactly.

    One shared refusal for ``conv2d``, ``launch/serve.py`` (which used to
    copy-paste this guard once per engine) and the planner: an explicit
    engine choice must never silently downgrade a policy to native dots --
    use ``path='auto'`` or ``path='im2col'`` (which honors every policy).
    """
    if path_supports_policy(path, policy):
        return
    pv = getattr(policy, "value", policy)
    implements = {
        "systolic": "the integer limb policies and fp32 only",
        "implicit": "the integer limb policies, fp32 and the bf16x3/bf16x6 "
                    "emulation schedules only",
        "winograd": "the integer limb policies only (the transforms live "
                    "in the quantized-limb domain)",
    }[path]
    raise ValueError(
        f"path={path!r} cannot run policy {pv!r} exactly: the {path} "
        f"engine implements {implements}, and an explicit path must not "
        "silently downgrade to native dots -- use path='auto' or "
        "path='im2col'")


# ---------------------------------------------------------------------------
# Limb decomposition: the one implementation of the balanced digit split.
# ---------------------------------------------------------------------------

def kom_qmax(base_bits: int = 7) -> int:
    """Largest |x| whose balanced (hi, lo) digits both fit [-2^(b-1), 2^(b-1)-1].

    kom_qmax(7) = 63*129 = 8127 ('int14', Karatsuba-safe: digit sums fit s8);
    kom_qmax(8) = 127*257 = 32639 ('int16', schoolbook only).
    """
    half = 1 << (base_bits - 1)
    return (half - 1) * ((1 << base_bits) + 1)


def balanced_split(x: jax.Array, base_bits: int) -> tuple[jax.Array, jax.Array]:
    """Split int values into balanced base-2^b digits: x == hi*2^b + lo.

    Both digits lie in [-2^(b-1), 2^(b-1)-1] provided |x| <= kom_qmax(b);
    balanced (signed) digits are what keep the Karatsuba digit sums inside
    the s8 range with a single guard bit (DESIGN.md section 2.1).
    """
    beta = 1 << base_bits
    half = beta >> 1
    x = x.astype(jnp.int32)
    lo = ((x + half) & (beta - 1)) - half
    hi = (x - lo) >> base_bits
    return hi, lo


def split_limbs(
    x: jax.Array, base_bits: int, narrow_dtype=jnp.int8
) -> tuple[jax.Array, jax.Array]:
    """Balanced digits already narrowed to the MXU pass dtype."""
    hi, lo = balanced_split(x, base_bits)
    return hi.astype(narrow_dtype), lo.astype(narrow_dtype)


def limb_partials_presplit(
    ah: jax.Array,
    al: jax.Array,
    bh: jax.Array,
    bl: jax.Array,
    dimension_numbers=MATMUL_DNUMS,
    *,
    variant: Variant = "karatsuba",
    narrow_dtype=jnp.int8,
    accum_dtype=jnp.int32,
    wide: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The narrow MXU passes over ALREADY-SPLIT limb planes.

    The Winograd engine transforms each limb plane separately (the B/G
    transforms are linear, so transform-after-split is exact) and the
    transformed planes are no longer balanced digits of anything -- they
    must be contracted as-is.  This is the pass schedule shared with
    :func:`limb_partials`, minus the split.  ``narrow_dtype`` must hold the
    digit sums (int8 for fresh balanced digits under the guard bit); planes
    that outgrow int8 pass ``wide=True``, which runs every pass as a
    :func:`wide_int_dot` (three int8 passes, int32 result).
    """
    if variant not in PASS_COUNTS:
        raise ValueError(f"unknown variant: {variant}")
    if wide:
        dot = functools.partial(wide_int_dot,
                                dimension_numbers=dimension_numbers)
        nd = lambda t: t
    else:
        dot = functools.partial(
            lax.dot_general,
            dimension_numbers=dimension_numbers,
            preferred_element_type=accum_dtype,
        )
        nd = lambda t: t.astype(narrow_dtype)
    p_hh = dot(nd(ah), nd(bh))
    p_ll = dot(nd(al), nd(bl))
    if variant == "karatsuba":
        # Third and final multiply; digit sums fit the narrow dtype.
        p_mid = dot(nd(ah + al), nd(bh + bl)) - p_hh - p_ll
    else:
        p_mid = dot(nd(ah), nd(bl)) + dot(nd(al), nd(bh))
    return p_hh, p_mid, p_ll


def limb_partials(
    a: jax.Array,
    b: jax.Array,
    dimension_numbers=MATMUL_DNUMS,
    *,
    variant: Variant = "karatsuba",
    base_bits: int = 7,
    narrow_dtype=jnp.int8,
    accum_dtype=jnp.int32,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The narrow MXU passes of one wide multiply: (p_hh, p_mid, p_ll).

    Karatsuba: 3 dots -- p_mid = (Ah+Al)(Bh+Bl) - p_hh - p_ll, the digit sums
    fitting the narrow dtype thanks to the guard bit.  Schoolbook: 4 dots.
    Returned un-recombined so Pallas kernels can accumulate each partial in
    its own scratch register across K blocks (the analogue of the FPGA
    design's partial-product registers) and recombine once at the end.
    """
    if variant not in PASS_COUNTS:
        raise ValueError(f"unknown variant: {variant}")
    if variant == "karatsuba" and base_bits > 7 and narrow_dtype == jnp.int8:
        raise ValueError(
            "karatsuba digit sums need a guard bit: base_bits <= 7 for int8 passes"
        )
    ah, al = balanced_split(a, base_bits)
    bh, bl = balanced_split(b, base_bits)
    return limb_partials_presplit(
        ah, al, bh, bl, dimension_numbers,
        variant=variant, narrow_dtype=narrow_dtype, accum_dtype=accum_dtype,
    )


def limb_recombine(
    p_hh: jax.Array,
    p_mid: jax.Array,
    p_ll: jax.Array,
    *,
    base_bits: int,
    dtype=jnp.float32,
) -> jax.Array:
    """p_hh*beta^2 + p_mid*beta + p_ll in ``dtype`` (int64 for bit-exact)."""
    beta = 1 << base_bits
    return (
        p_hh.astype(dtype) * (beta * beta)
        + p_mid.astype(dtype) * beta
        + p_ll.astype(dtype)
    )


def wide_int_dot(a: jax.Array, b: jax.Array,
                 dimension_numbers=MATMUL_DNUMS) -> jax.Array:
    """Exact int32 dot_general of integer operands too wide for int8.

    One Karatsuba level on balanced base-2^7 digits (three int8 passes,
    |a|, |b| <= kom_qmax(7)) recombined in int32 -- exact whenever the true
    result fits int32 (int32 adds wrap mod 2^32 in between).  The TPU's MXU
    has int8 passes but no int16 one; this is how the Winograd engine's
    transformed planes (|.| <= 18h) reach it.
    """
    p_hh, p_mid, p_ll = limb_partials(a, b, dimension_numbers,
                                      variant="karatsuba", base_bits=7)
    return p_hh * (1 << 14) + p_mid * (1 << 7) + p_ll


def limb_dot_general(
    a: jax.Array,
    b: jax.Array,
    dimension_numbers=MATMUL_DNUMS,
    *,
    variant: Variant = "karatsuba",
    base_bits: int = 7,
    narrow_dtype=jnp.int8,
    accum_dtype=jnp.int32,
    recombine_dtype=jnp.float32,
) -> jax.Array:
    """Wide integer dot_general out of narrow MXU passes (split + recombine)."""
    p_hh, p_mid, p_ll = limb_partials(
        a, b, dimension_numbers,
        variant=variant, base_bits=base_bits,
        narrow_dtype=narrow_dtype, accum_dtype=accum_dtype,
    )
    return limb_recombine(p_hh, p_mid, p_ll, base_bits=base_bits,
                          dtype=recombine_dtype)


# ---------------------------------------------------------------------------
# Pass-count resource model (paper Tables 1-4 restated for the MXU).
# ---------------------------------------------------------------------------

def pass_count(variant_or_passes) -> int:
    """Resource model: narrow MXU passes per wide multiply (paper Tables 1-4)."""
    if isinstance(variant_or_passes, int):
        return variant_or_passes
    return PASS_COUNTS[variant_or_passes]


def recursion_pass_count(depth: int, variant: Variant = "karatsuba") -> int:
    """Passes if the paper's recursion ('until 2 bits') were followed.

    One level: 3 passes of b/2-bit work.  Two levels: 9 passes of b/4-bit
    work, etc.  On the MXU every pass costs a full matrix issue regardless of
    operand width below 8 bits -- which is why we stop at one level
    (DESIGN.md section 8.3).
    """
    per_level = PASS_COUNTS[variant]
    return per_level**depth


# ---------------------------------------------------------------------------
# Quantization state.
# ---------------------------------------------------------------------------

class QTensor(NamedTuple):
    """Integer values + the float scale that dequantizes them (dynamic)."""

    values: jax.Array  # int32 container holding |v| <= qmax
    scale: jax.Array   # f32; scalar (per-tensor) or broadcastable (per-axis)
    qmax: int

    @property
    def shape(self):
        return self.values.shape


def quantize_symmetric(
    x: jax.Array,
    *,
    qmax: int | None = None,
    base_bits: int = 7,
    axis: Optional[int | tuple[int, ...]] = None,
) -> QTensor:
    """Symmetric (zero-point-free) quantization.

    ``axis``: None -> per-tensor scale; an int or tuple of ints -> per-slice
    scales along those KEPT axes (e.g. per-output-feature for weights, all
    leading axes for per-row activation quant), kept broadcastable.
    """
    if qmax is None:
        qmax = kom_qmax(base_bits)
    x = x.astype(jnp.float32)
    if axis is None:
        amax = jnp.max(jnp.abs(x))
    else:
        keep = (axis,) if isinstance(axis, int) else tuple(axis)
        reduce_axes = tuple(i for i in range(x.ndim) if i not in keep)
        amax = jnp.max(jnp.abs(x), axis=reduce_axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int32)
    return QTensor(values=q, scale=scale, qmax=qmax)


def dequantize(q: QTensor) -> jax.Array:
    return q.values.astype(jnp.float32) * q.scale


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["values", "scale"],
    meta_fields=["base_bits"],
)
@dataclasses.dataclass(frozen=True)
class QWeight:
    """A weight quantized ONCE at model build: int16 values + cached scales.

    ``values`` holds balanced-digit-safe integers (|v| <= kom_qmax(base_bits))
    with the output-channel axis LAST; ``scale`` is the per-output-channel
    f32 scale, shape (cout,), broadcasting against any output whose trailing
    dim is cout.  Registered as a pytree with ``base_bits`` static, so a
    QWeight threads through jit/pytree params unchanged and the forward pass
    never re-quantizes the weight (DESIGN.md section 7.2).
    """

    values: jax.Array
    scale: jax.Array
    base_bits: int = 7

    @property
    def shape(self):
        return self.values.shape

    @property
    def ndim(self):
        return self.values.ndim

    def astype(self, dtype):
        # Compute dtype is decided at dequant/recombine time; casting a cached
        # integer weight is a no-op so generic `w.astype(...)` call sites work.
        return self


def quantize_weight(
    w: jax.Array, *, base_bits: int = 7, stack_axes: int = 0
) -> QWeight:
    """Per-output-channel (last axis) symmetric quantization, done once.

    Works for FC weights (k, n) and conv HWIO weights (kh, kw, cin, cout):
    the output-channel axis is the last one in both layouts; the scale comes
    out flat, shape (cout,).

    ``stack_axes``: leading axes that are layer/expert stacks rather than
    contraction dims (e.g. scan-stacked transformer weights (L, k, n) use
    ``stack_axes=1``).  Scales then keep those axes -- shape (L, 1, n) --
    so a stacked QWeight slices correctly under ``lax.scan``.
    """
    qmax = kom_qmax(base_bits)
    w = w.astype(jnp.float32)
    reduce_axes = tuple(range(stack_axes, w.ndim - 1))
    if stack_axes == 0:
        amax = jnp.max(jnp.abs(w), axis=reduce_axes)
    else:
        amax = jnp.max(jnp.abs(w), axis=reduce_axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / qmax
    q = jnp.clip(jnp.round(w / scale), -qmax, qmax).astype(jnp.int16)
    return QWeight(values=q, scale=scale, base_bits=base_bits)


def dequantize_weight(w: QWeight) -> jax.Array:
    return w.values.astype(jnp.float32) * w.scale


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["values", "scale"],
    meta_fields=["base_bits", "h", "w"],
)
@dataclasses.dataclass(frozen=True)
class QActivation:
    """A pre-quantized activation handed between fused conv layers.

    Produced by the ``pool_quant`` epilogue fusion (DESIGN.md section 7.7):
    the conv that FEEDS a ``3x3/s1/SAME`` int layer quantizes its pooled
    output once per pixel with the consumer's tile-granular scale plan
    (DESIGN.md section 7.5), so the consumer reads int16 + a small scale
    grid from HBM instead of f32.

    ``values`` is the consumer's PADDED input, already SAME-padded for the
    3x3/s1 conv, quantized per pixel: shape (n, h+2, w+2, c) int16, where
    pixel (py, px) used the 4x4/s2 cell scale
    ``scale[n, min(py//2, th-1), min(px//2, tw-1)]`` (every pixel sits
    inside its cell's 4x4 amax window, so |q| <= kom_qmax(base_bits)).
    ``scale`` is that (n, th, tw) f32 grid with th=ceil(h/2), tw=ceil(w/2).
    ``h``/``w`` are the true UNPADDED spatial dims (static, like
    ``base_bits``), so plan lookups and shape checks see the logical
    activation.  Padding rows/cols quantize to exactly 0 (round(0/s) == 0),
    which is why storing the padded tensor is bitwise-equivalent to
    re-padding an unpadded one with integer zeros.
    """

    values: jax.Array
    scale: jax.Array
    base_bits: int = 7
    h: int = 0
    w: int = 0

    @property
    def shape(self):
        n = self.values.shape[0]
        return (n, self.h, self.w, self.values.shape[3])

    @property
    def ndim(self):
        return 4

    @property
    def dtype(self):
        return self.values.dtype


@jax.custom_vjp
def _inference_only(x):
    """Identity whose backward pass raises: quantized round/clip would
    otherwise yield silent zero gradients for the whole upstream network."""
    return x


def _inference_only_fwd(x):
    return x, None


def _inference_only_bwd(_, g):
    raise NotImplementedError(
        "prequant_dot_general (cached QWeight path) is inference-only; "
        "train on the float params with the straight-through policy path "
        "and quantize at deployment"
    )


_inference_only.defvjp(_inference_only_fwd, _inference_only_bwd)


def prequant_dot_general(
    x: jax.Array,
    w: QWeight,
    dimension_numbers=MATMUL_DNUMS,
    *,
    variant: Variant = "karatsuba",
    row_scale: jax.Array | None = None,
) -> jax.Array:
    """Dynamic per-row activation quant x cached per-channel weight.

    The serving hot path: the weight's limbs come from int16 storage (no
    per-forward requantization); only the activation is quantized on the fly.
    For ANY last-dim contraction -- (m, k), (b, t, k), deeper stacks -- each
    activation ROW (all leading axes) gets its own scale (a row is one token
    / one im2col patch), so a request's logits are bit-identical whatever
    batch-mates or padding rows it is served with, without callers having to
    pre-flatten -- the batch-invariance contract the serving engines test
    differentially (DESIGN.md section 9.3).  Only genuinely non-matmul
    dimension numbers (batched or non-trailing contractions) fall back to a
    per-tensor scale, which voids per-row invariance and is documented as
    such.

    ``row_scale``: a precomputed activation scale, broadcastable against
    ``x`` -- callers that share one scale plan across conv paths (the
    Winograd tile-granular scales, DESIGN.md section 7.5) pass it so every
    path quantizes with the SAME rounding: q = clip(round(x / row_scale)).

    INFERENCE-ONLY: unlike the quantize-on-the-fly policy path (which
    installs a straight-through VJP), this path refuses differentiation --
    training must run on the float params and quantize at deployment.
    """
    x = _inference_only(x)  # raises under jax.grad instead of silent zeros
    if row_scale is not None:
        qmax = kom_qmax(w.base_bits)
        qv = jnp.clip(jnp.round(x.astype(jnp.float32) / row_scale),
                      -qmax, qmax).astype(jnp.int32)
        qx = QTensor(values=qv, scale=row_scale, qmax=qmax)
    else:
        (lcs, _), (lb, rb) = dimension_numbers
        per_row = tuple(lcs) == (x.ndim - 1,) and not lb and not rb
        qx = quantize_symmetric(
            x, base_bits=w.base_bits,
            axis=tuple(range(x.ndim - 1)) if per_row else None)
    raw = limb_dot_general(
        qx.values, w.values.astype(jnp.int32), dimension_numbers,
        variant=variant, base_bits=w.base_bits,
    )
    return raw * (qx.scale * w.scale)


# ---------------------------------------------------------------------------
# Conv planning + dispatch.
# ---------------------------------------------------------------------------

def conv_pads(h, w, kh, kw, stride, padding):
    """SAME/VALID output sizes + explicit pads, shared by every conv path.

    Returns (out_h, out_w, ((top, bottom), (left, right))).
    """
    if padding == "SAME":
        ho = -(-h // stride)
        wo = -(-w // stride)
        pad_h = max((ho - 1) * stride + kh - h, 0)
        pad_w = max((wo - 1) * stride + kw - w, 0)
        pads = ((pad_h // 2, pad_h - pad_h // 2), (pad_w // 2, pad_w - pad_w // 2))
    elif padding == "VALID":
        ho = (h - kh) // stride + 1
        wo = (w - kw) // stride + 1
        pads = ((0, 0), (0, 0))
    else:
        raise ValueError(padding)
    return ho, wo, pads


def select_conv_path(
    *, kh: int, kw: int, stride: int, cin: int, cout: int,
    on_tpu: bool | None = None, policy=None, cached_weight: bool = False,
    padding: str = "SAME", stem_cin: int | None = None,
) -> str:
    """Shape- and policy-driven conv dispatch (DESIGN.md sections 7.1/7.4).

    Shape rules (the systolic engine's niche -- whole-Cin taps, int16
    activation streams -- is where its row-block/halo scheme is cheap and
    the channels fill the MXU):

      * kernel <= 7, stride <= 2, cin >= 16, cout % 128 == 0, on TPU, the
        policy exact on the engine, and (for integer policies) a cached
        weight -> ``systolic``;
      * everything else used to mean the materialized im2col-GEMM.

    With ``policy`` given, the implicit-GEMM engine is preferred over the
    MATERIALIZED im2col path wherever it runs the policy exactly AND its
    per-tap contraction is not starved:

      * integer policies with a cached :class:`QWeight` (the serving path)
        stream patches through ``implicit`` on every backend when
        ``cin >= 16`` -- off-TPU the engine runs its bitwise lax mirror,
        not interpret-mode Pallas.  Thin stems (``cin < 16``, e.g. the RGB
        first layer) keep the SMALL patch GEMM: their per-tap contraction
        depth starves any streaming engine (measured ~35x slower at
        11x11/cin=3) while their patch matrix is only kh*kw*cin <~ 400
        wide -- per-layer algorithm selection, exactly Shen et al.'s
        point.  Float weights under an integer policy keep the im2col
        straight-through-estimator path (it is the trainable one);
      * fp32 / bf16x3 / bf16x6 stream through ``implicit`` on TPU (off-TPU
        XLA's native patch GEMM is the right float call);
      * native_bf16 stays on im2col (not implemented by either engine).

    3x3/stride-1/SAME layers under ``winograd_accum_bound`` with a cached
    QWeight under an integer policy prefer ``winograd`` on EVERY backend:
    F(2x2, 3x3) cuts the pointwise multiplies ~2.25x exactly where the limb
    substrate already pays 3-4 passes per multiply (DESIGN.md section 7.5).

    The ``cin >= 16`` thin-stem threshold defaults to 16; the tuner-cached
    per-backend consult lives in :func:`repro.core.planner.heuristic_path`
    (the repo's ONE call site of this function), which passes ``stem_cin=``
    explicitly -- this function is a pure shape/policy rule with no IO.

    ``policy=None`` keeps the legacy shape-only rules (im2col/systolic).
    """
    if on_tpu is None:
        on_tpu = jax.default_backend() == "tpu"
    stem = 16 if stem_cin is None else stem_cin
    systolic_shape = (max(kh, kw) <= 7 and stride <= 2 and cin >= stem
                      and cout % 128 == 0)
    if policy is not None:
        pv = getattr(policy, "value", policy)
        is_int = pv in INT_POLICY_SPECS
        if is_int and cached_weight and kh == 3 and kw == 3 and stride == 1 \
                and padding == "SAME" and cin >= stem:
            from repro.kernels.conv2d.winograd import winograd_accum_bound
            variant, base_bits = INT_POLICY_SPECS[pv]
            if winograd_accum_bound(cin, variant=variant,
                                    base_bits=base_bits) < 2**31:
                return "winograd"
        # The systolic engine keeps its TPU niche -- but an integer policy
        # with FLOAT weights is the trainable configuration, and both Pallas
        # engines quantize weights with a plain round/clip (no straight-
        # through estimator): only the im2col STE path carries gradients.
        if (on_tpu and systolic_shape and systolic_exact(policy)
                and (cached_weight or not is_int)):
            return "systolic"
        if is_int:
            return "implicit" if (cached_weight and cin >= stem) else "im2col"
        if implicit_supported(policy) and on_tpu and cin >= stem:
            return "implicit"
        return "im2col"
    if not on_tpu:
        return "im2col"
    return "systolic" if systolic_shape else "im2col"


def conv2d(
    x: jax.Array,
    w,
    *,
    stride: int = 1,
    padding: str = "SAME",
    policy="native_bf16",
    path: str = "auto",
    block: tuple | None = None,
    bias: jax.Array | None = None,
    activation: Optional[str] = None,
    interpret: bool | None = None,
    pool: tuple | None = None,
    quantize_next: int | None = None,
    k_pipeline: bool = True,
):
    """NHWC conv behind one policy-driven entry point, epilogue fused.

    ``w`` is an HWIO float array or a cached :class:`QWeight`.  ``path`` is
    ``"auto"`` (resolved through the planner's fallback scorer,
    :func:`repro.core.planner.heuristic_path` -- model forwards resolve a
    whole-network :class:`~repro.core.planner.ExecutionPlan` ONCE at build
    and pass each layer's planned path/block here instead), ``"im2col"``,
    ``"systolic"``, ``"implicit"`` or ``"winograd"``.  ``block`` is the
    chosen engine's tile schedule (``(bh, bc)`` systolic, ``(bm, bc, bk)``
    implicit, ``(bt, bc)`` winograd; ignored by im2col, which has no tile
    knob) -- ``None`` keeps the per-layer tuner-cache resolution inside the
    ops wrappers.  ``bias`` (cout,) and ``activation`` ("relu") are fused
    into the conv epilogue on every path -- together with the dequant scale
    under integer policies, a conv layer is ONE call and one HBM write
    instead of three round-trips (DESIGN.md section 7.3).

    Integer policies run every contraction on the limb substrate.  The
    systolic engine implements exactly the integer policies and fp32; the
    implicit-GEMM engine additionally runs bf16x3/bf16x6 (streamed patches,
    per-K-block recombine schedule, no HBM patch matrix -- DESIGN.md
    section 7.4).  ``"auto"`` keeps native_bf16 on im2col, and an EXPLICIT
    engine choice with an unimplemented policy raises through
    :func:`validate_path_policy` rather than silently downgrading to
    native dots.

    The implicit engine's deeper epilogue fusions (DESIGN.md section 7.7):
    ``pool=(window, pstride, ppad)`` folds the FOLLOWING maxpool into the
    conv epilogue (the output is the pooled tensor); ``quantize_next=b``
    additionally quantizes the (pooled) output with the next 3x3/s1/SAME
    layer's tile-granular scale plan at ``base_bits=b``, returning a
    :class:`QActivation`.  A QActivation ``x`` input is the matching
    consumer side and runs on the implicit engine only.  ``k_pipeline``
    toggles the implicit kernel's double-buffered DMA pipelining across
    K steps (planner-visible; a no-op off-TPU).
    """
    # Lazy imports: systolic/kernels import this module for the limb core,
    # and the planner imports this module for the dispatch primitives.
    from .systolic import conv2d_im2col
    from repro.kernels.conv2d import (
        conv2d_implicit, conv2d_systolic, conv2d_winograd)

    kh, kw, cin, cout = w.shape
    if isinstance(x, QActivation):
        if path in ("auto", "implicit"):
            path = "implicit"
        else:
            raise ValueError(
                f"path={path!r} cannot consume a QActivation: pre-quantized "
                "handoff activations are an implicit-engine contract "
                "(DESIGN.md section 7.7)")
    if path == "auto":
        from .planner import heuristic_path
        path = heuristic_path(kh=kh, kw=kw, stride=stride, cin=cin,
                              cout=cout, policy=policy, padding=padding,
                              cached_weight=isinstance(w, QWeight))
        # Defense in depth: even if the selector is overridden/buggy, auto
        # must never downgrade a policy to an engine that cannot run it
        # exactly -- reroute to im2col, which honors every policy.
        if not path_supports_policy(path, policy):
            path = "im2col"
    if pool is not None or quantize_next is not None:
        want = "pool_quant" if quantize_next is not None else "pool"
        if not path_supports_fusion(path, want):
            raise ValueError(
                f"path={path!r} does not implement the {want!r} epilogue "
                "fusion; only the implicit engine pools/quantizes in its "
                "epilogue (DESIGN.md section 7.7)")
    if path == "im2col":
        return conv2d_im2col(x, w, stride=stride, padding=padding,
                             policy=policy, bias=bias, activation=activation)
    validate_path_policy(path, policy)
    spec = policy_int_spec(policy)
    if path == "systolic":
        if spec is None:
            variant, base_bits = "native", 7
            if isinstance(w, QWeight):
                w = dequantize_weight(w)
        else:
            variant, base_bits = spec
        bh, bc = block if block is not None else (None, None)
        return conv2d_systolic(
            x, w, stride=stride, padding=padding,
            block_h=bh, block_c=bc,
            variant=variant, base_bits=base_bits,
            bias=bias, activation=activation, interpret=interpret,
        )
    if path == "implicit":
        if spec is None:
            pv = getattr(policy, "value", policy)
            variant = "native" if pv == "fp32" else pv
            base_bits = 7
        else:
            variant, base_bits = spec
        return conv2d_implicit(
            x, w, stride=stride, padding=padding, block=block,
            variant=variant, base_bits=base_bits,
            bias=bias, activation=activation, interpret=interpret,
            pool=pool, quantize_next=quantize_next, k_pipeline=k_pipeline,
        )
    if path == "winograd":
        variant, base_bits = spec
        return conv2d_winograd(
            x, w, stride=stride, padding=padding, block=block,
            variant=variant, base_bits=base_bits,
            bias=bias, activation=activation, interpret=interpret,
        )
    raise ValueError(f"unknown conv path: {path!r}")
