"""Reconfigurable systolic engine (paper Figs. 1-3), TPU-native.

The paper's engine is a grid of MAC cells whose interconnect a RISC-V core
rewires per layer type (conv / pool / FC / FIR).  On TPU the systolic grid is
the MXU and the 'bit file' is an XLA executable: ``SystolicEngine.configure``
returns a jitted callable specialized for the requested op, all sharing the
same matmul substrate (``policy_dot``) so the KOM technique applies uniformly.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from .precision import MatmulPolicy, policy_matmul
from .substrate import (
    QWeight,
    conv_pads,
    kom_qmax,
    policy_int_spec,
    prequant_dot_general,
)


def fir_systolic(x: jax.Array, h: jax.Array) -> jax.Array:
    """1-D FIR via the paper's systolic dataflow: Y_n = Y_{n-1} + h_k * X.

    ``x``: (..., n) signal; ``h``: (k,) taps.  Output (..., n) causal FIR
    (y[n] = sum_k h[k] x[n-k]) computed as a scan over taps -- a faithful
    transcription of Fig. 2's cell pipeline (each scan step is one cell).
    """
    n = x.shape[-1]

    def cell(y, k):
        shifted = jnp.roll(x, k, axis=-1)
        mask = jnp.arange(n) >= k
        return y + h[k] * shifted * mask, None

    y0 = jnp.zeros_like(x)
    y, _ = lax.scan(cell, y0, jnp.arange(h.shape[0]))
    return y


@functools.partial(jax.jit, static_argnames=("variant", "ho", "wo"))
def _im2col_tile_gemm(cols, wmat, xp, *, variant, ho, wo):
    """Tile-scaled int GEMM for winograd-eligible layers, under jit.

    The scale grid, the /scale quantization, and the dequant multiply all
    live inside ONE jit scope so their floating-point rewrites match the
    (internally jitted) winograd and implicit cores bit for bit whether the
    caller is eager or jitted -- the same regime-pinning trick those cores
    use (DESIGN.md section 7.5).
    """
    from repro.kernels.conv2d.winograd import (
        tile_scale_grid,
        tile_scales_upsampled,
    )
    qmax = kom_qmax(wmat.base_bits)
    ho_t, wo_t = -(-ho // 2), -(-wo // 2)
    s_tile = tile_scale_grid(xp, qmax, ho_t, wo_t)
    row_scale = tile_scales_upsampled(s_tile, ho, wo).reshape(-1, 1)
    return prequant_dot_general(cols, wmat, variant=variant,
                                row_scale=row_scale)


def conv2d_im2col(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int = 1,
    padding: str = "SAME",
    policy: MatmulPolicy = MatmulPolicy.NATIVE_BF16,
    bias: jax.Array | None = None,
    activation: str | None = None,
) -> jax.Array:
    """NHWC conv as im2col-GEMM -- the MXU mapping of the systolic conv array.

    x: (n, h, w, cin); w: (kh, kw, cin, cout) float HWIO or a cached
    :class:`~repro.core.substrate.QWeight`.  The GEMM goes through the
    precision policy, so conv layers inherit the KOM path.  ``bias`` (cout,)
    and ``activation`` ("relu") are applied post-GEMM in the same jit scope
    -- the im2col half of the fused conv epilogue (DESIGN.md section 7.3).
    """
    kh, kw, cin, cout = w.shape
    ho, wo, pads = conv_pads(x.shape[1], x.shape[2], kh, kw, stride, padding)
    xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
    # im2col patches: (n, out_h, out_w, kh*kw*cin).  The extractor is a
    # convolution with a one-hot kernel; HIGHEST keeps a TPU from running
    # it as one bf16 pass, which would round every patch value to bf16.
    patches = lax.conv_general_dilated_patches(
        xp.transpose(0, 3, 1, 2),  # NCHW for the patch extractor
        filter_shape=(kh, kw),
        window_strides=(stride, stride),
        padding="VALID",
        precision=lax.Precision.HIGHEST,
    )  # (n, cin*kh*kw, out_h, out_w)
    n, ck, oh, ow = patches.shape
    cols = patches.transpose(0, 2, 3, 1).reshape(n * oh * ow, ck)
    # conv_general_dilated_patches emits channel-major (cin, kh, kw) order.
    if isinstance(w, QWeight):
        wmat = QWeight(w.values.transpose(2, 0, 1, 3).reshape(ck, cout),
                       w.scale, w.base_bits)
    else:
        wmat = w.transpose(2, 0, 1, 3).reshape(ck, cout)
    spec = policy_int_spec(policy) if isinstance(w, QWeight) else None
    tile_scaled = False
    if spec is not None:
        # Winograd-eligible layers (int policy, cached weight, 3x3/s1 under
        # the growth bound) quantize with the SHARED tile-granular scale
        # plan, so the materialized GEMM's integers -- hence its output --
        # are bitwise equal to the winograd/implicit engines' (DESIGN.md
        # section 7.5).
        from repro.kernels.conv2d.winograd import winograd_scale_eligible
        variant = spec[0]
        tile_scaled = winograd_scale_eligible(
            kh, kw, stride, cin, variant=variant, base_bits=w.base_bits)
    if tile_scaled:
        out = _im2col_tile_gemm(cols, wmat, xp, variant=variant, ho=ho, wo=wo)
    else:
        out = policy_matmul(cols, wmat, policy=policy)
    out = out.reshape(n, oh, ow, cout)
    if bias is not None:
        out = out + bias
    if activation == "relu":
        out = jax.nn.relu(out)
    elif activation is not None:
        raise ValueError(f"unknown activation: {activation!r}")
    return out


def pool2d(x: jax.Array, *, window: int, stride: int, kind: str = "max",
           padding: str = "VALID") -> jax.Array:
    """NHWC pooling on the same engine (reduce cells instead of MAC cells)."""
    if kind == "max":
        init, op = -jnp.inf, lax.max
    elif kind == "avg":
        init, op = 0.0, lax.add
    else:
        raise ValueError(kind)
    out = lax.reduce_window(
        x.astype(jnp.float32),
        init,
        op,
        window_dimensions=(1, window, window, 1),
        window_strides=(1, stride, stride, 1),
        padding=padding,
    )
    if kind == "avg":
        out = out / (window * window)
    return out


class SystolicEngine:
    """Config-driven dispatcher mirroring the paper's reconfigurable engine."""

    OPS = ("matmul", "fc", "conv2d", "pool_max", "pool_avg", "fir")

    def __init__(self, policy: MatmulPolicy = MatmulPolicy.NATIVE_BF16):
        self.policy = MatmulPolicy(policy)

    def configure(self, op: str, **cfg) -> Callable:
        """'Download the bit file': return a jitted callable for ``op``."""
        if op in ("matmul", "fc"):
            fn = functools.partial(policy_matmul, policy=self.policy)
        elif op == "conv2d":
            fn = functools.partial(conv2d_im2col, policy=self.policy, **cfg)
        elif op == "pool_max":
            fn = functools.partial(pool2d, kind="max", **cfg)
        elif op == "pool_avg":
            fn = functools.partial(pool2d, kind="avg", **cfg)
        elif op == "fir":
            fn = fir_systolic
        else:
            raise ValueError(f"unknown op {op!r}; expected one of {self.OPS}")
        return jax.jit(fn)
