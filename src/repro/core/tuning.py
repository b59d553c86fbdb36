"""VMEM-aware conv tile autotuner: feasibility model, measured sweep, cache.

Shen et al. ("Maximizing CNN Accelerator Efficiency Through Resource
Partitioning") show that tuning the compute schedule to each layer's shape
recovers large efficiency losses; on this repo's Pallas conv engines the
schedule is the tile triple ``(bm, bc, bk)`` of the implicit-GEMM kernel
and ``(block_h, block_c)`` of the systolic kernel.  This module owns that
knob end to end:

* **Feasibility model** (:func:`implicit_vmem_bytes` /
  :func:`systolic_vmem_bytes` / :func:`winograd_vmem_bytes` /
  :func:`feasible`): the VMEM working set of
  a candidate tile -- dual halo row-blocks, streamed weight block, output
  block, scratch accumulators, double buffering, (8, 128) tile padding --
  plus the halo and wrap-free-group rules.  The winograd kind's set adds
  the 16-point transformed working set (two int16 V planes and three int32
  limb partial planes per point block).  Pure arithmetic, no execution:
  CI runs ``python -m repro.core.tuning --check`` so a tile-shape
  regression that would OOM VMEM fails fast.
* **Measured sweep** (:func:`tune_layer` / :func:`tune_model`): time the
  real conv entry points over the feasible candidates ON THIS BACKEND and
  persist the argmin.
* **Persistent cache**: ``benchmarks/tuned/default.json`` (committed; the
  only file tiles are read from), keyed by
  :func:`layer_key` = kind | variant/base_bits | layer geometry | backend.
  Atomic tmp+rename writes, round-trip tested.  The same cache also holds
  the DISPATCH schema: the thin-stem channel threshold
  (:func:`stem_cin`, key ``dispatch|stem_cin|{backend}``) that
  ``select_conv_path`` consults, so the materialize-vs-stream crossover is
  a measured, per-backend knob rather than a hard-coded constant.
* **Resolution** (:func:`resolve_block`): what the ops wrappers call at
  trace time when no explicit block is given -- cache hit (re-validated
  against the feasibility model) or the heuristic default.  ``cnn_forward``
  and ``CNNServeEngine`` therefore consult the tuner for every conv layer
  without any plumbing.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import pathlib
import tempfile
import time
from typing import Iterable, Optional

#: v5e-class VMEM per core; candidates must fit a conservative fraction.
VMEM_BYTES = 16 * 2**20
VMEM_BUDGET = int(0.75 * VMEM_BYTES)

_INT_VARIANTS = ("karatsuba", "schoolbook")

#: Lanes of one vreg: a Pallas conv kernel stages its input in chunks this
#: wide (``kernels.conv2d.conv2d.staging_shape``).
LANES = 128

CACHE_ENV = "REPRO_TUNED_DIR"
DEFAULT_CACHE_NAME = "default.json"
SCHEMA = "conv-tile-cache/v1"


def _roundup(n: int, m: int) -> int:
    return -(-n // m) * m


def _tile_bytes(shape: tuple[int, ...], itemsize: int) -> int:
    """Bytes of a VMEM buffer with (8, 128) sublane/lane tile padding."""
    dims = list(shape)
    dims[-1] = _roundup(dims[-1], 128)
    if len(dims) >= 2:
        dims[-2] = _roundup(dims[-2], 8)
    out = itemsize
    for d in dims:
        out *= d
    return out


def _max_cin_block(kh, kw, variant, base_bits):
    from repro.kernels.conv2d.implicit_gemm import max_cin_block
    return max_cin_block(kh, kw, variant=variant, base_bits=base_bits)


# ---------------------------------------------------------------------------
# Feasibility model.
# ---------------------------------------------------------------------------

def implicit_vmem_bytes(*, kh, kw, stride, w_img, cin, cout, bm, bc, bk,
                        variant, fusion: str = "bias_relu") -> int:
    """VMEM working set of one implicit-GEMM grid step (model, not measured).

    Dual f32 halo row-blocks + streamed weight block (int16 for the limb
    variants) + output block + scratch accumulators (3x int32 + f32 group
    accumulator for integer variants, one f32 otherwise), with double
    buffering on the pipelined operands.  A ``pool``/``pool_quant`` fusion
    prices the pooled epilogue: one overhang conv row on the scratch
    accumulator (the 3x2 window's dual-halo borrow), the activation scale
    grid bound TWICE (this row block + the next, so a pool window may
    straddle the seam), and the pooled (bm/2, wo/2) output tile in place
    of the full conv tile.
    """
    integer = variant in _INT_VARIANTS
    pooled = fusion in ("pool", "pool_quant")
    wp = w_img + kw  # upper bound on the SAME-padded width
    wo = max((wp - kw) // stride + 1, 1)
    bk = min(bk, cin)
    bc = min(bc, cout)
    bm_e = bm + 1 if pooled else bm  # overhang row of the pooled epilogue
    x_blk = 2 * _tile_bytes((bm_e * stride, wp, bk), 4)
    w_blk = _tile_bytes((kh * kw * bk, bc), 2 if integer else 4)
    o_rows, o_cols = (max(bm // 2, 1), max(wo // 2, 1)) if pooled \
        else (bm, wo)
    o_blk = _tile_bytes((o_rows * o_cols, bc), 4)
    acc = (4 if integer else 1) * _tile_bytes((bm_e * wo, bc), 4)
    scales = ((2 if pooled else 1) * _tile_bytes((bm_e, wo), 4)
              + _tile_bytes((1, bc), 4)) if integer else 0
    return 2 * (x_blk + w_blk) + 2 * o_blk + acc + scales


def systolic_vmem_bytes(*, kh, kw, stride, w_img, cin, block_h, block_c,
                        variant) -> int:
    """VMEM working set of one systolic grid step (whole-Cin taps)."""
    integer = variant in _INT_VARIANTS
    wp = w_img + kw
    wo = max((wp - kw) // stride + 1, 1)
    ib = 2 if integer else 4
    x_blk = 2 * _tile_bytes((block_h * stride, wp, cin), ib)
    w_blk = _tile_bytes((kh * kw * cin, block_c), ib)
    o_blk = _tile_bytes((block_h * wo, block_c), 4)
    acc = (3 if integer else 1) * _tile_bytes((block_h * wo, block_c), 4)
    return 2 * (x_blk + w_blk) + 2 * o_blk + acc


def winograd_vmem_bytes(*, kh, kw, stride, w_img, cin, cout, bt, bc,
                        variant) -> int:
    """VMEM working set of one winograd F(2x2,3x3) grid step.

    Dual f32 halo row-blocks (2*bt padded rows each) + both int16 weight
    plane tensors (4x4xCinxbc each) + the 16-point transformed input planes
    (two int16 V planes) + the three int32 limb point-partial planes + the
    (2bt, 2tw) output block and the tile/channel scale vectors, with double
    buffering on the pipelined operands.
    """
    wp = w_img + kw
    wo = max((wp - kw) // stride + 1, 1)
    tw = max(-(-wo // 2), 1)
    bc = min(bc, _roundup(cout, 8))
    x_blk = 2 * _tile_bytes((2 * bt, wp, cin), 4)     # dual halo row blocks
    w_blk = 2 * _tile_bytes((16 * cin, bc), 2)        # uh + ul planes
    v_blk = 2 * _tile_bytes((16 * bt * tw, cin), 2)   # transformed input
    m_blk = 3 * _tile_bytes((16 * bt * tw, bc), 4)    # limb point partials
    o_blk = _tile_bytes((2 * bt * 2 * tw, bc), 4)
    scales = _tile_bytes((bt, tw), 4) + _tile_bytes((1, bc), 4)
    return 2 * (x_blk + w_blk) + 2 * o_blk + v_blk + m_blk + scales


def feasible(kind: str, *, kh, kw, stride, h, cin, cout, variant,
             base_bits, block, fusion: str = "bias_relu"
             ) -> tuple[bool, str]:
    """(ok, reason): halo rule, wrap-free group rule, VMEM budget.

    ``fusion``: the planned epilogue.  Pool fusions are an implicit-engine
    contract (:func:`repro.core.substrate.path_supports_fusion`) and add
    the pooled-tile/scale-grid terms to the implicit VMEM model.
    """
    if fusion in ("pool", "pool_quant") and kind != "implicit":
        return False, (f"fusion {fusion!r} needs the implicit engine's "
                       f"pooled epilogue, not {kind!r}")
    if kind == "winograd":
        bt, bc = block
        if min(bc, cout) > LANES:
            return False, (f"bc={bc}: winograd's strided output stores take "
                           f"at most {LANES} lanes")
        if kh != 3 or kw != 3 or stride != 1:
            return False, f"winograd needs 3x3/s1, got k{kh}x{kw} s{stride}"
        if variant in _INT_VARIANTS:
            from repro.kernels.conv2d.winograd import winograd_accum_bound
            if winograd_accum_bound(cin, variant=variant,
                                    base_bits=base_bits) >= 2**31:
                return False, f"cin={cin}: tile contraction would wrap int32"
        else:
            return False, f"winograd needs an int variant, got {variant!r}"
        used = winograd_vmem_bytes(kh=kh, kw=kw, stride=stride, w_img=h,
                                   cin=cin, cout=cout, bt=bt, bc=bc,
                                   variant=variant)
    elif kind == "implicit":
        bm, bc, bk = block
        if bm * stride < kh - stride:
            return False, f"halo: bm*stride={bm * stride} < kh-stride={kh - stride}"
        if variant in _INT_VARIANTS:
            cap = _max_cin_block(kh, kw, variant, base_bits)
            if min(bk, cin) > cap:
                return False, f"bk={bk}: one K step would wrap int32 (cap {cap})"
        if fusion in ("pool", "pool_quant") and min(bc, cout) > LANES:
            return False, (f"bc={bc}: the pooled epilogue's strided loads "
                           f"take at most {LANES} lanes")
        if min(bk, cin) > LANES and min(bk, cin) % LANES:
            return False, (f"bk={min(bk, cin)}: a K chunk wider than {LANES} "
                           f"lanes must be a multiple of {LANES} (staging)")
        used = implicit_vmem_bytes(kh=kh, kw=kw, stride=stride, w_img=h,
                                   cin=cin, cout=cout, bm=bm, bc=bc, bk=bk,
                                   variant=variant, fusion=fusion)
    elif kind == "systolic":
        block_h, block_c = block
        if block_h * stride < kh - stride:
            return False, f"halo: block_h*stride={block_h * stride} < kh-stride={kh - stride}"
        used = systolic_vmem_bytes(kh=kh, kw=kw, stride=stride, w_img=h,
                                   cin=cin, block_h=block_h, block_c=block_c,
                                   variant=variant)
    else:
        return False, f"unknown kind {kind!r}"
    if used > VMEM_BUDGET:
        return False, f"vmem {used / 2**20:.1f} MiB > budget {VMEM_BUDGET / 2**20:.1f} MiB"
    return True, ""


def default_block(kind: str, *, kh, kw, stride, h, cin, cout, variant,
                  base_bits) -> tuple:
    """Heuristic tile schedule when the cache has no measured entry."""
    if kind == "systolic":
        return (8, 128)
    if kind == "winograd":
        bt, bc = 4, min(128, _roundup(cout, 8))
        def wused(b):
            return winograd_vmem_bytes(kh=kh, kw=kw, stride=stride, w_img=h,
                                       cin=cin, cout=cout, bt=b[0], bc=b[1],
                                       variant=variant)
        while wused((bt, bc)) > VMEM_BUDGET and bt > 1:
            bt //= 2
        return (bt, bc)
    bm = 8
    while bm * stride < kh - stride:
        bm *= 2
    bc = min(128, _roundup(cout, 8))
    if cin <= 512:
        bk = cin
    else:
        nk = -(-cin // 512)
        bk = _roundup(-(-cin // nk), 8)
    if variant in _INT_VARIANTS:
        bk = min(bk, _max_cin_block(kh, kw, variant, base_bits))
    if bk > LANES:  # whole lane tiles: the kernels stage 128-lane chunks
        bk = bk // LANES * LANES
    # Shrink the K chunk, then the Cout tile, then the row block (down to
    # its halo floor), until the model says it fits.
    def used(b):
        return implicit_vmem_bytes(kh=kh, kw=kw, stride=stride, w_img=h,
                                   cin=cin, cout=cout, bm=b[0], bc=b[1],
                                   bk=b[2], variant=variant)
    while used((bm, bc, bk)) > VMEM_BUDGET and bk > LANES:
        bk = max(bk // 2 // LANES * LANES, LANES)
    while used((bm, bc, bk)) > VMEM_BUDGET and bc > 128:
        bc = _roundup(bc // 2, 8)
    bm_floor = 1
    while bm_floor * stride < kh - stride:
        bm_floor *= 2
    while used((bm, bc, bk)) > VMEM_BUDGET and bm > bm_floor:
        bm //= 2
    return (bm, bc, bk)


def conv_hbm_bytes(path: str, *, kh, kw, stride, h, cin, cout, variant,
                   base_bits, n: int = 1, fusion: str = "bias_relu",
                   handoff_in: bool = False) -> int:
    """Modeled HBM traffic of one conv call (bytes, batch ``n``, SAME pads).

    Both paths are modeled as tiled GEMMs that re-read their A source once
    per Cout block and their weights once per M block.  The materialized
    im2col path's A source is the (M, KH*KW*Cin) patch matrix -- written
    once after reading the input, then re-read per Cout block (the KH*KW x
    blowup the implicit path eliminates); the implicit path's A source is
    the compact NHWC input itself, read twice per pass for the dual
    halo row-blocks.  The absolute numbers are a model, not a measurement;
    the RATIO is the benchmark's HBM-bytes-per-image delta.

    ``fusion`` changes what the epilogue writes back (DESIGN.md 7.7):

    * ``"bias_relu"`` -- the fused default: one f32 output write.
    * ``"none"`` -- the unfused epilogue re-reads and re-writes the raw
      conv output for the separate bias+relu pass (+2x output bytes).
    * ``"pool"`` -- the 2x2/s2 maxpool runs on the output tile in VMEM, so
      only the POOLED f32 tensor reaches HBM (~1/4 the output bytes).
    * ``"pool_quant"`` -- the pooled output leaves as the next layer's
      handoff: consumer-padded int16 values plus the f32 tile-scale grid
      (~1/8 the f32 bytes).

    ``handoff_in`` models the A side of a handoff CONSUMER: the input is
    read as padded int16 values + the scale grid instead of f32 (halves
    every A-source term), and the per-patch activation-scale stream
    disappears (the cell grid rides the A side).
    """
    integer = variant in _INT_VARIANTS
    ho = -(-h // stride)
    wo = ho
    m = n * ho * wo
    kdim = kh * kw * cin
    if handoff_in:
        x_bytes = (n * (h + 2) * (h + 2) * cin * 2
                   + n * -(-h // 2) * -(-h // 2) * 4)
    else:
        x_bytes = n * h * h * cin * 4
    out_bytes = m * cout * 4
    extra = 0
    if fusion == "none":
        extra = 2 * out_bytes      # separate bias+relu pass round-trip
    elif fusion in ("pool", "pool_quant"):
        hp, wp = max(ho // 2, 1), max(wo // 2, 1)   # 2x2/s2 VALID
        if fusion == "pool":
            out_bytes = n * hp * wp * cout * 4
        else:
            out_bytes = (n * (hp + 2) * (wp + 2) * cout * 2      # int16
                         + n * -(-hp // 2) * -(-wp // 2) * 4)    # scale grid
    elif fusion != "bias_relu":
        raise ValueError(f"unknown fusion {fusion!r}")
    out_bytes += extra
    w_elt = 2 if integer else 4
    w_bytes = kdim * cout * w_elt
    if path == "im2col":
        patches = m * kdim * 4
        cout_blocks = -(-cout // 128)
        m_blocks = -(-m // 128)
        return (x_bytes + patches                      # build the matrix
                + patches * cout_blocks                # re-read per N block
                + w_bytes * m_blocks + out_bytes)
    if path == "implicit":
        bm, bc, _ = default_block("implicit", kh=kh, kw=kw, stride=stride,
                                  h=h, cin=cin, cout=cout, variant=variant,
                                  base_bits=base_bits)
        cout_blocks = -(-cout // min(bc, cout))
        row_blocks = n * max(-(-ho // bm), 1)
        scales = m * 4 if integer and not handoff_in else 0
        return (2 * x_bytes * cout_blocks              # dual halo row blocks
                + w_bytes * row_blocks + out_bytes + scales)
    if path == "systolic":
        ib = 2 if integer else 4
        cout_blocks = -(-cout // 128)
        row_blocks = n * max(-(-ho // 8), 1)
        return (2 * (n * h * h * cin * ib) * cout_blocks
                + w_bytes * row_blocks + out_bytes + (n * cout * 4))
    if path == "winograd":
        # 16 transformed taps replace the 9 spatial taps, shipped as TWO
        # int16 limb planes; the A source is still the compact NHWC input
        # (dual halo row blocks), and the tile-granular scale grid is a
        # quarter the size of the implicit path's per-patch scales.  The
        # kernel grid runs batch INNERMOST, so the weight planes are
        # fetched once per row block and stay resident across the batch:
        # row_blocks deliberately has NO xN factor.
        bt, bc = default_block("winograd", kh=kh, kw=kw, stride=stride, h=h,
                               cin=cin, cout=cout, variant=variant,
                               base_bits=base_bits)
        th = max(-(-ho // 2), 1)
        cout_blocks = -(-cout // min(bc, cout))
        row_blocks = max(-(-th // bt), 1)
        wino_w_bytes = 2 * 16 * cin * cout * 2
        scales = n * th * max(-(-wo // 2), 1) * 4 + cout * 4
        return (2 * x_bytes * cout_blocks
                + wino_w_bytes * row_blocks + out_bytes + scales)
    raise ValueError(f"unknown path {path!r}")


# ---------------------------------------------------------------------------
# Persistent cache.
# ---------------------------------------------------------------------------

def tuned_dir() -> pathlib.Path:
    """benchmarks/tuned/ (or $REPRO_TUNED_DIR) -- the cache directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return pathlib.Path(env)
    return (pathlib.Path(__file__).resolve().parents[3] / "benchmarks"
            / "tuned")


def layer_key(kind: str, *, kh, kw, stride, h, cin, cout, variant, base_bits,
              backend: Optional[str] = None) -> str:
    """Stable cache key: tile kind, multiplier, layer geometry, backend."""
    if backend is None:
        import jax
        backend = jax.default_backend()
    return (f"{kind}|{variant}|b{base_bits}|k{kh}x{kw}|s{stride}|h{h}"
            f"|cin{cin}|cout{cout}|{backend}")


class TuneCache:
    """The persistent JSON cache: {key: {block, us, measured}}."""

    def __init__(self, path):
        self.path = pathlib.Path(path)
        self.entries: dict = {}

    @classmethod
    def load(cls, path) -> "TuneCache":
        cache = cls(path)
        p = pathlib.Path(path)
        if p.exists():
            data = json.loads(p.read_text())
            if data.get("schema") == SCHEMA:
                cache.entries = data.get("entries", {})
        return cache

    def save(self) -> None:
        self.path.parent.mkdir(parents=True, exist_ok=True)
        payload = {"schema": SCHEMA, "entries": self.entries}
        # Atomic tmp + rename (the checkpointer's convention): a killed
        # writer never corrupts the committed cache.
        fd, tmp = tempfile.mkstemp(dir=self.path.parent, suffix=".tmp")
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)

    def get(self, key: str) -> Optional[dict]:
        return self.entries.get(key)

    def put(self, key: str, block, *, us: Optional[float] = None,
            measured: bool = True) -> None:
        self.entries[key] = {"block": list(block), "us": us,
                             "measured": measured}

    def put_stem(self, cin: int, *, backend: Optional[str] = None) -> None:
        """Persist the thin-stem dispatch threshold for ``backend``."""
        self.entries[stem_key(backend)] = {"cin": int(cin)}


#: Fallback thin-stem channel threshold: below this Cin the materialized
#: im2col stem beats the streaming engines (the RGB-stem crossover measured
#: when the dispatch rule landed); the cache can override it per backend.
DEFAULT_STEM_CIN = 16


def stem_key(backend: Optional[str] = None) -> str:
    """Cache key of the dispatch-schema stem threshold entry."""
    if backend is None:
        import jax
        backend = jax.default_backend()
    return f"dispatch|stem_cin|{backend}"


def stem_cin(backend: Optional[str] = None) -> int:
    """The thin-stem Cin threshold ``select_conv_path`` compares against.

    Cache entry ``{"cin": N}`` under :func:`stem_key` wins; otherwise
    :data:`DEFAULT_STEM_CIN`.  Malformed entries fall back to the default
    rather than poisoning dispatch.
    """
    ent = _cache().get(stem_key(backend))
    if isinstance(ent, dict):
        cin = ent.get("cin")
        if isinstance(cin, int) and cin >= 1:
            return cin
    return DEFAULT_STEM_CIN


@functools.lru_cache(maxsize=None)
def _load_cache(path_str: str, mtime: float) -> TuneCache:
    return TuneCache.load(path_str)


def _cache() -> TuneCache:
    """The committed cache, ``default.json`` -- the one source of measured
    tiles, so what runs is what the repository holds (keyed by mtime so a
    rewrite is seen)."""
    p = tuned_dir() / DEFAULT_CACHE_NAME
    return _load_cache(str(p), p.stat().st_mtime if p.exists() else 0.0)


def resolve_block(kind: str, *, kh, kw, stride, h, cin, cout, variant,
                  base_bits) -> tuple:
    """The per-layer tile schedule: cache hit (re-validated) or default.

    Called by ``conv2d_implicit``/``conv2d_systolic`` at trace time when no
    explicit block is passed, so every model forward and serving engine
    consults the tuner per conv layer.
    """
    key = layer_key(kind, kh=kh, kw=kw, stride=stride, h=h, cin=cin,
                    cout=cout, variant=variant, base_bits=base_bits)
    ent = _cache().get(key)
    if ent is not None:
        block = tuple(ent["block"])
        ok, _ = feasible(kind, kh=kh, kw=kw, stride=stride, h=h, cin=cin,
                         cout=cout, variant=variant, base_bits=base_bits,
                         block=block)
        if ok:
            return block
    return default_block(kind, kh=kh, kw=kw, stride=stride, h=h, cin=cin,
                         cout=cout, variant=variant, base_bits=base_bits)


# ---------------------------------------------------------------------------
# Measured sweep.
# ---------------------------------------------------------------------------

def candidate_blocks(kind: str, *, kh, kw, stride, h, cin, cout, variant,
                     base_bits) -> list[tuple]:
    """Feasible candidates around the default (the measured sweep's domain)."""
    base = default_block(kind, kh=kh, kw=kw, stride=stride, h=h, cin=cin,
                         cout=cout, variant=variant, base_bits=base_bits)
    if kind == "systolic":
        cands = {base} | {(bh, bc) for bh in (8, 16, 32) for bc in (128, 256)}
    elif kind == "winograd":
        cands = {base} | {(bt, bc) for bt in (1, 2, 4, 8) for bc in (128, 256)}
    else:
        bm0, bc0, _ = base
        bks = {min(cin, b) for b in (128, 256, 512, 1024, 2048)} | {base[2]}
        cands = {(bm, bc0, bk) for bm in {bm0, 16} for bk in bks}
        cands.add(base)
    out = []
    for block in sorted(cands):
        ok, _ = feasible(kind, kh=kh, kw=kw, stride=stride, h=h, cin=cin,
                         cout=cout, variant=variant, base_bits=base_bits,
                         block=block)
        if ok:
            out.append(block)
    return out


def _time_call(fn, *args, iters: int = 3, warmup: int = 1) -> float:
    """Median wall microseconds (mirrors benchmarks.common.time_call; core
    must not import benchmarks)."""
    import jax
    import numpy as np
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def _first_line(e: BaseException) -> str:
    """``Type: first line of the message`` -- how a pruned candidate is
    reported."""
    msg = str(e).strip().splitlines()
    return f"{type(e).__name__}: {msg[0] if msg else ''}"


def tune_layer(kind: str, *, kh, kw, stride, h, cin, cout, variant,
               base_bits, iters: int = 3, cache: Optional[TuneCache] = None,
               verbose: bool = False) -> tuple:
    """Measure the feasible candidates on this backend, persist the argmin."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.substrate import quantize_weight
    from repro.kernels.conv2d.ops import (
        conv2d_implicit,
        conv2d_systolic,
        conv2d_winograd,
    )

    if kind in ("systolic", "winograd") and jax.default_backend() != "tpu":
        # Interpret-mode Pallas timings are meaningless; keep the default.
        return default_block(kind, kh=kh, kw=kw, stride=stride, h=h, cin=cin,
                             cout=cout, variant=variant, base_bits=base_bits)
    if kind == "winograd" and not feasible(
            kind, kh=kh, kw=kw, stride=stride, h=h, cin=cin, cout=cout,
            variant=variant, base_bits=base_bits,
            block=default_block(kind, kh=kh, kw=kw, stride=stride, h=h,
                                cin=cin, cout=cout, variant=variant,
                                base_bits=base_bits))[0]:
        # Ineligible layer shape: conv2d_winograd would reroute to implicit,
        # so any measurement here times the wrong engine.
        return default_block(kind, kh=kh, kw=kw, stride=stride, h=h, cin=cin,
                             cout=cout, variant=variant, base_bits=base_bits)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, h, h, cin)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((kh, kw, cin, cout)) * 0.1,
                    jnp.float32)
    integer = variant in _INT_VARIANTS
    if integer:
        w = quantize_weight(w, base_bits=base_bits)
    cands = candidate_blocks(kind, kh=kh, kw=kw, stride=stride, h=h,
                             cin=cin, cout=cout, variant=variant,
                             base_bits=base_bits)
    if kind == "implicit" and jax.default_backend() != "tpu":
        # The off-TPU lax mirror consumes only bk (the recombine group
        # boundaries); bm/bc are Pallas tile shapes it ignores, so timing
        # their variants would just measure noise at full conv cost.
        seen, dedup = set(), []
        for b in cands:
            if b[2] not in seen:
                seen.add(b[2])
                dedup.append(b)
        cands = dedup
    best, best_us = None, float("inf")
    for block in cands:
        if kind == "implicit":
            fn = functools.partial(conv2d_implicit, stride=stride,
                                   variant=variant, base_bits=base_bits,
                                   block=tuple(block))
        elif kind == "winograd":
            fn = functools.partial(conv2d_winograd, stride=stride,
                                   variant=variant, base_bits=base_bits,
                                   block=tuple(block))
        else:
            fn = functools.partial(conv2d_systolic, stride=stride,
                                   variant=variant if integer else "native",
                                   base_bits=base_bits,
                                   block_h=block[0], block_c=block[1])
        try:
            us = _time_call(fn, x, w, iters=iters)
        except Exception as e:  # infeasible at runtime: skip, keep tuning
            # Always said, verbose or not: a tile the compiler refuses must
            # not vanish silently from a tune run.
            print(f"  {block}: pruned ({_first_line(e)})")
            continue
        if verbose:
            print(f"  {block}: {us:.1f} us")
        if us < best_us:
            best, best_us = tuple(block), us
    if best is None:
        return default_block(kind, kh=kh, kw=kw, stride=stride, h=h, cin=cin,
                             cout=cout, variant=variant, base_bits=base_bits)
    if cache is not None:
        key = layer_key(kind, kh=kh, kw=kw, stride=stride, h=h, cin=cin,
                        cout=cout, variant=variant, base_bits=base_bits)
        cache.put(key, best, us=best_us)
    return best


def conv_layer_shapes(cfg) -> list[dict]:
    """Unique conv layer geometries of a CNNConfig (the tuning work list).

    Thin dedup over :func:`repro.models.cnn.cnn_conv_geometries` -- the one
    walker of a config's conv spine -- dropping the padding field (tile
    feasibility and timing depend on the geometry, not the pad plan).
    """
    from repro.models.cnn import cnn_conv_geometries

    shapes, seen = [], set()
    for g in cnn_conv_geometries(cfg):
        key = (g["kh"], g["stride"], g["h"], g["cin"], g["cout"])
        if key not in seen:
            seen.add(key)
            shapes.append({k: v for k, v in g.items() if k != "padding"})
    return shapes


def _policy_variant(policy: str) -> tuple[str, int]:
    from repro.core.substrate import INT_POLICY_SPECS
    pv = getattr(policy, "value", policy)
    if pv in INT_POLICY_SPECS:
        return INT_POLICY_SPECS[pv]
    if pv in ("bf16x3", "bf16x6"):
        return (pv, 7)
    return ("native", 7)


def tune_model(name: str, *, policies=("kom_int14", "schoolbook_int16"),
               kinds=("implicit", "systolic", "winograd"), iters: int = 3,
               cache_path=None, verbose: bool = True) -> TuneCache:
    """Measured sweep over every unique conv layer of a registered CNN."""
    from repro.configs import get_config

    path = cache_path or (tuned_dir() / DEFAULT_CACHE_NAME)
    cache = TuneCache.load(path)
    cfg = get_config(name)
    for layer in conv_layer_shapes(cfg):
        for policy in policies:
            variant, base_bits = _policy_variant(policy)
            for kind in kinds:
                if verbose:
                    print(f"{name} {kind} {policy} "
                          f"k{layer['kh']} s{layer['stride']} h{layer['h']} "
                          f"cin{layer['cin']} cout{layer['cout']}:")
                tune_layer(kind, variant=variant, base_bits=base_bits,
                           iters=iters, cache=cache, verbose=verbose, **layer)
    cache.save()
    _load_cache.cache_clear()  # next resolve_block sees the new entries
    return cache


def tune_config(cfg, *, iters: int = 2, cache_path=None,
                verbose: bool = False) -> TuneCache:
    """Measured sweep for one CNNConfig's conv layers under its own policy.

    The hook :class:`~repro.serving.cnn_engine.CNNServeEngine` calls with
    ``tune=True``: every unique conv layer shape of ``cfg`` is swept on this
    backend and the argmin persisted, so the engine's jitted forward picks
    the tuned tiles up through :func:`resolve_block` at trace time.

    Writes go to the committed cache, ``default.json``: its keys are
    backend-scoped, so a sweep on one backend adds entries beside the
    other backends' without touching them, and the result is committed
    like any other tile choice.
    """
    path = cache_path or (tuned_dir() / DEFAULT_CACHE_NAME)
    cache = TuneCache.load(path)
    variant, base_bits = _policy_variant(cfg.policy)
    for layer in conv_layer_shapes(cfg):
        for kind in ("implicit", "systolic", "winograd"):
            tune_layer(kind, variant=variant, base_bits=base_bits,
                       iters=iters, cache=cache, verbose=verbose, **layer)
    cache.save()
    _load_cache.cache_clear()
    return cache


# ---------------------------------------------------------------------------
# CI check mode: feasibility only, no execution.
# ---------------------------------------------------------------------------

def check(models: Iterable[str] = ("alexnet", "vgg16", "vgg19"),
          policies=("kom_int14", "schoolbook_int16", "fp32")) -> list[str]:
    """Resolve every layer's tile schedule and validate it against the
    feasibility model (and the wrap-free recombine schedule).  Returns the
    list of violations -- empty means no tile-shape regression."""
    from repro.configs import get_config
    from repro.kernels.conv2d.implicit_gemm import recombine_schedule

    from repro.core.planner import heuristic_path

    errors = []
    for name in models:
        cfg = get_config(name)
        for layer in conv_layer_shapes(cfg):
            for policy in policies:
                variant, base_bits = _policy_variant(policy)
                # implicit must be feasible everywhere (explicit calls and
                # depth reroutes may land any layer on it); systolic and
                # winograd only where TPU dispatch actually routes the layer.
                kinds = ["implicit"]
                sel = heuristic_path(kh=layer["kh"], kw=layer["kw"],
                                     stride=layer["stride"],
                                     cin=layer["cin"], cout=layer["cout"],
                                     on_tpu=True, policy=policy,
                                     cached_weight=True)
                if sel in ("systolic", "winograd"):
                    kinds.append(sel)
                for kind in kinds:
                    block = resolve_block(kind, variant=variant,
                                          base_bits=base_bits, **layer)
                    ok, why = feasible(
                        kind, kh=layer["kh"], kw=layer["kw"],
                        stride=layer["stride"], h=layer["h"],
                        cin=layer["cin"], cout=layer["cout"],
                        variant=variant, base_bits=base_bits, block=block)
                    if not ok:
                        errors.append(
                            f"{name}/{policy}/{kind} {layer}: {block} -- {why}")
                if variant in _INT_VARIANTS:
                    bk = resolve_block("implicit", variant=variant,
                                       base_bits=base_bits, **layer)[2]
                    try:
                        recombine_schedule(layer["kh"], layer["kw"],
                                           layer["cin"], min(bk, layer["cin"]),
                                           variant=variant,
                                           base_bits=base_bits)
                    except ValueError as e:
                        errors.append(f"{name}/{policy}/implicit {layer}: {e}")
    return errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="feasibility model only, no measurement (CI lane)")
    ap.add_argument("--tune", action="store_true",
                    help="measured sweep on this backend, persist the cache")
    ap.add_argument("--models", nargs="*",
                    default=["alexnet", "vgg16", "vgg19"])
    ap.add_argument("--policies", nargs="*",
                    default=["kom_int14", "schoolbook_int16", "fp32"])
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--cache", default=None,
                    help="cache path (default benchmarks/tuned/default.json)")
    args = ap.parse_args(argv)
    if args.check:
        errors = check(models=args.models, policies=tuple(args.policies))
        for e in errors:
            print(f"INFEASIBLE: {e}")
        print(f"tile feasibility: {len(errors)} violation(s)")
        return 1 if errors else 0
    if args.tune:
        for name in args.models:
            tune_model(name, policies=tuple(args.policies),
                       iters=args.iters, cache_path=args.cache)
        return 0
    ap.print_help()
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
