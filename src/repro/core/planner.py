"""Whole-network ExecutionPlan: the per-model design-space explorer.

Shen et al.'s resource-partitioning result and Ahmad & Pasha's
design-space-exploration work (PAPERS.md) both argue the winning FPGA
configuration is a *jointly optimized per-layer plan*, not a per-call
heuristic.  This module is that plan's one home on the KOM substrate:

* **ExecutionPlan** (:class:`ExecutionPlan` / :class:`LayerPlan`): a
  schema-versioned, backend-stamped artifact for one (model, policy,
  backend) triple -- one entry per conv layer recording the chosen engine
  ``path``, its tile ``block``, the epilogue ``fusion``, the scored cost
  (``est_us``), the modeled ``hbm_bytes``, the achieved-vs-roofline
  fraction and the exactness bound the choice lives under, plus a
  ``source`` tag (``measured`` / ``model`` / ``default``) so a committed
  plan can never hide a silent coverage gap.  Registered as a *static*
  pytree: a plan threads through jit closures unchanged.
* **Design-space explorer** (:func:`explore`): per layer, jointly searches
  path x tile x fusion.  Candidates are pruned by the tuner's VMEM
  feasibility model and the engines' exactness bounds, then scored either
  by measured wall time of the real conv entry points (``tune_layer``-style
  timing, serving call convention) or -- with ``model_only=True`` -- by the
  :func:`repro.analysis.roofline.conv_layer_roofline` cost model over
  :func:`repro.core.tuning.conv_hbm_bytes` traffic.
* **Fallback scorer** (:func:`heuristic_path`): the ONE call site of
  ``substrate.select_conv_path`` in the repo (grep-tested).  It owns the
  tuner-cache consult for the thin-stem threshold that used to live inside
  ``substrate.py``; ``conv2d(path="auto")``, ``tuning.check`` and the
  benchmark tables all route here.
* **Resolution chain** (:func:`resolve_plan`): explicit plan > committed
  artifact for this (model, policy, backend) > :func:`heuristic_plan`,
  which reproduces today's per-call dispatch exactly (path from
  ``heuristic_path``, blocks left to the tuner cache).  ``cnn_forward``
  and ``CNNServeEngine`` resolve ONCE at build and thread the plan to
  every conv call.
* **Committed artifacts**: ``benchmarks/tuned/plans/<backend>.json`` --
  schema-versioned, backend-stamped, one file per backend holding the
  plans of every explored (model, policy).  ``python -m repro.core.planner
  --check`` validates the committed artifacts in CI (schema current,
  backend stamp matches the filename, every conv layer of the named model
  covered, every entry's path legal for its policy, blocks feasible under
  the VMEM model, exactness bounds under 2^31).

DESIGN.md section 7.6 documents the schema, the search order and the
artifact lifecycle.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import pathlib
from typing import Dict, Iterable, List, Optional, Tuple

from .substrate import (
    FUSIONS,
    INT_POLICY_SPECS,
    path_supports_fusion,
    path_supports_policy,
    policy_int_spec,
    select_conv_path,
)

PLAN_SCHEMA = "execution-plan/v1"
PLANS_DIRNAME = "plans"

#: Provenance tags a LayerPlan entry may carry (satellite: no silent
#: coverage gap -- a committed plan says per layer whether its score came
#: from a measurement, the cost model, or a defaulted fallback).
SOURCES = ("measured", "model", "default")

_INT_VARIANTS = ("karatsuba", "schoolbook")

#: Engines with a tunable tile schedule (the tuner cache's ``kind``s);
#: the materialized im2col GEMM has no block knob.
TUNABLE_KINDS = ("implicit", "systolic", "winograd")


# ---------------------------------------------------------------------------
# The artifact.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """One conv layer's jointly-chosen execution: engine, tiles, fusion."""

    key: str                 # geometry key, :func:`geometry_key`
    path: str                # im2col | systolic | implicit | winograd
    block: Optional[tuple]   # tile schedule for `path` (None: tuner/default)
    fusion: str = "bias_relu"        # one of substrate.FUSIONS: "none" |
    #   "bias_relu" | "pool" | "pool_quant" (pool fusions: implicit only,
    #   applied where the topology has a maxpool next -- DESIGN.md 7.7)
    est_us: Optional[float] = None   # scored cost (measured or modeled)
    hbm_bytes: Optional[int] = None  # modeled HBM traffic per image
    roofline_us: Optional[float] = None
    roofline_frac: Optional[float] = None  # achieved-vs-roofline (measured)
    exactness_bound: Optional[float] = None  # int32 accum bound of `path`
    source: str = "default"          # measured | model | default

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["block"] = list(self.block) if self.block is not None else None
        return d

    @classmethod
    def from_json(cls, d: dict) -> "LayerPlan":
        d = dict(d)
        if d.get("block") is not None:
            d["block"] = tuple(int(b) for b in d["block"])
        return cls(**d)


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Per-layer execution choices for one (model, policy, backend) triple."""

    model: str
    policy: str
    backend: str
    entries: Tuple[LayerPlan, ...]
    schema: str = PLAN_SCHEMA

    @functools.cached_property
    def by_key(self) -> Dict[str, LayerPlan]:
        return {e.key: e for e in self.entries}

    def lookup(self, *, kh, kw, stride, h, cin, cout,
               padding) -> Optional[LayerPlan]:
        """The entry for one conv layer geometry, or None (fallback)."""
        return self.by_key.get(geometry_key(kh=kh, kw=kw, stride=stride,
                                            h=h, cin=cin, cout=cout,
                                            padding=padding))

    def __hash__(self):  # static-pytree requirement (cached_property is ok:
        # frozen blocks field mutation, not attribute caching)
        return hash((self.model, self.policy, self.backend, self.entries,
                     self.schema))

    def __eq__(self, other):
        return (isinstance(other, ExecutionPlan)
                and (self.model, self.policy, self.backend, self.entries,
                     self.schema)
                == (other.model, other.policy, other.backend, other.entries,
                    other.schema))

    def to_json(self) -> dict:
        return {"model": self.model, "policy": self.policy,
                "layers": [e.to_json() for e in self.entries]}

    @classmethod
    def from_json(cls, d: dict, *, backend: str) -> "ExecutionPlan":
        return cls(model=d["model"], policy=d["policy"], backend=backend,
                   entries=tuple(LayerPlan.from_json(e)
                                 for e in d["layers"]))


# A plan is trace-time metadata: register as a static pytree so engines can
# close over (or pass) one through jit without it becoming a tracer.
try:
    import jax

    jax.tree_util.register_static(ExecutionPlan)
    jax.tree_util.register_static(LayerPlan)
except (ImportError, ValueError):  # pragma: no cover - double registration
    pass


def geometry_key(*, kh, kw, stride, h, cin, cout, padding) -> str:
    """Stable per-layer key: the exact shape tuple conv2d is called with."""
    return f"k{kh}x{kw}|s{stride}|h{h}|cin{cin}|cout{cout}|{padding}"


def parse_geometry_key(key: str) -> dict:
    """Invert :func:`geometry_key` (analysis tooling re-derives shapes)."""
    import re
    m = re.fullmatch(
        r"k(\d+)x(\d+)\|s(\d+)\|h(\d+)\|cin(\d+)\|cout(\d+)\|(SAME|VALID)",
        key)
    if m is None:
        raise ValueError(f"malformed geometry key: {key!r}")
    kh, kw, stride, h, cin, cout = (int(v) for v in m.groups()[:6])
    return dict(kh=kh, kw=kw, stride=stride, h=h, cin=cin, cout=cout,
                padding=m.group(7))


def plan_key(model: str, policy) -> str:
    return f"{model}|{getattr(policy, 'value', policy)}"


# ---------------------------------------------------------------------------
# Fallback scorer: the ONE select_conv_path call site in the repo.
# ---------------------------------------------------------------------------

def _stem_cin_threshold(stem_cin: Optional[int]) -> int:
    """The thin-stem routing threshold: tuner-cached per backend, default 16.

    Moved here from ``substrate.py`` -- the lazy tuner-cache consult is the
    planner's job now; ``select_conv_path`` itself is a pure shape rule.
    """
    if stem_cin is not None:
        return stem_cin
    try:
        from .tuning import stem_cin as tuned_stem_cin
        return tuned_stem_cin()
    except Exception:
        return 16  # tuning.DEFAULT_STEM_CIN, without cache IO in the way


def heuristic_path(*, kh: int, kw: int, stride: int, cin: int, cout: int,
                   on_tpu: Optional[bool] = None, policy=None,
                   cached_weight: bool = False, padding: str = "SAME",
                   stem_cin: Optional[int] = None) -> str:
    """Today's shape/policy dispatch rule, planner-owned.

    This is the repo's single call site of
    :func:`repro.core.substrate.select_conv_path` (grep-tested): the
    heuristic the resolution chain bottoms out on when no explicit plan and
    no committed artifact applies, byte-for-byte the pre-plan behavior.
    """
    return select_conv_path(
        kh=kh, kw=kw, stride=stride, cin=cin, cout=cout, on_tpu=on_tpu,
        policy=policy, cached_weight=cached_weight, padding=padding,
        stem_cin=_stem_cin_threshold(stem_cin))


def heuristic_plan(cfg, *, backend: Optional[str] = None,
                   on_tpu: Optional[bool] = None) -> ExecutionPlan:
    """The fallback ExecutionPlan: per-call dispatch, made explicit.

    Every conv layer gets ``heuristic_path``'s choice with ``block=None``
    (the ops wrappers keep resolving tiles through the tuner cache), so
    running a model through this plan is bitwise identical to today's
    ``path="auto"`` per-call resolution.
    """
    from repro.models.cnn import cnn_conv_geometries

    if backend is None:
        import jax
        backend = jax.default_backend()
    if on_tpu is None:
        on_tpu = backend == "tpu"
    cached = policy_int_spec(cfg.policy) is not None
    entries = []
    seen = set()
    for g in cnn_conv_geometries(cfg):
        key = geometry_key(**g)
        if key in seen:
            continue
        seen.add(key)
        path = heuristic_path(on_tpu=on_tpu, policy=cfg.policy,
                              cached_weight=cached,
                              **{k: v for k, v in g.items() if k != "h"})
        entries.append(LayerPlan(key=key, path=path, block=None,
                                 source="default"))
    return ExecutionPlan(model=cfg.name,
                         policy=getattr(cfg.policy, "value", cfg.policy),
                         backend=backend, entries=tuple(entries))


def materialized_fallback_plan(plan: ExecutionPlan) -> ExecutionPlan:
    """Reroute every conv layer to the materialized im2col path.

    The degraded-mode plan: engines switch to this after OOM-shaped
    failures, because the materialized path has the smallest live-VMEM
    footprint per tile (no streamed patch windows, no Pallas scratch) and
    honors EVERY policy.  Legality is the exactness contract the repo
    already tests -- under the integer policies all conv paths are bitwise
    equal (plan == auto == forced im2col, DESIGN.md sections 7.6/9), so a
    request retried on the degraded plan produces logits bitwise identical
    to the healthy plan.  Blocks are cleared so the tuner re-picks
    im2col-feasible tiles.

    Pool fusions are downgraded to ``bias_relu``: im2col has no pooled
    epilogue (``path_supports_fusion``), so the pool runs as its own
    ``pool2d`` pass.  For ``"pool"`` plans that is still bitwise (max is
    exact selection); a ``"pool_quant"`` plan is the ONE case where the
    degraded plan's logits may differ bitwise from the healthy plan's,
    because the healthy plan's handoff quantization recipe (DESIGN.md
    7.7) no longer runs -- a documented carve-out of the degrade
    contract.
    """
    entries = tuple(dataclasses.replace(
        e, path="im2col", block=None, est_us=None, roofline_frac=None,
        fusion="bias_relu" if e.fusion in ("pool", "pool_quant")
        else e.fusion,
        source="fallback")
                    for e in plan.entries)
    return dataclasses.replace(plan, entries=entries)


# ---------------------------------------------------------------------------
# The design-space explorer.
# ---------------------------------------------------------------------------

def _policy_variant(policy) -> tuple[str, int]:
    pv = getattr(policy, "value", policy)
    if pv in INT_POLICY_SPECS:
        return INT_POLICY_SPECS[pv]
    if pv in ("bf16x3", "bf16x6"):
        return (pv, 7)
    return ("native", 7)


def candidate_paths(*, kh, kw, stride, cin, cout, padding, policy,
                    backend: str) -> List[str]:
    """Exact-capable engines for this layer on this backend, pruned.

    im2col honors every policy everywhere.  The systolic engine is a TPU
    engine (off-TPU it would time interpret-mode Pallas) and must fit its
    shape niche; winograd needs an int policy, 3x3/s1/SAME and the growth
    bound; implicit runs ints on every backend but floats only where the
    streamed taps beat XLA's native patch GEMM (TPU).  Streaming engines
    are pruned below the measured thin-stem crossover (the RGB stem's
    per-tap contraction starves them ~35x, DESIGN.md section 7.1).
    """
    from repro.kernels.conv2d.winograd import winograd_accum_bound

    paths = ["im2col"]
    pv = getattr(policy, "value", policy)
    is_int = pv in INT_POLICY_SPECS
    on_tpu = backend == "tpu"
    stem = _stem_cin_threshold(None)
    if path_supports_policy("implicit", policy) and cin >= stem \
            and (is_int or on_tpu):
        paths.append("implicit")
    if on_tpu and path_supports_policy("systolic", policy) \
            and max(kh, kw) <= 7 and stride <= 2 and cin >= stem \
            and cout % 128 == 0:
        paths.append("systolic")
    if is_int and kh == 3 and kw == 3 and stride == 1 \
            and padding == "SAME" and cin >= stem:
        variant, base_bits = INT_POLICY_SPECS[pv]
        if winograd_accum_bound(cin, variant=variant,
                                base_bits=base_bits) < 2**31:
            paths.append("winograd")
    return paths


def _entry_bound(path: str, *, kh, kw, cin, variant, base_bits
                 ) -> Optional[float]:
    """The int32 accumulation bound the chosen engine must stay under."""
    if variant not in _INT_VARIANTS:
        return None
    from repro.kernels.conv2d.conv2d import int_accum_bound
    from repro.kernels.conv2d.winograd import winograd_accum_bound

    if path == "winograd":
        return float(winograd_accum_bound(cin, variant=variant,
                                          base_bits=base_bits))
    return float(int_accum_bound(kh, kw, cin, variant=variant,
                                 base_bits=base_bits))


def _measure_paths(paths, *, kh, kw, stride, h, cin, cout, padding, policy,
                   iters: int, verbose: bool) -> dict:
    """Wall-time each candidate engine via the PUBLIC conv2d entry point.

    The serving call convention (eager wrapper around the jitted core) so
    per-QWeight state -- the winograd mirror's cached transformed operands
    -- engages exactly as it does in `CNNServeEngine`.  Returns
    {path: (us, fused_us, unfused_us)}; paths that fail to run are absent.
    """
    import jax.numpy as jnp
    import numpy as np

    from .substrate import conv2d, quantize_weight
    from .tuning import _first_line, _time_call

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, h, h, cin)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((kh, kw, cin, cout)) * 0.1,
                    jnp.float32)
    b = jnp.asarray(rng.standard_normal((cout,)), jnp.float32)
    spec = policy_int_spec(policy)
    if spec is not None:
        w = quantize_weight(w, base_bits=spec[1])
    out = {}
    for path in paths:
        fused = lambda a, q, p=path: conv2d(
            a, q, stride=stride, padding=padding, policy=policy, path=p,
            bias=b, activation="relu")
        unfused = lambda a, q, p=path: jnp.maximum(conv2d(
            a, q, stride=stride, padding=padding, policy=policy, path=p)
            + b, 0.0)
        try:
            us_f = _time_call(fused, x, w, iters=iters)
            us_u = _time_call(unfused, x, w, iters=iters)
        except Exception as e:  # engine infeasible here: prune, keep going
            # Always said, verbose or not: a kernel the compiler refuses
            # must not vanish silently from an explore run.
            print(f"    {path}: pruned ({_first_line(e)})")
            continue
        if verbose:
            print(f"    {path}: fused {us_f:.1f} us, unfused {us_u:.1f} us")
        out[path] = (min(us_f, us_u), us_f, us_u)
    return out


def explore(cfg, *, model_only: bool = False, backend: Optional[str] = None,
            iters: int = 3, tune_tiles: bool = True, requant: bool = False,
            verbose: bool = False) -> ExecutionPlan:
    """Jointly search path x tile x fusion per conv layer of ``cfg``.

    ``model_only=True`` scores candidates with the roofline cost model
    (compute term at the limb-pass int8 rate vs the modeled HBM traffic
    term -- no execution, deterministic, the CI-committed artifact mode);
    otherwise each surviving candidate engine is wall-timed through the
    public ``conv2d`` on THIS backend and the winning engine's tile
    schedule is refined with the tuner's measured sweep.

    The fusion axis is decided from the model topology
    (:func:`repro.models.cnn.cnn_layer_topology`): an implicit-path layer
    whose next layer is the 2x2/s2 maxpool gets ``fusion="pool"`` (bitwise
    free, ~4x smaller output write -- DESIGN.md 7.7).  With
    ``requant=True`` a pool-fused layer feeding an eligible 3x3/s1
    consumer under an integer policy upgrades to ``"pool_quant"`` -- the
    conv epilogue also emits the NEXT layer's quantized activations.
    ``pool_quant`` is a quantization-recipe change (the consumer reads
    handoff-quantized ints rather than re-quantizing f32), so it is
    opt-in: plans built with ``requant=False`` stay bitwise identical to
    per-call auto dispatch.

    Every conv layer gets an entry -- layers whose candidates all fail to
    score fall back to the heuristic with ``source="default"`` and are
    logged, so a committed plan cannot hide a silent coverage gap (the old
    ``tune_config`` loop skipped un-tunable layers silently).
    """
    from repro.analysis.roofline import conv_layer_roofline
    from repro.models.cnn import cnn_conv_geometries, cnn_layer_topology

    from .tuning import conv_hbm_bytes, resolve_block, tune_layer

    if backend is None:
        import jax
        backend = jax.default_backend()
    variant, base_bits = _policy_variant(cfg.policy)
    is_int = getattr(cfg.policy, "value", cfg.policy) in INT_POLICY_SPECS
    topo = cnn_layer_topology(cfg)
    pool_keys = {geometry_key(**{k: t[k] for k in
                                 ("kh", "kw", "stride", "h", "cin", "cout",
                                  "padding")})
                 for t in topo if t["pool_after"]}
    # (producer key, consumer key) handoff pairs: position i's pool_quant
    # output is position i+1's int input.  Producers precede consumers in
    # geometry order, so `planned` below is filled by the time a consumer
    # key is scored.
    def _tkey(t):
        return geometry_key(**{k: t[k] for k in
                               ("kh", "kw", "stride", "h", "cin", "cout",
                                "padding")})
    handoff_pairs = [( _tkey(topo[i]), _tkey(topo[i + 1]))
                     for i in range(len(topo) - 1)
                     if topo[i]["handoff_next"]]
    producer_keys = {p for p, _ in handoff_pairs}
    fallback = heuristic_plan(cfg, backend=backend)
    entries: List[LayerPlan] = []
    planned: Dict[str, str] = {}
    seen = set()
    for g in cnn_conv_geometries(cfg):
        key = geometry_key(**g)
        if key in seen:
            continue
        seen.add(key)
        shape = {k: g[k] for k in ("kh", "kw", "stride", "h", "cin", "cout")}
        paths = candidate_paths(padding=g["padding"], policy=cfg.policy,
                                backend=backend, **{k: g[k] for k in
                                                    ("kh", "kw", "stride",
                                                     "cin", "cout")})
        if verbose:
            print(f"  {key}: candidates {paths}")
        best_path, est_us, fusion, source = None, None, "bias_relu", "default"
        roof = {p: conv_layer_roofline(p, variant=variant,
                                       base_bits=base_bits, **shape)
                for p in paths}
        if model_only:
            scored = {p: 1e6 * roof[p]["roofline_s"] for p in paths}
            best_path = min(scored, key=scored.get)
            est_us, source = scored[best_path], "model"
        else:
            walls = _measure_paths(paths, padding=g["padding"],
                                   policy=cfg.policy, iters=iters,
                                   verbose=verbose, **shape)
            if walls:
                best_path = min(walls, key=lambda p: walls[p][0])
                est_us, us_f, us_u = walls[best_path]
                fusion = "bias_relu" if us_f <= us_u else "none"
                source = "measured"
        if best_path is None:
            ent = fallback.lookup(**g)
            print(f"[planner] {cfg.name}/{key}: no candidate scored, "
                  f"falling back to heuristic path {ent.path!r} "
                  f"(source=default)")
            best_path, est_us, source = ent.path, None, "default"
        # Fusion axis: topology-driven.  The pooled epilogue is an
        # implicit-engine contract and strictly shrinks the output write,
        # so any pool-followed implicit layer takes it; pool_quant
        # (requant-gated) additionally needs an eligible consumer.
        if best_path == "implicit" and key in pool_keys:
            fusion = "pool"
            if requant and is_int and key in producer_keys:
                fusion = "pool_quant"
        planned[key] = fusion
        handoff_in = any(planned.get(p) == "pool_quant"
                         for p, c in handoff_pairs if c == key)
        block = None
        if best_path in TUNABLE_KINDS:
            if not model_only and tune_tiles:
                block = tuple(tune_layer(best_path, variant=variant,
                                         base_bits=base_bits, iters=iters,
                                         **shape))
            else:
                block = tuple(resolve_block(best_path, variant=variant,
                                            base_bits=base_bits, **shape))
        r = roof.get(best_path)
        roof_us = 1e6 * r["roofline_s"] if r else None
        entries.append(LayerPlan(
            key=key, path=best_path, block=block, fusion=fusion,
            est_us=round(est_us, 3) if est_us is not None else None,
            hbm_bytes=conv_hbm_bytes(best_path, variant=variant,
                                     base_bits=base_bits, fusion=fusion,
                                     handoff_in=handoff_in, **shape),
            roofline_us=round(roof_us, 3) if roof_us is not None else None,
            roofline_frac=(round(roof_us / est_us, 6)
                           if source == "measured" and est_us else None),
            exactness_bound=_entry_bound(best_path, kh=g["kh"], kw=g["kw"],
                                         cin=g["cin"], variant=variant,
                                         base_bits=base_bits),
            source=source))
    return ExecutionPlan(model=cfg.name,
                         policy=getattr(cfg.policy, "value", cfg.policy),
                         backend=backend, entries=tuple(entries))


# ---------------------------------------------------------------------------
# Committed artifacts: benchmarks/tuned/plans/<backend>.json
# ---------------------------------------------------------------------------

def plans_dir() -> pathlib.Path:
    from .tuning import tuned_dir
    return tuned_dir() / PLANS_DIRNAME


def plan_path(backend: Optional[str] = None) -> pathlib.Path:
    if backend is None:
        import jax
        backend = jax.default_backend()
    return plans_dir() / f"{backend}.json"


def save_plans(plans: Iterable[ExecutionPlan],
               path: Optional[os.PathLike] = None) -> pathlib.Path:
    """Write (merge) plans into the backend-stamped artifact file."""
    plans = list(plans)
    if not plans:
        raise ValueError("no plans to save")
    backend = plans[0].backend
    if any(p.backend != backend for p in plans):
        raise ValueError("one artifact file holds ONE backend's plans")
    path = pathlib.Path(path) if path is not None else plan_path(backend)
    payload = {"schema": PLAN_SCHEMA, "backend": backend, "plans": {}}
    if path.exists():
        try:
            old = json.loads(path.read_text())
            if old.get("schema") == PLAN_SCHEMA \
                    and old.get("backend") == backend:
                payload["plans"] = old.get("plans", {})
        except (ValueError, OSError):
            pass
    for p in plans:
        payload["plans"][plan_key(p.model, p.policy)] = p.to_json()
    path.parent.mkdir(parents=True, exist_ok=True)
    import tempfile
    fd, tmp = tempfile.mkstemp(dir=path.parent, suffix=".tmp")
    with os.fdopen(fd, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    _load_plan_file.cache_clear()
    return path


class PlanArtifactError(ValueError):
    """Schema-version or backend-stamp mismatch in a plan artifact."""


@functools.lru_cache(maxsize=None)
def _load_plan_file(path_str: str, mtime: float) -> dict:
    data = json.loads(pathlib.Path(path_str).read_text())
    if data.get("schema") != PLAN_SCHEMA:
        raise PlanArtifactError(
            f"{path_str}: schema {data.get('schema')!r} != {PLAN_SCHEMA!r} "
            "-- regenerate with `python -m repro.core.planner --explore`")
    return data


def load_plans(path, *, backend: Optional[str] = None
               ) -> Dict[str, ExecutionPlan]:
    """All plans in one artifact file, validated against ``backend``.

    Raises :class:`PlanArtifactError` on a schema-version mismatch or when
    the artifact's backend stamp does not match the requested backend --
    a TPU-tuned plan must never silently drive CPU dispatch.
    """
    if backend is None:
        import jax
        backend = jax.default_backend()
    p = pathlib.Path(path)
    data = _load_plan_file(str(p), p.stat().st_mtime)
    if data.get("backend") != backend:
        raise PlanArtifactError(
            f"{p}: plan artifact is stamped backend="
            f"{data.get('backend')!r}, this process runs {backend!r}")
    return {k: ExecutionPlan.from_json(v, backend=backend)
            for k, v in data.get("plans", {}).items()}


def committed_plan(model: str, policy,
                   backend: Optional[str] = None) -> Optional[ExecutionPlan]:
    """The committed artifact's plan for (model, policy, backend), or None."""
    if backend is None:
        import jax
        backend = jax.default_backend()
    path = plan_path(backend)
    if not path.exists():
        return None
    try:
        return load_plans(path, backend=backend).get(plan_key(model, policy))
    except (PlanArtifactError, OSError, ValueError):
        return None


def resolve_plan(cfg, plan: Optional[ExecutionPlan] = None,
                 *, backend: Optional[str] = None) -> ExecutionPlan:
    """The resolution chain: explicit > committed artifact > heuristic.

    The heuristic tail reproduces today's per-call ``select_conv_path``
    dispatch exactly, so a model with no committed plan behaves
    byte-for-byte as before the planner existed.  An explicit plan for a
    different (model, policy) raises -- a plan is not transferable.
    """
    if backend is None:
        import jax
        backend = jax.default_backend()
    if plan is not None:
        pv = getattr(cfg.policy, "value", cfg.policy)
        if (plan.model, plan.policy) != (cfg.name, pv):
            raise ValueError(
                f"plan is for {plan.model}|{plan.policy}, config is "
                f"{cfg.name}|{pv}")
        if plan.backend != backend:
            raise PlanArtifactError(
                f"plan is stamped backend={plan.backend!r}, this process "
                f"runs {backend!r}")
        return plan
    hit = committed_plan(cfg.name, cfg.policy, backend=backend)
    if hit is not None:
        # Committed plans describe the FULL-SIZE model; a reduced twin's
        # geometries simply miss every entry and fall through per layer.
        return hit
    return heuristic_plan(cfg, backend=backend)


# ---------------------------------------------------------------------------
# CI check mode: validate the committed artifacts, no execution.
# ---------------------------------------------------------------------------

def check(paths: Optional[Iterable[os.PathLike]] = None) -> List[str]:
    """Validate committed plan artifacts; returns the violation list.

    Per artifact: schema current, backend stamp == filename.  Per plan:
    the model resolves in the registry, every conv layer geometry of the
    full-size config has an entry (``source`` tags make partial coverage
    an error, not a silent gap), each entry's engine runs the plan's
    policy exactly, its ``fusion`` is one the engine can implement
    (``path_supports_fusion`` -- pool_quant on the systolic path must
    fail) AND one the model topology supports (pool fusions only where a
    maxpool actually follows; pool_quant only under an integer policy
    with an eligible handoff consumer), tile blocks pass the tuner's VMEM
    feasibility model under that fusion, and the exactness bound of the
    chosen engine holds (< 2^31).
    """
    from repro.configs import get_config
    from repro.models.cnn import cnn_conv_geometries, cnn_layer_topology

    from .tuning import feasible

    if paths is None:
        d = plans_dir()
        paths = sorted(d.glob("*.json")) if d.exists() else []
    errors: List[str] = []
    for path in paths:
        path = pathlib.Path(path)
        want_backend = path.stem
        try:
            plans = load_plans(path, backend=want_backend)
        except (PlanArtifactError, ValueError, OSError) as e:
            errors.append(f"{path.name}: {e}")
            continue
        for pkey, plan in plans.items():
            where = f"{path.name}:{pkey}"
            try:
                cfg = get_config(plan.model)
            except KeyError:
                errors.append(f"{where}: unknown model {plan.model!r}")
                continue
            if getattr(cfg, "family", None) != "cnn":
                errors.append(f"{where}: {plan.model!r} is not a CNN -- "
                              "plans cover conv spines only")
                continue
            cfg = cfg.replace(policy=_as_policy(plan.policy, errors, where))
            variant, base_bits = _policy_variant(plan.policy)
            is_int = plan.policy in INT_POLICY_SPECS
            topo = cnn_layer_topology(cfg)
            _gkeys = ("kh", "kw", "stride", "h", "cin", "cout", "padding")
            pool_keys = {geometry_key(**{k: t[k] for k in _gkeys})
                         for t in topo if t["pool_after"]}
            producer_keys = {geometry_key(**{k: t[k] for k in _gkeys})
                             for t in topo if t["handoff_next"]}
            want = {}
            for g in cnn_conv_geometries(cfg):
                want.setdefault(geometry_key(**g), g)
            for key, g in want.items():
                ent = plan.by_key.get(key)
                if ent is None:
                    errors.append(f"{where}: layer {key} has NO entry "
                                  "(silent coverage gap)")
                    continue
                if ent.source not in SOURCES:
                    errors.append(f"{where}/{key}: bad source "
                                  f"{ent.source!r}")
                if not path_supports_policy(ent.path, plan.policy):
                    errors.append(f"{where}/{key}: path {ent.path!r} cannot "
                                  f"run policy {plan.policy!r} exactly")
                    continue
                if ent.fusion not in FUSIONS:
                    errors.append(f"{where}/{key}: unknown fusion "
                                  f"{ent.fusion!r} (expected one of "
                                  f"{list(FUSIONS)})")
                    continue
                if not path_supports_fusion(ent.path, ent.fusion):
                    errors.append(
                        f"{where}/{key}: fusion {ent.fusion!r} is not "
                        f"implementable by path {ent.path!r} (pooled "
                        "epilogue is implicit-engine only)")
                if ent.fusion in ("pool", "pool_quant") \
                        and key not in pool_keys:
                    errors.append(
                        f"{where}/{key}: fusion {ent.fusion!r} but no "
                        f"maxpool follows this geometry in {plan.model}")
                if ent.fusion == "pool_quant":
                    if not is_int:
                        errors.append(
                            f"{where}/{key}: pool_quant needs an integer "
                            f"policy, plan is {plan.policy!r}")
                    elif key not in producer_keys:
                        errors.append(
                            f"{where}/{key}: pool_quant but no eligible "
                            "3x3/s1 handoff consumer follows")
                bound = _entry_bound(ent.path, kh=g["kh"], kw=g["kw"],
                                     cin=g["cin"], variant=variant,
                                     base_bits=base_bits)
                if bound is not None and bound >= 2**31:
                    errors.append(
                        f"{where}/{key}: {ent.path} accumulation bound "
                        f"{bound:.3g} wraps int32")
                if ent.path in TUNABLE_KINDS and ent.block is not None:
                    fus = ent.fusion if ent.fusion in FUSIONS \
                        and path_supports_fusion(ent.path, ent.fusion) \
                        else "bias_relu"
                    ok, why = feasible(
                        ent.path, kh=g["kh"], kw=g["kw"],
                        stride=g["stride"], h=g["h"], cin=g["cin"],
                        cout=g["cout"], variant=variant,
                        base_bits=base_bits, block=tuple(ent.block),
                        fusion=fus)
                    if not ok:
                        errors.append(f"{where}/{key}: block "
                                      f"{list(ent.block)} -- {why}")
            extra = set(plan.by_key) - set(want)
            for key in sorted(extra):
                errors.append(f"{where}: entry {key} matches no conv layer "
                              f"of {plan.model}")
    return errors


def _as_policy(pv: str, errors: list, where: str):
    from repro.core.precision import MatmulPolicy
    try:
        return MatmulPolicy(pv)
    except ValueError:
        errors.append(f"{where}: unknown policy {pv!r}")
        return MatmulPolicy.FP32


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="validate the committed plan artifacts (CI lane)")
    ap.add_argument("--explore", action="store_true",
                    help="run the design-space explorer and persist plans "
                         "for this backend")
    ap.add_argument("--model-only", action="store_true",
                    help="score with the roofline cost model only -- no "
                         "execution (deterministic, the committed-artifact "
                         "mode)")
    ap.add_argument("--models", nargs="*",
                    default=["alexnet", "vgg16", "vgg19"])
    ap.add_argument("--policies", nargs="*",
                    default=["kom_int14", "schoolbook_int16"])
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--requant", action="store_true",
                    help="allow pool_quant fusion (the cross-layer handoff "
                         "quantization recipe -- changes the consumer's "
                         "activation quantization, see DESIGN.md 7.7)")
    ap.add_argument("--out", default=None,
                    help="artifact path (default benchmarks/tuned/plans/"
                         "<backend>.json)")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    if args.check:
        errors = check()
        for e in errors:
            print(f"PLAN VIOLATION: {e}")
        n_files = len(list(plans_dir().glob("*.json"))) \
            if plans_dir().exists() else 0
        print(f"plan artifacts: {n_files} file(s), {len(errors)} "
              "violation(s)")
        return 1 if errors else 0
    if args.explore:
        from repro.configs import get_config
        from repro.core.precision import MatmulPolicy

        plans = []
        for name in args.models:
            for pv in args.policies:
                cfg = get_config(name).replace(policy=MatmulPolicy(pv))
                print(f"[planner] exploring {name}|{pv} "
                      f"({'cost model' if args.model_only else 'measured'})")
                plan = explore(cfg, model_only=args.model_only,
                               iters=args.iters, requant=args.requant,
                               verbose=args.verbose)
                for e in plan.entries:
                    blk = list(e.block) if e.block else "-"
                    print(f"  {e.key}: {e.path} block={blk} "
                          f"fusion={e.fusion} est_us={e.est_us} "
                          f"source={e.source}")
                plans.append(plan)
        out = save_plans(plans, path=args.out)
        print(f"[planner] wrote {out}")
        return 0
    ap.print_help()
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
