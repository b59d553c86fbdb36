"""Benchmark harness: one cell of ``BENCHMARK.json``, one process, one run.

    python bench/run.py --workload vgg16.offline --seed 7 --seconds 20 --trace 0

Builds ``CNNServeEngine`` for the cell's configuration (``bench/configs``)
with float weights made on the device from ``--seed``, warms up the
buckets its traffic (``bench/traffic``) uses, drives the engine through
``submit``/``step`` for ``--seconds`` on the real clock, then checks a
seeded sample of the served logits against the plain float32 reference
(``bench/reference.py``).  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (end-to-end with
``--trace 0``, per-layer with ``--trace 1``), ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``: each number compared beside its
limit.  Each metric is computed by its own reader, ``bench/metrics/<name>.py``.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: Fixed, inside the checkout: the path is part of the compile cache's key.
CACHE_DIR = ROOT / ".bench_cache"
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR / "jax")
# libtpu logs to a fixed /tmp path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

import loadgen  # noqa: E402
import work  # noqa: E402

#: Distinct images a run cycles through, by request uid.
POOL = 128
#: Besides the first request of every pool image, keep the logits of one
#: request in this many (the offset drawn from the seed) for the check.
KEEP_EVERY = 61


class NoChip(RuntimeError):
    pass


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(spec: dict, name: str) -> dict:
    """Everything a run of workload ``name`` needs, found by name."""
    wl = {w["name"]: w for w in spec["workloads"]}
    if name not in wl:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(wl)}")
    return assemble(spec, wl[name])


def assemble(spec: dict, w: dict) -> dict:
    """The cell of workload entry ``w``: its configuration, traffic, metrics."""
    name = w["name"]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = load_json(ROOT / cfg_entry["file"])
    traffic = loadgen.check(load_json(BENCH / "traffic" / f"{w['traffic']}.json"))

    def reports(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    e2e = [m for m in spec["end_to_end"] if reports(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return {"workload": w, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": per_layer}


def reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; bench/peaks.json "
                       f"has {sorted(table)}")
    return table[kind]


def devices_for(chips: int, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"need {chips} TPU chip(s); JAX sees {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return devs[:chips]


def program_config(config: dict, policy: str):
    """The program's config for ``config``, checked against the file."""
    from repro.configs import get_config
    from repro.core.precision import MatmulPolicy
    from repro.models.cnn import cnn_conv_geometries

    cfg = get_config(config["model"], policy=MatmulPolicy(policy),
                     img_size=config["img_size"],
                     in_channels=config["in_channels"],
                     n_classes=config["n_classes"],
                     layers=tuple(tuple(s) for s in config["layers"]))
    pad = cnn_conv_geometries(cfg)[0]["padding"]
    if pad != config["first_conv_padding"]:
        raise ValueError(f"the program pads {config['model']}'s stem {pad}, "
                         f"the configuration says "
                         f"{config['first_conv_padding']}")
    return cfg


def pallas_layers(engine, config: dict) -> list:
    """The conv layers that the engine's resolved plan puts on Pallas."""
    from repro.models.cnn import cnn_conv_geometries

    convs = [l for l in work.layers(config) if l.kind == "conv"]
    out = []
    for layer, g in zip(convs, cnn_conv_geometries(engine.cfg)):
        ent = engine.plan.lookup(**g) if engine.plan is not None else None
        path = ent.path if ent is not None else engine.cfg.conv_path
        if path in ("systolic", "implicit", "winograd"):
            out.append(layer)
    return out


def setup(c: dict, seed: int, *, policy: str | None = None):
    """Weights, engine and image pool; every bucket warmed up."""
    import jax

    import reference
    from repro.serving.cnn_engine import CNNServeEngine

    # every program the run compiles goes to the cache, however quick
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    config = c["config"]
    cfg = program_config(config, policy or config["policy"])
    params = reference.init_params(config, seed)
    jax.block_until_ready(params)
    engine = CNNServeEngine(cfg, params, buckets=tuple(c["traffic"]["buckets"]))
    engine.warmup()
    h, ch = config["img_size"], config["in_channels"]
    rng = np.random.default_rng([seed % 2 ** 64, 1])
    images = rng.standard_normal((POOL, h, h, ch), dtype=np.float32)
    return {"params": params, "engine": engine, "images": images}


def counters(engine) -> dict:
    b = engine.batcher
    return {"steps": b.steps, "real_rows": b.real_rows,
            "padded_rows": b.padded_rows, "bucket_counts": dict(b.bucket_counts)}


def measure(c: dict, ctx: dict, seed: int, seconds: float, *,
            trace_dir: pathlib.Path | None = None) -> dict:
    """One measured window; the record that the metric readers read."""
    import jax

    engine = ctx["engine"]
    offset = seed % KEEP_EVERY
    keep = lambda uid: uid < POOL or uid % KEEP_EVERY == offset
    spans: list = []
    if trace_dir is not None:
        @contextlib.contextmanager
        def span(name):
            t0 = time.monotonic_ns()
            try:
                yield
            finally:
                spans.append((name, t0, time.monotonic_ns()))

        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        # No host tracer: the runtime writes a host event for every chunk of
        # an input's layout transpose (thousands per step), which slowed the
        # host threefold.  The harness keeps its own spans instead and
        # trace_reduce aligns them to the device clock.
        opts.host_tracer_level = 0
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    else:
        span = lambda name: contextlib.nullcontext()
    from repro.serving.cnn_engine import ImageRequest

    before = counters(engine)
    with span("window"):
        w = loadgen.drive(engine, c["traffic"], ctx["images"], seconds, seed,
                          request_cls=ImageRequest, keep=keep, span=span)
    after = counters(engine)
    reduced = None
    if trace_dir is not None:
        jax.profiler.stop_trace()
        import trace_reduce

        reduced = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_xplane(str(trace_dir))),
            spans, w.forwards)
        shutil.rmtree(trace_dir, ignore_errors=True)
    delta = {k: after[k] - before[k] for k in ("steps", "real_rows", "padded_rows")}
    delta["bucket_steps"] = {b: after["bucket_counts"].get(b, 0)
                             - before["bucket_counts"].get(b, 0)
                             for b in after["bucket_counts"]}
    return {"window": w, "counters": delta, "trace": reduced,
            "window_s": w.t_end - w.t0, "images": loadgen.images_done(w),
            "latencies_ms": loadgen.latencies_ms(w),
            "attempted": loadgen.attempted(w), "failed": len(w.failed),
            "lost": loadgen.outstanding(w)}


def compare(config: dict, params, images: np.ndarray, kept: dict) -> float:
    """Worst max|served - reference| / max|reference| over the kept rows."""
    import reference

    if not kept:
        return float("inf")
    uids = sorted(kept)
    idx = sorted({u % len(images) for u in uids})
    ref = dict(zip(idx, reference.logits(params, config, images[idx])))
    worst = 0.0
    for u in uids:
        r = ref[u % len(images)]
        err = float(np.abs(kept[u] - r).max() / np.abs(r).max())
        if not np.isfinite(err):
            return float("inf")
        worst = max(worst, err)
    return worst


def run_cell(spec: dict, name: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True,
             policy: str | None = None) -> tuple[dict, dict]:
    """One run of one cell: the result line, and what else the run saw."""
    c = cell(spec, name)
    devices = devices_for(int(c["workload"]["chips"]), require_chip)
    dev = devices[0]
    ctx = setup(c, seed, policy=policy)
    setup_s = time.monotonic() - T_PROCESS
    pallas = pallas_layers(ctx["engine"], c["config"])
    trace_dir = CACHE_DIR / "trace" / name if trace else None
    m = measure(c, ctx, seed, seconds, trace_dir=trace_dir)
    peak = _memory_peak(devices)
    kept = m["window"].kept
    degraded = len(ctx["engine"].degrade_log)
    # free the program's state before the reference runs on the chip
    del ctx["engine"]
    gc.collect()
    err = compare(c["config"], ctx["params"], ctx["images"], kept)
    limit = c["config"]["limit"]["max_rel_err"]
    checks = {
        # a reading that is no number (NaN logits, nothing kept) prints as null
        "max_rel_err": {"value": err if np.isfinite(err) else None,
                        "limit": limit},
        "lost_requests": {"value": m["lost"], "limit": 0},
        "degrade_steps": {"value": degraded, "limit": 0},
    }
    correct = (limit is not None and err <= limit and m["lost"] == 0
               and degraded == 0)
    rec = {**m, "setup_s": setup_s, "config": c["config"],
           "traffic": c["traffic"], "pallas_layers": pallas,
           "ops_per_image": work.ops_per_image(c["config"]),
           "peaks": peaks_for(dev.device_kind) if require_chip
           else _peaks_or_none(dev.device_kind)}
    metrics = {}
    for mt in (c["per_layer"] if trace else c["end_to_end"]):
        v = reader(mt["name"])(rec)
        if v is not None:
            metrics[mt["name"]] = {"value": v, "unit": mt["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": m["attempted"],
            "failed": m["failed"], "metrics": metrics, "device": device}
    if trace:
        t = m["trace"]
        device["busy_s"] = t["busy_s"]
        device["window_s"] = t["window_s"]
        line["breakdown"] = {"device_ops": t["device_ops"],
                             "idle_gaps": t["idle_gaps"]}
    line["checks"] = checks
    info = {"policy": policy or c["config"]["policy"],
            "window_s": m["window_s"], "images": m["images"],
            "steps": m["counters"]["steps"],
            "bucket_steps": m["counters"]["bucket_steps"],
            "kept": len(kept), "late_s": m["window"].late_s,
            "pending_peak": m["window"].pending_peak, "setup_s": setup_s}
    return line, info


def _peaks_or_none(kind: str):
    try:
        return peaks_for(kind)
    except KeyError:
        return None


def _memory_peak(devices) -> int | None:
    """Peak bytes in use on the fullest chip; None where none is reported."""
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [int(s["peak_bytes_in_use"]) for s in stats
             if "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


def print_result(line: dict, info: dict) -> None:
    print("run " + json.dumps(info), file=sys.stderr)
    for name, chk in line["checks"].items():
        print(f"check {name} {chk['value']!r} limit {chk['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_json(ROOT / "BENCHMARK.json")
    try:
        line, info = run_cell(spec, args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print_result(line, info)
    return 0


if __name__ == "__main__":
    sys.exit(main())
