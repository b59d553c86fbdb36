"""Where a cell's time goes, from the program's own spans: one run of one cell.

    python3 bench/phases.py --workload alexnet.offline --seed 7 --seconds 40 --profile 1

Runs the cell as ``bench/run.py`` does (the same configuration, weights,
warm-up, traffic and window, but no correctness check), with a
``repro.serving.spans.Recorder`` installed before set-up, so the engine's
set-up phases, each step's phases, compiles and garbage collections are
recorded beside the harness's own spans.  Prints one JSON line: the
end-to-end numbers of the window,
``host_ms_per_step`` (``bench/metrics/host_ms_per_step.*``), each phase's
mean ms per step, the set-up phases, the compiles and collections inside
the window, and with ``--profile 1`` the device trace reduced both ways:
``trace_reduce`` (what ``bench/run.py --trace 1`` reports) and
``trace_phases`` (idle gaps by phase, device time by layer, the clock's
slack).  ``--profile 0`` measures what the recorder alone costs.
``--save DIR`` keeps the spans and the gzipped trace (a test fixture).
Needs a program with ``repro.serving.spans``.
"""
from __future__ import annotations

import run  # noqa: I001  (first: it sets the compile cache and the path)

import argparse
import gzip
import json
import pathlib
import shutil
import sys
import time

import loadgen
import trace_phases
import trace_reduce

#: Spans of engine set-up, reported by name with their durations.
SETUP = ("engine.quantize_weights", "engine.plan", "engine.jit", "engine.warmup")


def _window_counts(rec, w0: int, w1: int) -> dict:
    out = {"jax.compile": 0, "python.gc": 0, "python.gc_ms": 0.0}
    for s in rec.spans:
        if s.name in ("jax.compile", "python.gc") and s.t1 is not None \
                and w0 <= s.t0 < w1:
            out[s.name] += 1
            if s.name == "python.gc":
                out["python.gc_ms"] += 1e-6 * (s.t1 - s.t0)
    return out


def _setup_phases(rec) -> dict:
    out: dict = {}
    for s in rec.spans:
        if s.t1 is None:
            continue
        if (s.parent is None and s.name in SETUP) \
                or s.name.startswith("engine.warmup.b"):
            out[s.name] = round(1e-9 * (s.t1 - s.t0), 6)
    first_window = next((s.t0 for s in rec.spans if s.name == "window"), None)
    out["jax.compile"] = sum(1 for s in rec.spans if s.name == "jax.compile"
                             and (first_window is None or s.t0 < first_window))
    return out


def run_phases(spec: dict, name: str, seed: int, seconds: float, profile: bool,
               *, require_chip: bool = True,
               save: pathlib.Path | None = None) -> dict:
    from repro.serving import spans
    from repro.serving.cnn_engine import ImageRequest

    c = run.cell(spec, name)
    run.devices_for(int(c["workload"]["chips"]), require_chip)
    rec = spans.install(spans.Recorder())
    try:
        ctx = run.setup(c, seed)
        setup_s = time.monotonic() - run.T_PROCESS
        hit_setup = rec.counts["jax.cache_hit"]
        engine = ctx["engine"]
        trace_dir = run.CACHE_DIR / "phases" / name
        if profile:
            import jax

            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 0      # as bench/run.py: device only
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        with spans.span("window"):
            w = loadgen.drive(engine, c["traffic"], ctx["images"], seconds, seed,
                              request_cls=ImageRequest, keep=lambda uid: False,
                              span=spans.span)
        if profile:
            jax.profiler.stop_trace()
    finally:
        spans.uninstall()
    win = next(s for s in reversed(rec.spans) if s.name == "window")
    record = {"window_s": w.t_end - w.t0, "images": loadgen.images_done(w),
              "latencies_ms": loadgen.latencies_ms(w),
              "program_spans": rec.spans}
    out = {"workload": name, "seed": seed, "profile": bool(profile),
           "setup_s": setup_s, "steps": w.steps,
           "metrics": {}, "phases_ms_per_step":
               trace_phases.phase_ms_per_step(rec.spans),
           "setup_phases_s": {**_setup_phases(rec),
                              "jax.cache_hit": hit_setup},
           "window": _window_counts(rec, win.t0, win.t1)}
    host = f"host_ms_per_step.{c['workload']['traffic']}"
    names = [m["name"] for m in c["end_to_end"] if m["name"] != "setup_s"]
    if (run.BENCH / "metrics" / f"{host}.py").is_file():
        names.append(host)
    for metric in names:
        v = run.reader(metric)(record)
        if v is not None:
            out["metrics"][metric] = v
    if profile:
        xplane = trace_reduce.find_xplane(str(trace_dir))
        prof = trace_reduce.load(xplane)
        harness = [(s.name, s.t0, s.t1) for s in rec.spans
                   if s.name in trace_reduce.HOST_SPANS + (trace_reduce.WINDOW_SPAN,)]
        out["trace_reduce"] = trace_reduce.reduce(prof, harness, w.forwards)
        out["trace_phases"] = trace_phases.reduce(
            prof, rec.spans, trace_phases.op_scopes(xplane))
        if save is not None:
            save.mkdir(parents=True, exist_ok=True)
            stem = name.replace(".", "_")
            with open(xplane, "rb") as src, \
                    gzip.open(save / f"{stem}.phases.xplane.pb.gz", "wb") as dst:
                shutil.copyfileobj(src, dst)
            (save / f"{stem}.phases.spans.json").write_text(json.dumps({
                "spans": rec.spans, "forwards": w.forwards}))
        shutil.rmtree(trace_dir, ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=1)
    ap.add_argument("--save", type=pathlib.Path, default=None)
    args = ap.parse_args(argv)
    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    try:
        out = run_phases(spec, args.workload, args.seed, args.seconds,
                         bool(args.profile), save=args.save)
    except run.NoChip as e:
        print(f"phases: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
