"""Serving launcher: batched requests through the serving engines.

Transformer archs go through the continuous-batching decode engine
(:class:`repro.serving.engine.ServeEngine`); the paper's CNN archs
(``alexnet`` / ``vgg16`` / ``vgg19``) go through the SLO-aware image engine
(:class:`repro.serving.cnn_engine.CNNServeEngine`).  Dispatch is on the
registry config's ``family``.  ``--arch a,b,...`` serves several models on
one device pool through the deadline-ordered
:class:`repro.serving.dispatcher.MultiModelDispatcher`; ``--slo`` /
``--deadline-ms`` attach per-request latency budgets (overdue requests are
rejected with typed results, printed in the tally).
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro.configs import get_config, reduced


def _serve_lm(cfg, args) -> int:
    from repro.models import transformer
    from repro.serving.engine import Request, ServeEngine

    if cfg.family in ("encdec",):
        print("engine serves decoder-only families; pick another arch")
        return 2
    params = transformer.init_params(cfg, jax.random.PRNGKey(args.seed))
    engine = ServeEngine(cfg, params, slots=args.slots, max_len=args.max_len,
                         **_resilience_kwargs(args))
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for uid in range(args.requests):
        plen = int(rng.integers(3, 9))
        prompt = rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32)
        engine.submit(Request(uid=uid, prompt=prompt,
                              max_new_tokens=args.max_new,
                              **_request_slo_kwargs(args)))
    done = engine.run()
    dt = time.time() - t0
    n_tok = sum(len(r.out_tokens) for r in done.values())
    for uid in sorted(done):
        r = done[uid]
        print(f"[serve] req {uid}: prompt {list(r.prompt)} -> {r.out_tokens}")
    for uid in sorted(engine.expired):
        print(f"[serve] req {uid}: EXPIRED before admission")
    for uid, flr in sorted(engine.failed.items()):
        print(f"[serve] req {uid}: FAILED after {flr.attempts} attempts "
              f"({flr.error})")
    print(f"[serve] {len(done)} requests ({len(engine.expired)} expired, "
          f"{len(engine.failed)} failed, health {engine.health}), "
          f"{n_tok} tokens in {dt:.1f}s ({n_tok/dt:.1f} tok/s)", flush=True)
    served = len(done) + len(engine.expired) + len(engine.failed)
    return 0 if served == args.requests else 1


def _request_slo_kwargs(args) -> dict:
    """Per-request deadline fields from the CLI flags (engine clock domain)."""
    kw = {}
    if args.slo:
        kw["slo"] = args.slo
    if args.deadline_ms is not None:
        kw["deadline"] = time.monotonic() + args.deadline_ms / 1e3
    return kw


def _resilience_kwargs(args) -> dict:
    """Engine retry/fault-injection kwargs from the validated CLI flags."""
    kw = {}
    if getattr(args, "_retry", None) is not None:
        kw["retry"] = args._retry
    if getattr(args, "_fault_plan", None) is not None:
        kw["faults"] = args._fault_plan
    return kw


def _cnn_plan(cfg, args):
    """The ExecutionPlan the CLI asked for, or None (engine's own chain).

    ``--explore`` runs the design-space explorer for THIS config on THIS
    backend at launch (``--model-only`` scores by the roofline cost model
    instead of wall time); ``--plan PATH`` serves a previously committed
    artifact.  Either way the engine pins every conv layer's engine + tile
    schedule at build.
    """
    if getattr(args, "explore", False):
        from repro.core.planner import explore
        plan = explore(cfg, model_only=getattr(args, "model_only", False),
                       requant=getattr(args, "requant", False))
        for e in plan.entries:
            print(f"[serve] plan {e.key}: {e.path} block="
                  f"{list(e.block) if e.block else '-'} "
                  f"fusion={e.fusion} est_us={e.est_us} ({e.source})")
        return plan
    if getattr(args, "plan", None):
        from repro.core.planner import load_plans, plan_key
        plans = load_plans(args.plan)
        key = plan_key(cfg.name, cfg.policy)
        if key not in plans:
            raise SystemExit(
                f"--plan {args.plan}: no plan for {key!r} "
                f"(has {sorted(plans)})")
        plan = plans[key]
        for e in plan.entries:
            print(f"[serve] plan {e.key}: {e.path} block="
                  f"{list(e.block) if e.block else '-'} "
                  f"fusion={e.fusion} ({e.source})")
        return plan
    return None


def _serve_cnn(cfg, args) -> int:
    from repro.models.cnn import cnn_init
    from repro.serving.cnn_engine import CNNServeEngine, ImageRequest

    params = cnn_init(cfg, jax.random.PRNGKey(args.seed))
    buckets = tuple(int(b) for b in args.buckets.split(","))
    engine = CNNServeEngine(cfg, params, buckets=buckets,
                            plan=_cnn_plan(cfg, args),
                            **_resilience_kwargs(args))
    engine.warmup()  # compile every bucket shape: serving is all cache hits
    rng = np.random.default_rng(args.seed)
    h, c = cfg.img_size, cfg.in_channels
    t0 = time.time()
    for uid in range(args.requests):
        img = rng.standard_normal((h, h, c)).astype(np.float32)
        engine.submit(ImageRequest(uid=uid, image=img,
                                   **_request_slo_kwargs(args)))
    done = engine.run()
    dt = time.time() - t0
    s = engine.stats()
    for uid in sorted(done):
        lat = engine.batcher.queue.latency(uid)
        print(f"[serve] img {uid}: label {done[uid].label} "
              f"({1e3 * lat:.1f} ms)")
    for uid, exp in sorted(engine.expired.items()):
        print(f"[serve] img {uid}: EXPIRED (deadline {exp.deadline:.3f} "
              f"< admission at {exp.expired_at:.3f})")
    for uid, flr in sorted(engine.failed.items()):
        print(f"[serve] img {uid}: FAILED after {flr.attempts} attempts "
              f"({flr.error})")
    print(f"[serve] {cfg.name}/{cfg.policy.value}: "
          f"{s['images_done']} images in {dt:.2f}s wall "
          f"({s['images_per_s']:.1f} img/s batched, "
          f"p95 latency {1e3 * s['latency_p95_s']:.1f} ms, "
          f"padding {100 * s['padding_fraction']:.0f}%, "
          f"expired {s['requests_expired']}, "
          f"failed {s['requests_failed']}, "
          f"retries {s['retries']}, health {s['health']}, "
          f"buckets {s['bucket_counts']})", flush=True)
    served = len(done) + len(engine.expired) + len(engine.failed)
    return 0 if served == args.requests else 1


def _build_engine(cfg, args):
    """One engine on the shared pool, CNN or LM, dispatcher-ready."""
    if cfg.family == "cnn":
        from repro.models.cnn import cnn_init
        from repro.serving.cnn_engine import CNNServeEngine

        params = cnn_init(cfg, jax.random.PRNGKey(args.seed))
        buckets = tuple(int(b) for b in args.buckets.split(","))
        eng = CNNServeEngine(cfg, params, buckets=buckets,
                             plan=_cnn_plan(cfg, args),
                             **_resilience_kwargs(args))
        eng.warmup()
        return eng
    from repro.models import transformer
    from repro.serving.engine import ServeEngine

    params = transformer.init_params(cfg, jax.random.PRNGKey(args.seed))
    return ServeEngine(cfg, params, slots=args.slots, max_len=args.max_len,
                       **_resilience_kwargs(args))


def _serve_multi(cfgs, args) -> int:
    """Several models, one device pool, deadline-ordered time slices."""
    from repro.serving.cnn_engine import ImageRequest
    from repro.serving.dispatcher import MultiModelDispatcher
    from repro.serving.engine import Request

    disp = MultiModelDispatcher()
    for cfg in cfgs:
        disp.register(cfg.name, _build_engine(cfg, args))
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    uid = 0
    for cfg in cfgs:           # interleave submissions round-robin-ish
        for _ in range(args.requests):
            kw = _request_slo_kwargs(args)
            if cfg.family == "cnn":
                h, c = cfg.img_size, cfg.in_channels
                img = rng.standard_normal((h, h, c)).astype(np.float32)
                disp.submit(cfg.name, ImageRequest(uid=uid, image=img, **kw))
            else:
                plen = int(rng.integers(3, 9))
                prompt = rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32)
                disp.submit(cfg.name, Request(uid=uid, prompt=prompt,
                                              max_new_tokens=args.max_new,
                                              **kw))
            uid += 1
    done = disp.run()
    dt = time.time() - t0
    s = disp.stats()
    for name in disp.models:
        eng = disp.engine(name)
        print(f"[serve] {name}: {len(done[name])} done, "
              f"{len(eng.request_queue.expired)} expired, "
              f"{len(getattr(eng.request_queue, 'failed', {}))} failed, "
              f"health {s['health'][name]}, "
              f"{s['per_model'][name]['dispatch_steps']} dispatch steps")
    # the fleet rollup: the conservation triple + resilience counters an
    # operator actually pages on, not just the nested per-model dicts
    print(f"[serve] fleet: {s['requests_done']} done, "
          f"{s['requests_expired']} expired, "
          f"{s['requests_failed']} failed, "
          f"{s['retries']} retries, {s['quarantined']} quarantined "
          f"across {len(cfgs)} models in {dt:.2f}s on one device pool",
          flush=True)
    if s["contained"]:
        for name, err in s["contained"].items():
            print(f"[serve] contained: {name} downed by {err}")
    want = args.requests * len(cfgs)
    served = (s["requests_done"] + s["requests_expired"]
              + s["requests_failed"])
    return 0 if served == want else 1


def _enable_compile_cache() -> None:
    """Once the arguments are valid: a refused command line leaves JAX's
    configuration alone."""
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b",
                    help="one arch, or a comma-separated list served on one "
                         "device pool via the multi-model dispatcher")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the config's small CPU twin instead of the "
                         "published widths")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--buckets", default="1,4,16",
                    help="CNN microbatch bucket sizes (comma-separated)")
    ap.add_argument("--conv-path", default=None,
                    help="CNN conv dispatch: auto | im2col | systolic | "
                         "implicit | winograd")
    ap.add_argument("--plan", default=None,
                    help="serve a committed ExecutionPlan artifact "
                         "(benchmarks/tuned/plans/<backend>.json); pins "
                         "every conv layer's engine + tile schedule")
    ap.add_argument("--explore", action="store_true",
                    help="run the per-layer design-space explorer for this "
                         "config at launch and serve the resulting plan")
    ap.add_argument("--model-only", action="store_true",
                    help="with --explore: score by the roofline cost model "
                         "instead of measuring (no warmup execution)")
    ap.add_argument("--requant", action="store_true",
                    help="with --explore: allow the pool_quant epilogue "
                         "fusion (cross-layer handoff quantization, "
                         "DESIGN.md 7.7)")
    ap.add_argument("--policy", default=None)
    ap.add_argument("--slo", default=None,
                    help="SLO class per request: interactive | standard | "
                         "batch (budget resolved at submit; overdue "
                         "requests are rejected, not served late)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="explicit per-request latency budget in ms "
                         "(wins over --slo's class budget)")
    ap.add_argument("--retries", type=int, default=None, metavar="N",
                    help="retry failed forwards up to N attempts per request "
                         "(exponential backoff, poison-batch bisection, "
                         "typed Failed results); default: no retry, a "
                         "forward failure propagates")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="deterministic fault injection, e.g. "
                         "'transient=0.1,poison=0.02,oom=0.05'; keys: "
                         "transient, poison, oom, latency, latency_s, "
                         "transient_fails (validated here, not mid-run); "
                         "implies --retries 3 unless --retries is given")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # Validate the resilience flags at ARG time: a typo'd fault spec or a
    # zero retry budget should fail here, not after model init/warmup.
    args._retry = None
    if args.retries is not None:
        from repro.serving.scheduler import RetryPolicy
        try:
            args._retry = RetryPolicy(max_attempts=args.retries)
        except ValueError as e:
            ap.error(f"--retries: {e}")
    args._fault_plan = None
    if args.fault_plan is not None:
        from repro.serving.faults import FaultPlan
        try:
            args._fault_plan = FaultPlan.parse(args.fault_plan,
                                               seed=args.seed)
        except ValueError as e:
            ap.error(f"--fault-plan: {e}")
        if args._retry is None:
            from repro.serving.scheduler import RetryPolicy
            args._retry = RetryPolicy()

    cfgs = []
    for arch in args.arch.split(","):
        cfg = get_config(arch.strip())
        if args.reduced:
            cfg = reduced(cfg)
        if args.policy:
            from repro.core.precision import MatmulPolicy
            cfg = cfg.replace(policy=MatmulPolicy(args.policy))
        cfgs.append(cfg)
    if len(cfgs) > 1:
        if any(c.family in ("encdec",) for c in cfgs):
            ap.error("the multi-model pool serves decoder-only LM families")
        _enable_compile_cache()
        return _serve_multi(cfgs, args)
    cfg = cfgs[0]
    if cfg.family == "cnn":
        if args.conv_path:
            cfg = cfg.replace(conv_path=args.conv_path)
        if cfg.conv_path != "auto" and (args.plan or args.explore):
            ap.error(f"--conv-path {cfg.conv_path} pins ONE engine for every "
                     "layer; --plan/--explore choose per layer -- drop one")
        # Fail at arg-parse time, not mid-warmup: an explicit engine choice
        # with a policy it cannot run exactly is the same refusal
        # substrate.conv2d raises (ONE definition, DESIGN.md 7.1).
        from repro.core.substrate import validate_path_policy
        try:
            validate_path_policy(cfg.conv_path, cfg.policy)
        except ValueError as e:
            ap.error(f"--conv-path {e}")
        _enable_compile_cache()
        return _serve_cnn(cfg, args)
    _enable_compile_cache()
    return _serve_lm(cfg, args)


if __name__ == "__main__":
    sys.exit(main())
