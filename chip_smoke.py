"""Chip smoke run: serve AlexNet, VGG16 and VGG19 at published widths on a TPU.

The quickest proof that the serving path a user calls still starts on the
chip.  For each network, under ``kom_int14``, with random weights from
``--seed``:

  * build ``CNNServeEngine`` with the default plan resolution and print
    each conv layer's planned engine;
  * warm up buckets (1, 4) and print the compile seconds;
  * serve 8 seeded requests (no retry policy, no faults) and require every
    request done, the engine healthy and no degrade step taken;
  * count ``tpu_custom_call`` in the compiled forward: at least one per
    conv layer the plan puts on a Pallas engine;
  * compare the served logits with a plain float32 reference
    (``repro.models.cnn_ref``) within :data:`REL_TOL`, and report top-1
    agreement;
  * serve the same requests through the materialized im2col fallback plan
    and report whether the planned path equals it bitwise (a finding
    either way, not a check).

``--chips 4`` runs only the data-parallel phase instead: VGG16 over a
4-way ``data`` mesh, buckets (4, 16), compared bitwise with a one-device
engine (the batch-invariance contract).

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips

Exits non-zero, printing no result, unless JAX finds a TPU.  The last line
of a passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

MODELS = ("alexnet", "vgg16", "vgg19")
N_REQUESTS = 8

#: Largest max|served - reference| / max|reference| accepted.  int14
#: weights and activations put ~2^-13 relative error on each layer; over
#: AlexNet's 8 and VGG19's 19 layers on random weights that stays near
#: 1e-3 (examples/cnn_kom.py reports ~1e-3 for AlexNet on CPU).  1e-2
#: leaves a 10x margin while a wrong kernel is off by O(1).
REL_TOL = 1e-2

PALLAS_PATHS = ("systolic", "implicit", "winograd")


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def serve(eng, images: np.ndarray) -> tuple[np.ndarray, float]:
    """Submit every image, drain the engine; (logits in uid order, wall s)."""
    from repro.serving.cnn_engine import ImageRequest

    t0 = time.perf_counter()
    for uid, img in enumerate(images):
        eng.submit(ImageRequest(uid=uid, image=img))
    done = eng.run()
    wall = time.perf_counter() - t0
    check(len(done) == len(images),
          f"{eng.cfg.name}: {len(done)} of {len(images)} requests done")
    check(eng.health == "healthy" and not eng.degrade_log,
          f"{eng.cfg.name}: health {eng.health}, degrade_log "
          f"{eng.degrade_log}")
    return np.stack([done[u].logits for u in range(len(images))]), wall


def smoke_model(name: str, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.planner import materialized_fallback_plan
    from repro.core.precision import MatmulPolicy
    from repro.models.cnn import cnn_init, cnn_layer_topology
    from repro.models.cnn_ref import cnn_reference
    from repro.serving.cnn_engine import CNNServeEngine

    cfg = get_config(name, policy=MatmulPolicy.KOM_INT14)
    params = cnn_init(cfg, jax.random.PRNGKey(seed))
    eng = CNNServeEngine(cfg, params, buckets=(1, 4))
    paths = []
    for g in cnn_layer_topology(cfg):
        ent = eng.plan.lookup(**{k: g[k] for k in (
            "kh", "kw", "stride", "h", "cin", "cout", "padding")})
        paths.append(ent.path if ent is not None else "auto")
    print(f"[{name}] plan ({eng.plan.backend}): "
          + " ".join(f"conv{i + 1}={p}" for i, p in enumerate(paths)),
          flush=True)

    t0 = time.perf_counter()
    eng.warmup()
    compile_s = time.perf_counter() - t0
    print(f"[{name}] warmup of buckets {eng.buckets}: {compile_s:.1f} s",
          flush=True)

    h = cfg.img_size
    rng = np.random.default_rng(seed)
    images = rng.standard_normal((N_REQUESTS, h, h, cfg.in_channels),
                                 dtype=np.float32)
    logits, serve_s = serve(eng, images)
    check(bool(np.isfinite(logits).all()), f"{name}: non-finite logits")

    hlo = eng._forward.lower(
        eng.params, jnp.zeros((4, h, h, cfg.in_channels), jnp.float32)
    ).compile().as_text()
    n_kernels = hlo.count("tpu_custom_call")
    n_pallas = sum(p in PALLAS_PATHS for p in paths)
    check(n_kernels >= n_pallas,
          f"{name}: {n_kernels} tpu_custom_call for {n_pallas} Pallas layers")

    ref = np.asarray(jax.jit(lambda p, x: cnn_reference(p, cfg, x))(
        params, jnp.asarray(images)))
    rel = float(np.abs(logits - ref).max() / np.abs(ref).max())
    top1 = float((logits.argmax(-1) == ref.argmax(-1)).mean())
    check(rel <= REL_TOL, f"{name}: max rel err {rel} > {REL_TOL}")

    fb = CNNServeEngine(cfg, params, buckets=(1, 4),
                        plan=materialized_fallback_plan(eng.plan))
    fb.warmup()
    fb_logits, _ = serve(fb, images)
    bitwise = bool(np.array_equal(logits, fb_logits))
    row = {
        "model": name, "policy": "kom_int14", "compile_s": compile_s,
        "serve_s": serve_s, "requests": N_REQUESTS,
        "tpu_custom_call": n_kernels, "pallas_layers": n_pallas,
        "max_rel_err": rel, "rel_tol": REL_TOL, "top1_agreement": top1,
        "planned_eq_im2col_bitwise": bitwise,
        "planned_vs_im2col_max_abs": float(np.abs(logits - fb_logits).max()),
    }
    print(f"[{name}] {json.dumps(row)}", flush=True)
    return row


def smoke_data_parallel(seed: int) -> dict:
    """VGG16 over a 4-way data mesh vs one device: logits bitwise equal."""
    import jax

    from repro.configs import get_config
    from repro.core.precision import MatmulPolicy
    from repro.models.cnn import cnn_init
    from repro.serving.cnn_engine import CNNServeEngine

    check(len(jax.devices()) >= 4,
          f"--chips 4 needs 4 devices, JAX sees {len(jax.devices())}")
    cfg = get_config("vgg16", policy=MatmulPolicy.KOM_INT14)
    params = cnn_init(cfg, jax.random.PRNGKey(seed))
    mesh = jax.make_mesh((4,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(seed)
    images = rng.standard_normal((16, cfg.img_size, cfg.img_size, 3),
                                 dtype=np.float32)
    out = {}
    for label, kw in (("dp4", {"mesh": mesh}), ("one_device", {})):
        eng = CNNServeEngine(cfg, params, buckets=(4, 16), **kw)
        t0 = time.perf_counter()
        eng.warmup()
        compile_s = time.perf_counter() - t0
        logits, serve_s = serve(eng, images)
        check(bool(np.isfinite(logits).all()), f"{label}: non-finite logits")
        out[label] = logits
        print(f"[vgg16/{label}] data_parallel={eng.dp} warmup {compile_s:.1f} "
              f"s, served {len(images)} in {serve_s:.2f} s", flush=True)
    bitwise = bool(np.array_equal(out["dp4"], out["one_device"]))
    row = {"model": "vgg16", "policy": "kom_int14", "mesh": {"data": 4},
           "buckets": [4, 16], "requests": len(images),
           "dp4_eq_one_device_bitwise": bitwise,
           "max_abs_diff": float(np.abs(out["dp4"] - out["one_device"]).max())}
    print(f"[vgg16/dp4] {json.dumps(row)}", flush=True)
    check(bitwise, "dp=4 logits differ from one-device logits")
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-way data-parallel phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    print(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {enable_compile_cache()}", flush=True)
    try:
        if args.chips == 4:
            smoke_data_parallel(args.seed)
        else:
            for name in MODELS:
                smoke_model(name, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
