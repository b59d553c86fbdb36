"""The chain family: a plain sequence of convs, pools and FC layers.

The configuration's ``layers`` is a list of ``["conv", k, cout, stride]``,
``["pool"]`` and ``["fc", n]``, run in order.  The semantics the
configurations state: ReLU after every conv and every FC but the
classifier, 2x2 stride-2 VALID max pools, SAME convs except where
``first_conv_padding`` says otherwise for the stem.  The program's
parameter layout is a list with ``{"w", "b"}`` per conv/FC layer and ``{}``
per pool.

The reference is written with nothing but ``lax.conv_general_dilated``,
``lax.reduce_window`` and ``jnp.dot`` at ``Precision.HIGHEST``; it imports
nothing of the program under test.  See ``bench/family.py`` for the
interface.
"""
from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import work
from reference import seed_key

_HIGHEST = lax.Precision.HIGHEST


def _shapes(config: dict) -> list:
    h, c = config["img_size"], config["in_channels"]
    first_conv, feat, out = True, None, []
    for spec in config["layers"]:
        if spec[0] == "conv":
            _, k, cout, stride = spec
            pad = config["first_conv_padding"] if first_conv else "SAME"
            first_conv = False
            out.append(((k, k, c, cout), k * k * c))
            h = (h - k) // stride + 1 if pad == "VALID" else -(-h // stride)
            c = cout
        elif spec[0] == "pool":
            out.append(None)
            h //= 2
        else:
            fin = feat if feat is not None else h * h * c
            out.append(((fin, spec[1]), fin))
            feat = spec[1]
    return out


def init_params(config: dict, seed: int) -> list:
    """He-scaled normal weights, N(0, 0.01^2) biases, float32, on device."""
    shapes = _shapes(config)

    def make(key):
        params = []
        for i, sh in enumerate(shapes):
            if sh is None:
                params.append({})
                continue
            wshape, fan = sh
            kw, kb = jax.random.split(jax.random.fold_in(key, i))
            params.append({
                "w": jax.random.normal(kw, wshape, jnp.float32)
                * np.float32((2.0 / fan) ** 0.5),
                "b": 0.01 * jax.random.normal(kb, (wshape[-1],), jnp.float32),
            })
        return params

    return jax.jit(make)(seed_key(seed))


def forward(params, config: dict, x: jax.Array) -> jax.Array:
    """x: (n, H, W, C) float images -> (n, n_classes) float32 logits."""
    x = x.astype(jnp.float32)
    first_conv = True
    n_layers = len(config["layers"])
    for i, spec in enumerate(config["layers"]):
        p = params[i]
        if spec[0] == "conv":
            stride = spec[3]
            pad = config["first_conv_padding"] if first_conv else "SAME"
            first_conv = False
            x = lax.conv_general_dilated(
                x, p["w"], (stride, stride), pad,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=_HIGHEST)
            x = jax.nn.relu(x + p["b"])
        elif spec[0] == "pool":
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
        else:
            x = x.reshape(x.shape[0], -1)
            x = jnp.dot(x, p["w"], precision=_HIGHEST) + p["b"]
            if i != n_layers - 1:
                x = jax.nn.relu(x)
    return x


def layers(config: dict) -> list[work.Layer]:
    """Conv and FC layers of ``config``, in order; ``index`` is the
    position in its layer list."""
    h, c = config["img_size"], config["in_channels"]
    first_conv = True
    feat = None
    out: list[work.Layer] = []
    for i, spec in enumerate(config["layers"]):
        if spec[0] == "conv":
            _, k, cout, stride = spec
            pad = config["first_conv_padding"] if first_conv else "SAME"
            first_conv = False
            oh = (h - k) // stride + 1 if pad == "VALID" else -(-h // stride)
            out.append(work.Layer(work.Kind.CONV, i,
                                  oh * oh * k * k * c * cout,
                                  k * k * c * cout * work.STORE_BYTES,
                                  (h * h * c + oh * oh * cout)
                                  * work.STORE_BYTES))
            h, c = oh, cout
        elif spec[0] == "pool":
            h //= 2
        else:
            fin = feat if feat is not None else h * h * c
            n = spec[1]
            out.append(work.Layer(work.Kind.FC, i, fin * n,
                                  fin * n * work.STORE_BYTES,
                                  (fin + n) * work.STORE_BYTES))
            feat = n
    return out


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


def pallas_layers(engine, config: dict) -> list[work.Layer]:
    """The conv layers that the engine's resolved plan puts on Pallas."""
    from repro.models.cnn import cnn_conv_geometries

    convs = [l for l in layers(config) if l.kind is work.Kind.CONV]
    geoms = cnn_conv_geometries(engine.cfg)
    if len(convs) != len(geoms):
        raise ValueError(f"{config['name']} has {len(convs)} conv layers, "
                         f"the program's {engine.cfg.name} {len(geoms)}")
    out = []
    for layer, g in zip(convs, geoms):
        ent = engine.plan.lookup(**g) if engine.plan is not None else None
        path = ent.path if ent is not None else engine.cfg.conv_path
        if path in ("systolic", "implicit", "winograd"):
            out.append(layer)
    return out


def tiny(config: dict) -> dict:
    """``config`` cut to a CPU-test size: the same layer kinds, kernels,
    strides and pools; channels capped at 16, FC widths at 32, 16 classes,
    32 px, or 67 px where the stem is VALID (AlexNet's 11x11/s4)."""
    out = copy.deepcopy(config)
    cut = []
    for s in config["layers"]:
        if s[0] == "conv":
            cut.append(["conv", s[1], min(s[2], 16), s[3]])
        elif s[0] == "fc":
            cut.append(["fc", min(s[1], 32)])
        else:
            cut.append(s)
    cut[-1] = ["fc", 16]
    out.update(layers=cut, n_classes=16,
               img_size=67 if config["first_conv_padding"] == "VALID" else 32)
    return out
