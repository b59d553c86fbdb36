"""Plain float32 reference for the benchmark's CNNs, and the seeded weights.

Written with nothing but ``lax.conv_general_dilated``, ``lax.reduce_window``
and ``jnp.dot`` at ``Precision.HIGHEST`` from a ``bench/configs`` layer
list; it imports nothing of the program under test.  The layer semantics
are the ones the configurations state: ReLU after every conv and every FC
but the classifier, 2x2 stride-2 VALID max pools, SAME convs except where
``first_conv_padding`` says otherwise for the stem.

:func:`init_params` makes the float weights the program is served with, on
the device in one jitted call, in the program's parameter layout (a list
with ``{"w", "b"}`` per conv/FC layer and ``{}`` per pool).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_HIGHEST = lax.Precision.HIGHEST


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, also one wider than 32 bits."""
    seed %= 2 ** 64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


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


def logits(params, config: dict, images: np.ndarray,
           block: int = 16) -> np.ndarray:
    """Reference logits of ``images``, ``block`` images at a time."""
    fwd = jax.jit(functools.partial(forward, config=config))
    n = len(images)
    out = []
    for s in range(0, n, block):
        chunk = images[s:s + block]
        if len(chunk) < block:      # one compiled shape for every block
            chunk = np.concatenate(
                [chunk, np.zeros((block - len(chunk),) + chunk.shape[1:],
                                 chunk.dtype)])
        out.append(np.asarray(fwd(params, x=jnp.asarray(chunk))))
    return np.concatenate(out)[:n]
