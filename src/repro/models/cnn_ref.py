"""Plain float32 reference for the paper's CNNs, independent of the substrate.

The serving path (:func:`repro.models.cnn.cnn_forward` through
``CNNServeEngine``) runs every conv and FC on the KOM limb substrate and the
Pallas engines.  This module is what it is checked against: the same
network written with nothing but ``lax.conv_general_dilated``,
``lax.reduce_window`` and ``jnp.dot`` at ``Precision.HIGHEST`` (no bf16
passes on a TPU), reading only the layer spec and the float parameters of
:func:`~repro.models.cnn.cnn_init`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_HIGHEST = lax.Precision.HIGHEST


def cnn_reference(params, cfg, x: jax.Array) -> jax.Array:
    """x: (n, H, W, C) float images -> (n, n_classes) float32 logits.

    Layer semantics of the published networks as this repo states them:
    ReLU after every conv and every FC but the classifier, 2x2/s2 VALID
    max pools, SAME convs except AlexNet's VALID stem.
    """
    x = x.astype(jnp.float32)
    first_conv = True
    for i, spec in enumerate(cfg.layers):
        p = params[i]
        if spec[0] == "conv":
            _, _, _, stride = spec
            padding = ("VALID" if cfg.name == "alexnet" and first_conv
                       else "SAME")
            first_conv = False
            x = lax.conv_general_dilated(
                x, p["w"].astype(jnp.float32), (stride, stride), padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=_HIGHEST)
            x = jax.nn.relu(x + p["b"])
        elif spec[0] == "pool":
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
        else:
            x = x.reshape(x.shape[0], -1)
            x = jnp.dot(x, p["w"].astype(jnp.float32),
                        precision=_HIGHEST) + p["b"]
            if i != len(cfg.layers) - 1:
                x = jax.nn.relu(x)
    return x
