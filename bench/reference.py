"""The plain reference's model-independent part: seeds and blocked logits.

The reference forward pass and the seeded weights of a configuration are
its family's (``bench/families/<family>.py``, found by ``bench/family.py``);
they import nothing of the program under test.  Here: the PRNG key of a
seed, and the jitted, blocked run of a family's ``forward`` over images.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, also one wider than 32 bits."""
    seed %= 2 ** 64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def logits(forward, params, config: dict, images: np.ndarray,
           block: int = 16) -> np.ndarray:
    """Reference logits of ``images`` by ``forward(params, config, x)``
    (a family's), ``block`` images at a time."""
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
