"""A configuration's family: the module that knows the shape of its network.

A configuration file may carry ``"family": "<name>"``; without the key it is
``"chain"``.  The family is the module ``bench/families/<name>.py``, found by
that name as a metric's reader is, so a network of a new shape is a new
configuration file and a new family file, with no edit to the harness.  A
family module provides:

- ``init_params(config, seed)``: the float weights on the device, in the
  program's parameter layout, made in one jitted call from the seed;
- ``forward(params, config, x)``: the plain float32 reference at
  ``Precision.HIGHEST``, ``(n, H, W, C)`` images to ``(n, n_classes)``
  logits, importing nothing of the program;
- ``layers(config)``: a ``work.Layer`` per conv and FC layer, in order;
- ``program_config(config, policy)``: the program's config, checked against
  the file;
- ``pallas_layers(engine, config)``: the ``work.Layer``s that the engine's
  resolved plan puts on a Pallas engine;
- ``tiny(config)``: the same network cut to a CPU-test size.

The keys every configuration keeps are ``name``, ``source``, ``model``,
``policy``, ``control_policy``, ``img_size``, ``in_channels``,
``n_classes``, ``reduced``, ``assumed`` and ``limit``; the rest are the
family's.
"""
from __future__ import annotations

import functools
import importlib.util
import pathlib

DIR = pathlib.Path(__file__).resolve().parent / "families"
DEFAULT = "chain"


def known() -> list[str]:
    return sorted(p.stem for p in DIR.glob("*.py"))


def load(name: str):
    """The family module ``name``; exits listing the known ones if none."""
    if name not in known():
        raise SystemExit(f"unknown family {name!r}; known: {known()}")
    return _load(DIR / f"{name}.py")


def of(config: dict):
    """The family module of configuration ``config``."""
    return load(config.get("family", DEFAULT))


@functools.cache
def _load(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_family_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
