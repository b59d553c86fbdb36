"""The plain float32 CNN reference (models/cnn_ref.py) on CPU.

It must agree with the substrate forward under fp32 (the same network,
written twice), and the KOM-int14 serving forward must sit within the
quantization error of it -- the comparison the chip smoke run makes at
published widths.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.precision import MatmulPolicy
from repro.models.cnn import cnn_forward, cnn_init, cnn_quantize_params
from repro.models.cnn_ref import cnn_reference

MODELS = ("alexnet", "vgg16", "vgg19")


def _case(name, policy):
    cfg = reduced(get_config(name)).replace(policy=policy)
    params = cnn_init(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (2, cfg.img_size, cfg.img_size, 3))
    return cfg, params, x


def _rel(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.mark.parametrize("name", MODELS)
def test_reference_matches_fp32_forward(name):
    """Same network, two writings: f32 rounding differences only."""
    cfg, params, x = _case(name, MatmulPolicy.FP32)
    assert _rel(cnn_forward(params, cfg, x),
                cnn_reference(params, cfg, x)) < 1e-5


@pytest.mark.parametrize("name", MODELS)
def test_kom_int14_forward_within_quantization_error(name):
    """int14 weights and activations cost ~2^-13 relative per layer; 1e-2
    bounds that over the whole network while a wrong engine is O(1) off."""
    cfg, params, x = _case(name, MatmulPolicy.KOM_INT14)
    got = cnn_forward(cnn_quantize_params(params, cfg), cfg, x)
    assert _rel(got, cnn_reference(params, cfg, x)) < 1e-2
