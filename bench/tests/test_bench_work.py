"""Direct work of the benchmark's configurations, from their files."""
import json

import pytest

import work
from conftest import BENCH


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name,conv_gmac,fc_m", [
    ("vgg16", 15.35, 123.6),
    ("alexnet", 1.08, 58.6),
])
def test_conv_macs_and_fc_weights(name, conv_gmac, fc_m):
    c = config(name)
    assert work.conv_macs(c) / 1e9 == pytest.approx(conv_gmac, abs=0.005)
    assert work.fc_weights(c) / 1e6 == pytest.approx(fc_m, abs=0.05)


@pytest.mark.parametrize("name,gop", [("vgg16", 30.94), ("alexnet", 2.27)])
def test_ops_per_image(name, gop):
    assert work.ops_per_image(config(name)) / 1e9 == pytest.approx(gop, abs=0.005)


def test_floor_is_the_larger_bound():
    layer = work.Layer("conv", 0, macs=10**9, weight_bytes=10**6,
                       act_bytes=3 * 10**6)
    peaks = {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    # per row 2 GOP at 1 TOP/s = 2 ms and 3 MB at 1 GB/s = 3 ms, weights 1 ms
    assert work.layer_floor_s(layer, 1, peaks) == pytest.approx(4e-3)
    assert work.layer_floor_s(layer, 4, peaks) == pytest.approx(13e-3)
    peaks["hbm_bytes_per_s"] = 1e12
    assert work.layer_floor_s(layer, 4, peaks) == pytest.approx(8e-3)
