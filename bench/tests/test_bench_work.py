"""Direct work of the benchmark's configurations, from their files."""
import json

import pytest

import work
from conftest import BENCH


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


#: Direct work of the full-size configurations, through their family.
WORK = {"vgg16": (30_940_528_640, 15_346_630_656, 123_633_664),
        "alexnet": (2_270_512_192, 1_076_634_144, 58_621_952)}


@pytest.mark.parametrize("name", sorted(WORK))
def test_conv_macs_and_fc_weights(name):
    c = config(name)
    _, conv, fc = WORK[name]
    assert work.conv_macs(c) == conv
    assert work.fc_weights(c) == fc


@pytest.mark.parametrize("name", sorted(WORK))
def test_ops_per_image(name):
    assert work.ops_per_image(config(name)) == WORK[name][0]


def test_floor_is_the_larger_bound():
    layer = work.Layer(work.Kind.CONV, 0, macs=10**9, weight_bytes=10**6,
                       act_bytes=3 * 10**6)
    peaks = {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    # per row 2 GOP at 1 TOP/s = 2 ms and 3 MB at 1 GB/s = 3 ms, weights 1 ms
    assert work.layer_floor_s(layer, 1, peaks) == pytest.approx(4e-3)
    assert work.layer_floor_s(layer, 4, peaks) == pytest.approx(13e-3)
    peaks["hbm_bytes_per_s"] = 1e12
    assert work.layer_floor_s(layer, 4, peaks) == pytest.approx(8e-3)
