"""Engine-independent work of a configuration, from its layer shapes alone.

Counts the *direct* work of each layer: multiply-accumulates of a plain
convolution or matrix product, and the fewest bytes a layer has to move
(weights once per call, its input and output once per image, every value
stored as int16).  Whichever engine runs a layer (im2col, systolic,
implicit GEMM, Winograd), the same count applies, so a share of the
roofline built on it compares engines fairly.  The layers themselves come
from the configuration's family (``bench/family.py``).
"""
from __future__ import annotations

import enum
from dataclasses import dataclass

import family

#: Bytes of one stored value at int16 storage.
STORE_BYTES = 2


class Kind(enum.Enum):
    CONV = enum.auto()   # a convolution
    FC = enum.auto()     # a matrix product over the whole feature vector


@dataclass(frozen=True)
class Layer:
    kind: Kind
    index: int           # position in the family's parameter list
    macs: int            # direct MACs per image
    weight_bytes: int    # per call
    act_bytes: int       # input + output, per image


def _macs(config: dict, kind: Kind) -> int:
    return sum(l.macs for l in family.of(config).layers(config)
               if l.kind is kind)


def conv_macs(config: dict) -> int:
    return _macs(config, Kind.CONV)


def fc_weights(config: dict) -> int:
    return _macs(config, Kind.FC)


def ops_per_image(config: dict) -> int:
    """2 x direct MACs of every conv and FC layer: one image's operations."""
    return 2 * sum(l.macs for l in family.of(config).layers(config))


def layer_floor_s(layer: Layer, rows: int, peaks: dict) -> float:
    """Least time one call of ``layer`` on ``rows`` images can take.

    The larger of its operations at the int8 peak and its bytes at the HBM
    bandwidth: the direct work, whatever the engine issues.
    """
    ops = 2 * layer.macs * rows
    moved = layer.weight_bytes + layer.act_bytes * rows
    return max(ops / peaks["int8_ops_per_s"], moved / peaks["hbm_bytes_per_s"])
