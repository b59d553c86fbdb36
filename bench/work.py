"""Engine-independent work of a configuration, from its layer shapes alone.

Counts the *direct* work of each layer: multiply-accumulates of a plain
convolution or matrix product, and the fewest bytes a layer has to move
(weights once per call, its input and output once per image, every value
stored as int16).  Whichever engine runs a layer (im2col, systolic,
implicit GEMM, Winograd), the same count applies, so a share of the
roofline built on it compares engines fairly.
"""
from __future__ import annotations

from dataclasses import dataclass

#: Bytes of one stored value at int16 storage.
STORE_BYTES = 2


@dataclass(frozen=True)
class Layer:
    kind: str            # "conv" | "fc"
    index: int           # position in the config's layer list
    macs: int            # direct MACs per image
    weight_bytes: int    # per call
    act_bytes: int       # input + output, per image


def layers(config: dict) -> list[Layer]:
    """Conv and FC layers of ``config`` (a ``bench/configs`` dict), in order."""
    h, c = config["img_size"], config["in_channels"]
    first_conv = True
    feat = None
    out: list[Layer] = []
    for i, spec in enumerate(config["layers"]):
        if spec[0] == "conv":
            _, k, cout, stride = spec
            pad = config["first_conv_padding"] if first_conv else "SAME"
            first_conv = False
            oh = (h - k) // stride + 1 if pad == "VALID" else -(-h // stride)
            out.append(Layer("conv", i, oh * oh * k * k * c * cout,
                             k * k * c * cout * STORE_BYTES,
                             (h * h * c + oh * oh * cout) * STORE_BYTES))
            h, c = oh, cout
        elif spec[0] == "pool":
            h //= 2
        else:
            fin = feat if feat is not None else h * h * c
            n = spec[1]
            out.append(Layer("fc", i, fin * n, fin * n * STORE_BYTES,
                             (fin + n) * STORE_BYTES))
            feat = n
    return out


def conv_macs(config: dict) -> int:
    return sum(l.macs for l in layers(config) if l.kind == "conv")


def fc_weights(config: dict) -> int:
    return sum(l.macs for l in layers(config) if l.kind == "fc")


def ops_per_image(config: dict) -> int:
    """2 x direct MACs of every conv and FC layer: one image's operations."""
    return 2 * sum(l.macs for l in layers(config))


def layer_floor_s(layer: Layer, rows: int, peaks: dict) -> float:
    """Least time one call of ``layer`` on ``rows`` images can take.

    The larger of its operations at the int8 peak and its bytes at the HBM
    bandwidth: the direct work, whatever the engine issues.
    """
    ops = 2 * layer.macs * rows
    moved = layer.weight_bytes + layer.act_bytes * rows
    return max(ops / peaks["int8_ops_per_s"], moved / peaks["hbm_bytes_per_s"])
