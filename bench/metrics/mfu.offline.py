"""mfu.offline: the whole model step's share of the chip's int8 peak, on the device.

Direct operations of one image (2 x every conv and FC MAC, ``bench/work.py``)
times the images completed in the window, over the device's busy time in
the traced window (the union of its ops, ``bench/trace_reduce.py``) times
the int8 peak of ``bench/peaks.json``.  With ``idle_share.offline`` it
splits ``images_per_s``: the rate is this share times the peak over the
operations per image, times the busy part of the window.  Engine-independent:
Winograd F(2x2,3x3) under three KOM int8 passes issues 1.33 int8 MACs per
direct MAC, so it reads at most 75%.
"""


def read(rec):
    t, peaks = rec["trace"], rec["peaks"]
    if t is None or not peaks or t["busy_s"] <= 0 or not rec["images"]:
        return None
    ops = rec["ops_per_image"] * rec["images"]
    return 100.0 * ops / (t["busy_s"] * peaks["int8_ops_per_s"])
