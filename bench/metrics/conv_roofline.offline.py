"""conv_roofline.offline: the Pallas conv kernels' share of their roofline.

Over the conv layers that the resolved plan puts on a Pallas engine: the sum
of each layer's floor (the larger of its direct operations at the int8 peak
and its fewest int16 bytes at the HBM bandwidth, ``bench/work.py``) for every
step's bucket of rows, over the device time of the Pallas (custom call) ops
in the trace.  None when the trace shows no Pallas time.
"""
import work


def read(rec):
    t, peaks = rec["trace"], rec["peaks"]
    if t is None or not peaks or not rec["pallas_layers"] or t["pallas_s"] <= 0:
        return None
    floor = sum(n * work.layer_floor_s(layer, bucket, peaks)
                for bucket, n in rec["counters"]["bucket_steps"].items()
                for layer in rec["pallas_layers"])
    return 100.0 * floor / t["pallas_s"]
