"""xla_ms_per_image.offline: device time of every non-Pallas op (stem, FC,
quantization, pools, glue) per image completed, from the trace."""


def read(rec):
    t = rec["trace"]
    if t is None or not t["devices"] or not rec["images"]:
        return None
    return 1e3 * t["xla_s"] / rec["images"]
