"""device_ms_per_step.sync1: device busy time in the window over engine steps."""


def read(rec):
    t, steps = rec["trace"], rec["counters"]["steps"]
    if t is None or not steps or t["busy_s"] <= 0:
        return None
    return 1e3 * t["busy_s"] / steps
