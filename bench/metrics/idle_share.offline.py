"""idle_share.offline: 1 - device busy union / traced window, in the offline cells."""


def read(rec):
    t = rec["trace"]
    if t is None or not t["devices"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
