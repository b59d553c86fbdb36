"""p50_ms: median latency, due time to completion, over every attempted request."""
import statistics


def read(rec):
    lat = rec["latencies_ms"]
    return statistics.median(lat) if lat else None
