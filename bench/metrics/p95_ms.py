"""p95_ms: 95th percentile latency, due time to completion, of every attempted
request; expired and failed requests sit in it at the time they were dropped."""
import numpy as np


def read(rec):
    lat = rec["latencies_ms"]
    return float(np.percentile(lat, 95)) if lat else None
