"""Open loop at ``rate_per_s``, with Poisson arrivals of the same set per seed.

The arrivals of a window are the exponential distribution's quantiles at
that rate, in an order drawn from the seed, so every seed offers the same
gaps in another order.  Requests pending when the window closes are drained
and keep their full latency.
"""
import numpy as np


def check(traffic: dict) -> None:
    if float(traffic["rate_per_s"]) <= 0:
        raise ValueError("a Poisson rate must be positive")


def offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Arrival offsets in [0, seconds) at ``rate``/s, the same set per seed.

    The ``n = round(rate * seconds)`` gaps are the exponential distribution's
    quantiles at (i + 0.5) / n, scaled so that they sum to ``seconds``, in an
    order drawn from ``seed``: the offered load is the same for every seed.
    """
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps *= seconds / gaps.sum()
    rng = np.random.default_rng([seed % 2 ** 64, 7])
    gaps = rng.permutation(gaps)
    # the first arrival at 0, the last a gap before the window's end
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def run(loop, traffic: dict, seconds: float, seed: int) -> None:
    w, clock = loop.w, loop.clock
    w.t0 = clock()
    due_times = w.t0 + offsets(float(traffic["rate_per_s"]), seconds, seed)
    i, n = 0, len(due_times)
    while i < n:
        now = clock()
        if due_times[i] > now and not loop.pending():
            loop.wait_until(due_times[i])
            now = clock()
        with loop.span("submit"):
            while i < n and due_times[i] <= now:
                w.late_s = max(w.late_s, now - due_times[i])
                loop.submit(float(due_times[i]))
                i += 1
        if loop.pending():
            loop.step()
    w.t_end = w.t0 + seconds
    w.backlog_end = loop.pending()
    with loop.span("drain"):
        while loop.pending():
            loop.step()
