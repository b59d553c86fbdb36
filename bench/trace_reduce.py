"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

The trace holds device events only; the harness's host spans (``window``,
``engine.step``, ``submit``, ``arrival_wait``, ``drain``) are recorded on
the host's monotonic clock and moved onto the device clock here: every
engine step that served requests ran the forward exactly once, so the
``XLA Modules`` events pair with those steps in order, and the offset is
the middle of the range that puts every module inside its step.

Device time comes from each TPU plane's ``XLA Ops`` line, clipped to the
``window`` span.  Busy time is the union of those intervals, averaged over
the chips; the Pallas share is the time of the ops that are custom calls
(every ``pallas_call`` lowers to one), the rest is XLA's.  Each idle gap
between busy intervals is labelled with the host span that covers its
midpoint, innermost first, or ``none``.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

#: Host spans the harness writes, innermost first when they nest.
HOST_SPANS = ("submit", "arrival_wait", "engine.step", "drain")
WINDOW_SPAN = "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def find_xplane(log_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


def is_pallas(name: str) -> bool:
    """A Pallas kernel: a custom call (the op's HLO text is its name)."""
    return " custom-call(" in name


def op_name(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def load(path: str):
    import jax.profiler

    return jax.profiler.ProfileData.from_file(path)


def clock_offset(modules: list, forwards: list) -> float:
    """Host ns minus device ns, from forwards paired in order with modules.

    The middle of the offsets that put every module inside its step; where
    none does (a count that differs, events dropped), the median pair's.
    """
    pairs = list(zip(forwards, modules))
    if not pairs:
        return 0.0
    lo = max(h0 - d0 for (h0, _), (d0, _) in pairs)
    hi = min(h1 - d1 for (_, h1), (_, d1) in pairs)
    if lo <= hi and len(modules) == len(forwards):
        return (lo + hi) / 2
    return sorted(h0 - d0 for (h0, _), (d0, _) in pairs)[len(pairs) // 2]


def reduce(profile, spans: list, forwards: list) -> dict:
    """Numbers of one traced window (seconds).

    ``spans``: ``(name, start_ns, end_ns)`` host spans, ``forwards``: the
    ``(start_ns, end_ns)`` of each step that ran the forward, both on the
    host's monotonic clock.
    """
    devices, modules = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([(ev.start_ns, ev.start_ns + ev.duration_ns,
                                     ev.name) for ev in line.events])
                elif line.name == MODULES_LINE and not modules:
                    modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                                     for ev in line.events)
    off = clock_offset(modules, sorted(forwards))
    host_spans: dict = defaultdict(list)
    for name, t0, t1 in spans:
        host_spans[name].append((t0 - off, t1 - off))
    if not host_spans.get(WINDOW_SPAN):
        raise ValueError("no 'window' span")
    w0, w1 = max(host_spans[WINDOW_SPAN], key=lambda se: se[1] - se[0])
    spans = {}
    for name in HOST_SPANS:
        iv = sorted(host_spans.get(name, ()))
        spans[name] = ([s for s, _ in iv], [e for _, e in iv])
    window_ns = w1 - w0
    busy = pallas = 0.0
    by_op: dict = defaultdict(float)
    gaps: list = []
    for events in devices:
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in events
                   if e > w0 and s < w1]
        for s, e, n in clipped:
            by_op[op_name(n)] += (e - s) / len(devices)
            if is_pallas(n):
                pallas += (e - s) / len(devices)
        merged = _union([(s, e) for s, e, _ in clipped])
        busy += sum(e - s for s, e in merged) / len(devices)
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_label(spans, (a + b) / 2),
                             (b - a) / len(devices)))
    total_op = sum(by_op.values())
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    gaps.sort(key=lambda g: -g[1])
    ns = 1e-9
    return {
        "devices": len(devices),
        "window_s": window_ns * ns,
        "busy_s": busy * ns,
        "pallas_s": pallas * ns,
        "xla_s": (total_op - pallas) * ns,
        "device_ops": [[n, t * ns] for n, t in ops],
        "idle_gaps": [[n, t * ns] for n, t in gaps[:TOP]],
    }


def _label(spans: dict, t: float) -> str:
    """The innermost host span that covers ``t``, or ``none``."""
    for name in HOST_SPANS:
        starts, ends = spans.get(name, ((), ()))
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < ends[i]:
            return name
    return "none"
