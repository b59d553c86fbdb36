"""The program's own spans against a profiler trace: phases, layers, gaps.

``trace_reduce`` aligns the harness's ``engine.step`` spans to the device
clock; this module reads what the program records when a
``repro.serving.spans.Recorder`` is installed (``bench/phases.py`` does):

* **Shared clock.**  Every step that served ran its forward once, inside the
  ``engine.forward`` span (the jitted call through ``block_until_ready``), so
  the ``XLA Modules`` events pair in order with those spans.  The call
  returns just after its module ends, so the offset (host ns minus device
  ns) is the largest that keeps every module's end before its span's end:
  the tight one-sided bound.  The feasible offsets run from the one that
  puts every module's start after its span's start up to that bound;
  ``clock_slack_ms`` is their spread, the alignment's uncertainty.
* **Phases.**  Each idle gap of the device is split over the spans that
  cover it, the innermost at each instant (``HOST_SPANS``, innermost
  first), and named after the one that covers most of it.  A step's host
  time is its ``engine.step`` span less its ``engine.forward`` child.
* **Layers.**  ``cnn_forward`` runs each layer under ``jax.named_scope``
  (``l02.conv1_2``); the trace's event metadata keeps that scope in each
  op's ``tf_op`` stat, which ``jax.profiler.ProfileData`` does not expose,
  so ``op_scopes`` reads it from the ``.xplane.pb`` itself.  Ops outside
  every layer (the input's layout copy, weight prefetches) are ``unscoped``.

Spans are rows ``(name, t0_ns, t1_ns, parent, error)`` on the host's
monotonic clock (``Recorder.spans``, or the same as JSON lists); ``parent``
is the index of the enclosing row or None.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

import trace_reduce

#: Spans that label an idle gap, innermost first where they nest: the
#: events the program records inside any phase, the phases of a step, then
#: the harness's own spans.
HOST_SPANS = ("python.gc", "jax.compile",
              "engine.to_device", "engine.forward", "engine.from_device",
              "batch.admit", "batch.stack", "batch.finish",
              "submit", "arrival_wait", "engine.step", "drain")
STEP, FORWARD, WINDOW = "engine.step", "engine.forward", trace_reduce.WINDOW_SPAN
#: Children of ``engine.step`` that split its wall time.
PHASES = ("batch.admit", "batch.stack", "engine.to_device", FORWARD,
          "engine.from_device", "batch.finish")
UNSCOPED = "unscoped"
_LAYER = re.compile(r"(?:^|/)(l\d\d\.[A-Za-z0-9_]+)(?:/|:|$)")


# -- the .xplane.pb's event metadata (protobuf wire format) -------------------

def _varint(b, i: int) -> tuple:
    x = s = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return x, i


def _fields(b):
    """``(field, value)`` of one message; length-delimited values as views."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield f, v


def _map_entry(b) -> tuple:
    key = val = None
    for f, v in _fields(b):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def op_scopes(path: str) -> dict:
    """HLO text of each device op -> its ``tf_op`` (the op's scope path).

    XSpace.planes (1); XPlane.name (2), .event_metadata (4: id -> XEventMetadata
    with name 2 and stats 5), .stat_metadata (5: id -> XStatMetadata with
    name 2); XStat.metadata_id (1), .str_value (5).
    """
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out: dict = {}
    for f, plane in _fields(data):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                metas.append(_map_entry(v)[1])
            elif g == 5:
                k, sm = _map_entry(v)
                stat_names[k] = next((bytes(x).decode() for h, x in _fields(sm)
                                      if h == 2), "")
        if not name.startswith("/device:TPU:"):
            continue
        tf_op_id = next((k for k, n in stat_names.items() if n == "tf_op"), None)
        for m in metas:
            op, scope = None, None
            for h, v in _fields(m):
                if h == 2:
                    op = bytes(v).decode()
                elif h == 5 and tf_op_id is not None:
                    sid = val = None
                    for s, x in _fields(v):
                        if s == 1:
                            sid = x
                        elif s == 5:
                            val = x
                    if sid == tf_op_id and val is not None:
                        scope = bytes(val).decode()
            if op is not None and scope is not None:
                out[op] = scope
    return out


def layer_of(scope: str | None) -> str:
    """``jit(fwd)/l02.conv1_2/jit(_conv2d_winograd_core)/...`` -> ``l02.conv1_2``."""
    m = _LAYER.search(scope or "")
    return m.group(1) if m else UNSCOPED


# -- spans ---------------------------------------------------------------------

def served_steps(spans) -> list:
    """``(step index, [forward indices])`` of each ``engine.step`` that served.

    A step served when a forward under it returned (no error flag).
    """
    fwd: dict = defaultdict(list)
    for i, r in enumerate(spans):
        if r[0] != FORWARD or r[2] is None:
            continue
        up = r[3]
        while up is not None and spans[up][0] != STEP:
            up = spans[up][3]
        if up is not None:
            fwd[up].append(i)
    return [(i, fwd[i]) for i, r in enumerate(spans)
            if r[0] == STEP and r[2] is not None
            and any(not spans[f][4] for f in fwd[i])]


def host_ms_per_step(spans) -> float | None:
    """Mean over served steps of ``engine.step`` less its forwards, in ms."""
    steps = served_steps(spans)
    if not steps:
        return None
    dur = lambda i: spans[i][2] - spans[i][1]
    return 1e-6 * sum(dur(s) - sum(dur(f) for f in fw)
                      for s, fw in steps) / len(steps)


def phase_ms_per_step(spans) -> dict:
    """Mean ms per served step of each child of ``engine.step``.

    Besides the children: ``engine.step`` itself, and ``coverage``, the
    children's share of the steps' wall time.
    """
    steps = {s for s, _ in served_steps(spans)}
    if not steps:
        return {}
    tot: dict = defaultdict(int)
    for r in spans:
        if r[3] in steps and r[2] is not None:
            tot[r[0]] += r[2] - r[1]
    wall = sum(spans[i][2] - spans[i][1] for i in steps)
    out = {n: 1e-6 * t / len(steps)
           for n, t in sorted(tot.items(), key=lambda kv: -kv[1])}
    out[STEP] = 1e-6 * wall / len(steps)
    out["coverage"] = sum(tot.values()) / wall if wall else 0.0
    return out


# -- the device clock ----------------------------------------------------------

def forward_offset(modules: list, forwards: list) -> tuple:
    """``(offset_ns, slack_ns)``: host ns minus device ns, and its uncertainty.

    ``modules`` and ``forwards`` are ``(start, end)`` pairs, paired in order.
    The offset is the largest that keeps every module's end before its
    forward's end; the slack runs down to the smallest that keeps every
    start after its forward's start.  Counts that differ (events dropped)
    give the median pair's end offset and no slack (None).
    """
    pairs = list(zip(forwards, modules))
    if not pairs:
        return 0.0, None
    hi = min(h1 - d1 for (_, h1), (_, d1) in pairs)
    lo = max(h0 - d0 for (h0, _), (d0, _) in pairs)
    if len(modules) != len(forwards) or lo > hi:
        return sorted(h1 - d1 for (_, h1), (_, d1) in pairs)[len(pairs) // 2], None
    return hi, hi - lo


def _device(profile) -> tuple:
    devices, modules = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    devices.append([(ev.start_ns, ev.start_ns + ev.duration_ns,
                                     ev.name) for ev in line.events])
                elif line.name == trace_reduce.MODULES_LINE and not modules:
                    modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                                     for ev in line.events)
    return devices, modules


def timeline(spans) -> list:
    """Disjoint ``[start, end, name]``: the innermost ``HOST_SPANS`` span at
    each instant, from ``(name, t0, t1)`` intervals on one clock."""
    rank = {n: i for i, n in enumerate(HOST_SPANS)}
    events = sorted(ev for name, t0, t1 in spans if name in rank and t1 > t0
                    for ev in ((t0, 1, rank[name]), (t1, -1, rank[name])))
    active = [0] * len(HOST_SPANS)
    out: list = []
    prev = None
    for t, step, r in events:
        if prev is not None and t > prev:
            inner = next((i for i, n in enumerate(active) if n), None)
            if inner is not None:
                if out and out[-1][1] == prev and out[-1][2] == HOST_SPANS[inner]:
                    out[-1][1] = t
                else:
                    out.append([prev, t, HOST_SPANS[inner]])
        active[r] += step
        prev = t
    return out


def split(a: float, b: float, segments: list, starts: list) -> dict:
    """How much of ``[a, b)`` each segment's span covers; the rest is ``none``."""
    out: dict = defaultdict(float)
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    covered = 0.0
    while i < len(segments) and segments[i][0] < b:
        s, e, name = segments[i]
        t = min(e, b) - max(s, a)
        if t > 0:
            out[name] += t
            covered += t
        i += 1
    if b - a > covered:
        out["none"] += b - a - covered
    return out


def reduce(profile, spans, scopes: dict | None = None) -> dict:
    """Idle time by phase, layer-named device ops and the clock's slack.

    ``scopes``: :func:`op_scopes` of the same trace (None: every op
    unscoped).  Each idle gap of the device is split over the innermost
    span covering each part of it (``idle_by_label``, exact) and named
    after the span that covers most of it (``idle_gaps``, the longest ten).
    ``forward_ms``: the mean forward split into ``wait`` (the call was
    made, its module had not started: the input's copy and dispatch),
    ``module`` and ``return`` (module end to the call's return).  Times in
    seconds, but those in ms.
    """
    devices, modules = _device(profile)
    fwd = sorted((r[1], r[2]) for r in spans
                 if r[0] == FORWARD and r[2] is not None and not r[4])
    off, slack = forward_offset(modules, fwd)
    spans = [(r[0], r[1] - off, r[2] - off) for r in spans if r[2] is not None]
    wins = [(t0, t1) for name, t0, t1 in spans if name == WINDOW]
    if not wins:
        raise ValueError("no 'window' span")
    w0, w1 = max(wins, key=lambda se: se[1] - se[0])
    segments = timeline(spans)
    starts = [seg[0] for seg in segments]
    scopes = scopes or {}
    by_op: dict = defaultdict(float)
    by_layer: dict = defaultdict(float)
    idle: dict = defaultdict(float)
    gaps: list = []
    busy = 0.0
    for events in devices:
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in events
                   if e > w0 and s < w1]
        for s, e, n in clipped:
            layer = layer_of(scopes.get(n))
            t = (e - s) / len(devices)
            by_op[f"{layer}/{trace_reduce.op_name(n)}"] += t
            by_layer[layer] += t
        merged = trace_reduce._union([(s, e) for s, e, _ in clipped])
        busy += sum(e - s for s, e in merged) / len(devices)
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                parts = split(a, b, segments, starts)
                for name, t in parts.items():
                    idle[name] += t / len(devices)
                gaps.append((max(parts, key=parts.get), (b - a) / len(devices)))
    gaps.sort(key=lambda g: -g[1])
    pairs = list(zip(fwd, modules)) if len(fwd) == len(modules) else []
    mean = lambda xs: 1e-6 * sum(xs) / len(xs) if xs else None
    ns = 1e-9
    top = trace_reduce.TOP
    return {
        "window_s": (w1 - w0) * ns,
        "busy_s": busy * ns,
        "clock_slack_ms": None if slack is None else slack * 1e-6,
        "modules": len(modules), "forwards": len(fwd),
        "forward_ms": {
            "wait": mean([d0 + off - h0 for (h0, _), (d0, _) in pairs]),
            "module": mean([d1 - d0 for _, (d0, d1) in pairs]),
            "return": mean([h1 - d1 - off for (_, h1), (_, d1) in pairs])},
        "device_ops": [[n, t * ns] for n, t in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "device_layers": [[n, t * ns] for n, t in
                          sorted(by_layer.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[n, t * ns] for n, t in gaps[:top]],
        "idle_by_label": [[n, t * ns] for n, t in
                          sorted(idle.items(), key=lambda kv: -kv[1])],
    }
