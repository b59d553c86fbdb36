"""host_ms_per_step.sync1: host time of a serving step in the one-caller cell, ms.

The mean, over the window's steps that served, of the ``engine.step`` span
less its ``engine.forward`` child (``bench/trace_phases.py``); at bucket 1
it is the part of each request's latency spent outside the forward call.
Reads ``rec["program_spans"]``; None without them.
"""
import trace_phases


def read(rec):
    spans = rec.get("program_spans")
    return trace_phases.host_ms_per_step(spans) if spans else None
