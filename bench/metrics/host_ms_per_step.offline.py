"""host_ms_per_step.offline: host time of a serving step in the offline cells, ms.

The mean, over the window's steps that served, of the ``engine.step`` span
less its ``engine.forward`` child: stacking, the copy to the device, the
copy back, unpadding, admission and bookkeeping, but not the jitted forward
(``bench/trace_phases.py``).  Reads the program's spans
(``repro.serving.spans``) from ``rec["program_spans"]``; None without them.
"""
import trace_phases


def read(rec):
    spans = rec.get("program_spans")
    return trace_phases.host_ms_per_step(spans) if spans else None
