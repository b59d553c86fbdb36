"""Host spans inside the serving path, recorded only while a recorder is installed.

``span(name)`` is the one hook the engine and the batcher call around each
phase of a step (``batch.admit``, ``batch.stack``, ``engine.to_device``,
``engine.forward``, ``engine.from_device``, ``batch.finish``) and of engine
set-up (``engine.quantize_weights``, ``engine.plan``, ``engine.jit``,
``engine.warmup``).  With no recorder installed it returns one shared
``nullcontext``: no clock is read and nothing is allocated, so serving pays
one global lookup per phase.

A caller that wants the spans (a benchmark harness, a profiling run)
installs a :class:`Recorder`::

    rec = spans.Recorder()
    spans.install(rec)
    ...                      # build, warm up and drive the engine
    spans.uninstall()
    rec.spans                # [Span(name, t0, t1, parent, error), ...]

Each span holds its name, its start and end on ``time.monotonic_ns()``, the
index of the enclosing span (its parent) in ``rec.spans`` or None, and
whether its body raised.  While installed, the recorder also turns JAX's
backend compiles (a compile or a persistent-cache load, both through
``jax.monitoring``) into ``jax.compile`` spans and Python's garbage
collections (``gc.callbacks``) into ``python.gc`` spans.  ``rec.counts``
tallies the spans by name, with the persistent-cache hits
(``jax.cache_hit``).
"""
from __future__ import annotations

import contextlib
import gc
import time
from typing import Dict, List, NamedTuple, Optional

#: The JAX event that times one executable's backend compile or cache load.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_NULL = contextlib.nullcontext()
_recorder: Optional["Recorder"] = None


class Span(NamedTuple):
    """One span.  A tuple of plain values, so the garbage collector stops
    tracking it once it is closed: a long recording does not slow the
    collections it records."""
    name: str
    t0: int                       # time.monotonic_ns() at entry
    t1: Optional[int] = None      # at exit; None while open
    parent: Optional[int] = None  # index of the enclosing span in the recorder
    error: bool = False           # the body raised


class _Open:
    """One recorded span's context manager."""
    __slots__ = ("rec", "name", "index")

    def __init__(self, rec: "Recorder", name: str):
        self.rec, self.name, self.index = rec, name, -1

    def __enter__(self):
        rec = self.rec
        s = Span(self.name, time.monotonic_ns(), None,
                 rec.stack[-1] if rec.stack else None)
        # the index only after the allocation: a collection it triggers
        # records its own span first
        self.index = len(rec.spans)
        rec.spans.append(s)
        rec.counts[self.name] = rec.counts.get(self.name, 0) + 1
        rec.stack.append(self.index)
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.monotonic_ns()
        rec, i = self.rec, self.index
        name, t0, _, parent, _ = rec.spans[i]
        rec.spans[i] = Span(name, t0, t1, parent, exc_type is not None)
        rec.stack.pop()
        return False


class Recorder:
    """Spans and event counts of one installation, kept in memory."""

    def __init__(self):
        self.spans: List[Span] = []
        self.stack: List[int] = []    # indices of the open spans, innermost last
        self.counts: Dict[str, int] = {"jax.compile": 0, "jax.cache_hit": 0,
                                       "python.gc": 0}
        self._gc_t0: Optional[int] = None

    def add(self, name: str, t0: int, t1: int) -> None:
        """A finished span, from an event timed elsewhere, under the open one."""
        self.spans.append(Span(name, t0, t1,
                               self.stack[-1] if self.stack else None))
        self.counts[name] = self.counts.get(name, 0) + 1

    # -- listeners, live while installed --------------------------------------

    def on_duration(self, event: str, duration_secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            t1 = time.monotonic_ns()
            self.add("jax.compile", t1 - int(duration_secs * 1e9), t1)

    def on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.counts["jax.cache_hit"] += 1

    def on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.monotonic_ns()
        elif self._gc_t0 is not None:
            self.add("python.gc", self._gc_t0, time.monotonic_ns())
            self._gc_t0 = None


def span(name: str):
    """A context manager that records ``name`` if a recorder is installed."""
    rec = _recorder
    return _NULL if rec is None else _Open(rec, name)


def install(recorder: Recorder) -> Recorder:
    """Record every span, compile and collection from now on into ``recorder``."""
    import jax.monitoring

    uninstall()
    global _recorder
    _recorder = recorder
    jax.monitoring.register_event_duration_secs_listener(recorder.on_duration)
    jax.monitoring.register_event_listener(recorder.on_event)
    gc.callbacks.append(recorder.on_gc)
    return recorder


def uninstall() -> Optional[Recorder]:
    """Stop recording; returns the recorder that was installed, if any."""
    import jax.monitoring

    global _recorder
    rec, _recorder = _recorder, None
    if rec is not None:
        jax.monitoring.unregister_event_duration_listener(rec.on_duration)
        jax.monitoring.unregister_event_listener(rec.on_event)
        gc.callbacks.remove(rec.on_gc)
    return rec
