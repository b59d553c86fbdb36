"""Traffic: a mix's data file, driven by the module of its arrival kind.

A traffic mix is a JSON file under ``bench/traffic/`` with these keys:

``kind``
    the arrival process: the module ``bench/traffic/kinds/<kind>.py``,
    which checks the mix's own parameters (``check(traffic)``) and drives
    one window (``run(loop, traffic, seconds, seed)``).  A new kind is a new
    module there; no existing file changes.
``buckets``
    the engine's batch buckets.
``slo``
    the SLO class each request names; ``budget_s`` (open loop) sets its
    deadline from the request's due time.

Every request is timed on the host clock from its **due** time (open loop:
its arrival time; closed loop: when its caller sent it) to the return of
the engine step that completed it.
"""
from __future__ import annotations

import importlib.util
import pathlib
import time
from dataclasses import dataclass, field
from typing import Callable

import numpy as np

KINDS_DIR = pathlib.Path(__file__).resolve().parent / "traffic" / "kinds"


def kind(name: str):
    """The module of arrival kind ``name``, found by its file name."""
    path = KINDS_DIR / f"{name}.py"
    if not isinstance(name, str) or "/" in name or not path.is_file():
        raise ValueError(f"traffic kind {name!r} has no module in {KINDS_DIR}")
    spec = importlib.util.spec_from_file_location(f"bench_kind_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check(traffic: dict) -> dict:
    kind(traffic.get("kind")).check(traffic)
    if not traffic.get("buckets"):
        raise ValueError("traffic names no engine buckets")
    return traffic


@dataclass
class Window:
    """What one measured window did, stamped on the host clock."""
    t0: float = 0.0
    t_end: float = 0.0            # window close (closed: a step boundary)
    t_last: float = 0.0           # return of the latest step
    due: dict = field(default_factory=dict)        # uid -> due time
    completed: dict = field(default_factory=dict)  # uid -> completion time
    failed: set = field(default_factory=set)       # expired or failed uids
    steps: int = 0                # engine steps of the window
    pending_peak: int = 0
    backlog_end: int = 0          # requests pending when the window closed
    late_s: float = 0.0           # how far submits ran behind their due time
    kept: dict = field(default_factory=dict)       # uid -> logits row
    forwards: list = field(default_factory=list)   # (t0, t1) ns of steps that served


class Loop:
    """What a kind's ``run`` drives the engine with; it fills ``self.w``."""

    def __init__(self, engine, traffic: dict, images: np.ndarray, *,
                 request_cls, keep: Callable[[int], bool],
                 span: Callable[[str], object], clock=time.monotonic):
        self.engine, self.images = engine, images
        self.request_cls, self.keep = request_cls, keep
        self.span, self.clock = span, clock
        self.slo = traffic.get("slo")
        self.budget = traffic.get("budget_s")
        self.w = Window()
        self.queue = engine.request_queue
        self.next_uid = self.queue.submitted_count   # uids are unique per queue
        self._seen = {"expired": len(self.queue.expired),
                      "failed": len(self.queue.failed)}

    def pending(self) -> int:
        return len(self.engine.request_queue)

    def submit(self, due: float) -> None:
        uid, self.next_uid = self.next_uid, self.next_uid + 1
        deadline = None if self.budget is None else due + float(self.budget)
        self.w.due[uid] = due
        self.engine.submit(self.request_cls(
            uid=uid, image=self.images[uid % len(self.images)],
            deadline=deadline, slo=self.slo))

    def step(self) -> list:
        w, n_bucket = self.w, self.pending()
        t0 = time.monotonic_ns()
        with self.span("engine.step"):
            done = self.engine.step()
        if done:
            w.forwards.append((t0, time.monotonic_ns()))
        t = w.t_last = self.clock()
        w.steps += 1
        w.pending_peak = max(w.pending_peak, n_bucket)
        for req in done:
            w.completed[req.uid] = t
            if self.keep(req.uid):
                w.kept[req.uid] = np.array(req.logits, np.float32)
        self.sweep_failures()
        return done

    def sweep_failures(self) -> None:
        """Stamp requests newly expired or failed (the ledgers only grow)."""
        for name, seen in self._seen.items():
            ledger = getattr(self.queue, name)
            if len(ledger) == seen:
                continue
            for uid in list(ledger)[seen:]:
                if uid in self.w.due:
                    self.w.failed.add(uid)
                    self.w.completed.setdefault(uid, self.clock())
            self._seen[name] = len(ledger)

    def wait_until(self, target: float) -> None:
        with self.span("arrival_wait"):
            while True:
                left = target - self.clock()
                if left <= 0:
                    return
                time.sleep(min(left, 0.002) if left > 0.0005 else 0)


def drive(engine, traffic: dict, images: np.ndarray, seconds: float, seed: int,
          *, request_cls, keep: Callable[[int], bool],
          span: Callable[[str], object], clock=time.monotonic) -> Window:
    """Run one window of ``traffic`` against ``engine``; return its record.

    ``images`` is the pool a request draws from, by uid.  ``keep(uid)``
    says whose logits to keep for the check.  ``span(name)`` opens a host
    span (a context manager) around each phase of the loop.
    """
    loop = Loop(engine, traffic, images, request_cls=request_cls, keep=keep,
                span=span, clock=clock)
    kind(traffic["kind"]).run(loop, traffic, seconds, seed)
    loop.sweep_failures()
    return loop.w


def latencies_ms(w: Window) -> list:
    """Latency of every attempted request, due to completion, in ms.

    Expired and failed requests count at the time the harness learned of
    their failure, so they sit in the tail and never shorten it.
    """
    return [1e3 * (w.completed[u] - w.due[u]) for u in w.due if u in w.completed]


def images_done(w: Window) -> int:
    return sum(1 for u in w.due if u in w.completed and u not in w.failed)


def attempted(w: Window) -> int:
    return len(w.due)


def outstanding(w: Window) -> int:
    """Attempted requests with no completion: lost, which is never sound."""
    return sum(1 for u in w.due if u not in w.completed)
