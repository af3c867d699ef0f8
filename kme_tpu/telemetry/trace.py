"""Phase timing spans + Chrome trace-event export.

PhaseTimer replaces the per-session `time.perf_counter()` blocks that
were duplicated across runtime/seqsession.py and parallel/seqmesh.py. Its `totals` dict IS the session's `phases`
attribute (same object, assigned once), and — unlike the old code —
totals ACCUMULATE across batches; callers snapshot/reset explicitly.

When a TraceRecorder is installed (module-global via install(), as
`kme-serve --trace-out` does), every phase span
is also emitted as a Chrome trace event; save() writes the standard
{"traceEvents": [...]} JSON that chrome://tracing / Perfetto load
directly.

In a process that has already imported jax, every span also enters a
`jax.profiler.TraceAnnotation` of the same name: whoever has a profiler
trace running (the benchmark's host, `--capture-dir`) finds the
program's spans in the `.xplane.pb` beside the device operations, on
one clock. This module never imports jax itself: `--engine oracle`, the
tools and the benchmark's parent stay free of it.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from contextlib import contextmanager


class TraceRecorder:
    """Collects Chrome trace-event "X" (complete) events.

    Timestamps are microseconds relative to recorder creation; `tid`
    groups events into named rows (one per session/component)."""

    def __init__(self):
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._events = []
        self._tids: dict = {}

    def _tid(self, track: str) -> int:
        t = self._tids.get(track)
        if t is None:
            t = len(self._tids)
            self._tids[track] = t
        return t

    def add(self, name: str, start_s: float, dur_s: float,
            track: str = "main", args: dict | None = None) -> None:
        ev = {
            "name": name,
            "ph": "X",
            "ts": (start_s - self._t0) * 1e6,
            "dur": dur_s * 1e6,
            "pid": os.getpid(),
        }
        with self._lock:
            ev["tid"] = self._tid(track)
            if args:
                ev["args"] = args
            self._events.append(ev)

    def flow(self, name: str, phase: str, flow_id: int,
             track: str = "main", at_s: float | None = None) -> None:
        """Chrome trace FLOW event: ph "s" starts arrow `flow_id`, ph
        "f" finishes it — the renderer draws a causality arrow from the
        span enclosing the start to the span enclosing the finish
        (bp="e": bind to the enclosing slice). Links an order batch's
        submit/engine span to its produce span across tracks."""
        if phase not in ("s", "f"):
            raise ValueError(f"flow phase must be 's' or 'f', "
                             f"got {phase!r}")
        t = at_s if at_s is not None else time.perf_counter()
        ev = {
            "name": name,
            "ph": phase,
            "cat": "flow",
            "id": int(flow_id),
            "ts": (t - self._t0) * 1e6,
            "pid": os.getpid(),
        }
        if phase == "f":
            ev["bp"] = "e"
        with self._lock:
            ev["tid"] = self._tid(track)
            self._events.append(ev)

    def instant(self, name: str, track: str = "main",
                args: dict | None = None) -> None:
        ev = {
            "name": name,
            "ph": "i",
            "ts": (time.perf_counter() - self._t0) * 1e6,
            "pid": os.getpid(),
            "s": "t",
        }
        with self._lock:
            ev["tid"] = self._tid(track)
            if args:
                ev["args"] = args
            self._events.append(ev)

    def trace_events(self) -> list:
        with self._lock:
            meta = [
                {"name": "thread_name", "ph": "M", "pid": os.getpid(),
                 "tid": tid, "args": {"name": track}}
                for track, tid in self._tids.items()
            ]
            return meta + list(self._events)

    def save(self, path: str) -> None:
        doc = {"traceEvents": self.trace_events(),
               "displayTimeUnit": "ms"}
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        os.replace(tmp, path)


# module-global recorder: CLI entry points install one so every
# PhaseTimer in the process emits trace events without plumbing
_tracer: TraceRecorder | None = None


def install(recorder: TraceRecorder | None) -> None:
    global _tracer
    _tracer = recorder


def get_tracer() -> TraceRecorder | None:
    return _tracer


# the spans open on each thread, outermost first: a span's parent is
# the one open on the same thread, whichever timer opened it
_open = threading.local()


class PhaseTimer:
    """Accumulating span timer — the program's one span primitive.

    `totals` maps phase name -> cumulative seconds across every span
    since the last reset(), `counts` the number of spans entered.
    Sessions expose `totals` directly as `self.phases`. Spans nest; a
    span given no `batch` takes its parent's, so the serve loop's batch
    ordinal ties a batch's submit, collect, produce and publish
    together down into the session's spans.

    Wall is not work in a process whose threads share one interpreter:
    for the spans named in `cpu` (those whose CPU something reads — the
    clock is a system call on some hosts, so it is not read for the
    rest) `cpu_totals` keeps the seconds of their wall that the span's
    own thread ran (`time.thread_time()`, read where the wall clock
    is); wall less CPU is what the thread waited, for the device, the
    disk, the interpreter lock or the scheduler."""

    def __init__(self, track: str = "main", cpu=()):
        self.totals: dict = {}
        self.cpu_totals: dict = dict.fromkeys(cpu, 0.0)
        self.counts: dict = {}
        self.track = track

    @contextmanager
    def phase(self, name: str, **args):
        stack = _open.__dict__.setdefault("stack", [])
        if "batch" not in args and stack and "batch" in stack[-1]:
            args["batch"] = stack[-1]["batch"]
        # the profiler's own span, on the device trace's clock: built
        # only while somebody has a trace running, one flag test when
        # nobody has
        prof = sys.modules.get("jax.profiler")
        ann = None
        if prof is not None and prof.TraceAnnotation.is_enabled():
            ann = prof.TraceAnnotation(name, **args)
        stack.append(args)
        timed = name in self.cpu_totals
        t0 = time.perf_counter()
        c0 = time.thread_time() if timed else 0.0
        try:
            if ann is None:
                yield
            else:
                with ann:
                    yield
        finally:
            dt = time.perf_counter() - t0
            if timed:
                self.cpu_totals[name] += time.thread_time() - c0
            stack.pop()
            self.totals[name] = self.totals.get(name, 0.0) + dt
            self.counts[name] = self.counts.get(name, 0) + 1
            tr = _tracer
            if tr is not None:
                tr.add(name, t0, dt, track=self.track,
                       args=args or None)

    def add(self, name: str, seconds: float, n: int = 1) -> None:
        """Fold `n` externally-timed spans into the totals (wall only:
        the C++ router's clock, the one caller's, knows no CPU)."""
        self.totals[name] = self.totals.get(name, 0.0) + seconds
        self.counts[name] = self.counts.get(name, 0) + n

    def gauges(self, also=()) -> dict:
        """Every span as two cumulative heartbeat gauges: `<name>_s`
        (seconds; a phase already named `..._s` keeps its name) and
        `<name>_n` (entries), with `<name>_cpu_s` beside them for the
        spans named in `cpu`. Names in `also` not entered yet read 0,
        as every `_cpu_s` does before its span's first entry."""
        out = {}
        for name in (*also, *self.totals):
            base = name[:-2] if name.endswith("_s") else name
            out[base + "_s"] = round(self.totals.get(name, 0.0), 6)
            out[base + "_n"] = self.counts.get(name, 0)
        for name, cpu in self.cpu_totals.items():
            base = name[:-2] if name.endswith("_s") else name
            out[base + "_cpu_s"] = round(cpu, 6)
        return out

    def reset(self) -> None:
        self.totals.clear()
        self.counts.clear()
        for name in self.cpu_totals:
            self.cpu_totals[name] = 0.0
