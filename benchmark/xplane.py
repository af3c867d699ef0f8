"""Reduction of a JAX profiler trace (`*.xplane.pb`) to the benchmark's
device numbers. Needs nothing but jax's own `ProfileData` reader.

What a v5e trace of the served path holds (looked at by hand, PR 24):
one plane per chip, `/device:TPU:<n>`, whose line `XLA Ops` has one event
per operation the chip ran (start, duration) and whose line `XLA Modules`
has one event per program execution; and `/host:CPU`, one line per host
thread, where `jax.profiler.TraceAnnotation` spans appear under their
own names. All on one clock.

  busy_s     union of the `XLA Ops` intervals, averaged over the chips
  window_s   first event start to last event end over all planes
  programs   seconds and executions per `XLA Modules` event name
  device_ops the ten operations that took most device time
  idle_gaps  the longest device-idle gaps' seconds, summed by the host
             span (of the names given) that covered most of each gap
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def find_trace(trace_dir: str) -> str:
    """The one `.xplane.pb` a `jax.profiler.start_trace(trace_dir)` left."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {found}")
    return found[0]


def events_of(plane, line_name: str) -> list:
    """[(start_s, end_s, name)] of one named line of a plane, sorted."""
    out = []
    for line in plane.lines:
        if line.name == line_name:
            for e in line.events:
                s = e.start_ns * 1e-9
                out.append((s, s + e.duration_ns * 1e-9, e.name))
    out.sort()
    return out


def merge(intervals: list) -> list:
    """Union of (start, end, ...) intervals as disjoint (start, end)."""
    merged = []
    for iv in sorted(intervals):
        s, e = iv[0], iv[1]
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def overlap(a0: float, a1: float, spans: list) -> float:
    """Seconds of [a0, a1] covered by the disjoint sorted `spans`."""
    return sum(max(0.0, min(a1, e) - max(a0, s)) for s, e in spans
               if e > a0 and s < a1)


def short_op(name: str) -> str:
    """'%while.2 = (u32[]...) while(...)' -> '%while.2 while': the HLO
    text of an operation runs to kilobytes."""
    m = re.match(r"(%[\w.\-]+) = .*?([a-z][\w\-]*)\(", name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def reduce(pd, span_names=()) -> dict:
    """The numbers above from one loaded profile. Raises ValueError when
    no device plane holds an operation: the chip did nothing traced."""
    lo, hi = float("inf"), float("-inf")
    host_spans: dict = {n: [] for n in span_names}
    device = []
    for plane in pd.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        if is_dev:
            device.append(plane)
        for line in plane.lines:
            for e in line.events:
                s = e.start_ns * 1e-9
                t = s + e.duration_ns * 1e-9
                lo, hi = min(lo, s), max(hi, t)
                if not is_dev and e.name in host_spans:
                    host_spans[e.name].append((s, t))
    per_chip = [events_of(p, OPS_LINE) for p in device]
    if not any(per_chip):
        raise ValueError("the trace holds no device operation "
                         f"(planes: {[p.name for p in pd.planes]})")
    busy = [merge(ops) for ops in per_chip]
    busy_s = sum(sum(e - s for s, e in b) for b in busy) / len(busy)
    op_s: dict = {}
    for ops in per_chip:
        for s, e, name in ops:
            name = short_op(name)
            op_s[name] = op_s.get(name, 0.0) + (e - s)
    programs: dict = {}
    for p in device:
        for s, e, name in events_of(p, MODULES_LINE):
            rec = programs.setdefault(name, {"seconds": 0.0, "runs": 0})
            rec["seconds"] += e - s
            rec["runs"] += 1
    # idle gaps of the first chip, named by what the host was doing
    spans = {n: merge(v) for n, v in host_spans.items()}
    total = {n: sum(e - s for s, e in v) for n, v in spans.items()}
    gap_s: dict = {}
    edges = [[lo, lo]] + busy[0] + [[hi, hi]]
    for (_, g0), (g1, _) in zip(edges, edges[1:]):
        if g1 - g0 <= 0:
            continue
        # of the spans that cover most of the gap, the innermost: nested
        # spans cover it alike, and the inner one has least time in all
        covering = [n for n, v in spans.items()
                    if overlap(g0, g1, v) > 0.5 * (g1 - g0)]
        name = (min(covering, key=total.get) if covering
                else "(no span covers it)")
        gap_s[name] = gap_s.get(name, 0.0) + (g1 - g0)
    top = lambda d: sorted(([k, v] for k, v in d.items()),  # noqa: E731
                           key=lambda kv: -kv[1])[:10]
    return {"busy_s": busy_s, "window_s": hi - lo, "chips": len(device),
            "programs": programs, "device_ops": top(op_s),
            "idle_gaps": top(gap_s),
            "span_s": total}


def reduce_dir(trace_dir: str, span_names=()) -> dict:
    return reduce(load(find_trace(trace_dir)), span_names)


def describe(pd, limit: int = 8) -> str:
    """A trace's planes, lines and first events, for reading by hand."""
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            ev = list(line.events)
            out.append(f"  LINE {line.name} ({len(ev)} events)")
            for e in ev[:limit]:
                out.append(f"    {e.name[:90]} start_ns={e.start_ns:.0f} "
                           f"dur_ns={e.duration_ns:.0f}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(describe(load(sys.argv[1])))
