"""Per-layer metrics as data. Every file in `benchmark/layer_metrics/`
describes one metric: its name, unit, layer, the end-to-end metric it
should move, the cells that report it (none given: every cell that
reports that end-to-end metric), and a `read`: where the number
comes from and one of a small fixed set of reductions. A later PR adds a
metric by adding a file (and its `BENCHMARK.json` entry); a reader that
finds nothing to read returns None and the metric is left out.

Sources a `read` can name with `from`:
  heartbeat  two snapshots of the server's heartbeat file, the first
             written after the window opened (a) and the newest at its
             close (b), so both lie inside the window; `key` is a
             dotted path under its `metrics` section
             (`counters.service_batches`, `gauges.plan_s`,
             `latencies.lat_produce`)
  client     the benchmark's own numbers (`gen_late_p99_ms`,
             `first_output_s`)
  trace      the reduction of the profiler trace (benchmark/xplane.py)
Reductions (`reduce`):
  last               b[key]
  delta_per          (b[key] - a[key]) / (b[per] - a[per])
  seconds_per_delta  (b.time - a.time) / (b[key] - a[key])
  share_of_window    (b[key] - a[key]) / (b.time - a.time)
  hist_mean          delta of a latency histogram's sum_s over its count
  sum                sum of `terms` (each a read, with a `sign`)
  program_us_per_message   device seconds of the named program per
                     traced second, over messages served per second
  program_roofline   least time the program's bytes need at the chip's
                     peak HBM rate, over the program's device time
all times `scale`."""

from __future__ import annotations

import glob
import importlib
import json
import os
import re

from benchmark import peaks

HERE = os.path.dirname(os.path.abspath(__file__))


def load_for(cell: str, reports: set) -> list:
    """The layer metrics of a cell, by name: those whose `cells` include
    it or, where a file gives no `cells`, those that move an end-to-end
    metric the cell reports (`reports`) — so a later cell takes up the
    general ones without an edit to their files."""
    out = []
    for path in sorted(glob.glob(os.path.join(HERE, "layer_metrics",
                                              "*.json"))):
        with open(path) as f:
            m = json.load(f)
        if cell in m["cells"] if "cells" in m else m["moves"] in reports:
            out.append(m)
    return out


def dig(snapshot: dict, key: str):
    v = snapshot.get("metrics", {})
    for part in key.split("."):
        if not isinstance(v, dict) or part not in v:
            return None
        v = v[part]
    return v


def program(trace: dict, pattern: str):
    """(seconds, runs) of the traced programs whose name matches."""
    hits = [v for k, v in trace.get("programs", {}).items()
            if re.search(pattern, k)]
    if not hits:
        return None
    return sum(h["seconds"] for h in hits), sum(h["runs"] for h in hits)


def read(spec: dict, ctx: dict):
    """One number, or None when there is nothing to read."""
    value = _read(spec, ctx)
    return None if value is None else value * spec.get("scale", 1)


def _read(spec: dict, ctx: dict):
    how = spec.get("reduce", "last")
    if how == "sum":
        parts = [read(t, ctx) for t in spec["terms"]]
        if any(p is None for p in parts):
            return None
        return sum(p * t.get("sign", 1)
                   for p, t in zip(parts, spec["terms"]))
    src = spec["from"]
    if src == "client":
        return ctx["client"].get(spec["key"])
    a, b = ctx.get("hb_a"), ctx.get("hb_b")
    if not a or not b:
        return None
    dt = b["time"] - a["time"]

    def delta(key):
        va, vb = dig(a, key), dig(b, key)
        return None if va is None or vb is None else vb - va

    if src == "heartbeat":
        if how == "last":
            return dig(b, spec["key"])
        if how == "hist_mean":
            ha, hb = dig(a, spec["key"]), dig(b, spec["key"])
            if not ha or not hb or hb["count"] == ha["count"]:
                return None
            return ((hb["sum_s"] - ha["sum_s"])
                    / (hb["count"] - ha["count"]))
        d = delta(spec["key"])
        if d is None:
            return None
        if how == "delta_per":
            per = delta(spec["per"])
            return d / per if per else None
        if how == "seconds_per_delta":
            return dt / d if d else None
        if how == "share_of_window":
            return d / dt if dt > 0 else None
    if src == "trace":
        trace = ctx.get("trace")
        if not trace:
            return None
        prog = program(trace, spec["program"])
        if prog is None or prog[0] <= 0:
            return None
        seconds, runs = prog
        if how == "program_us_per_message":
            served = delta("counters.service_records")
            if not served or dt <= 0:
                return None
            return (seconds / trace["window_s"]) / (served / dt) * 1e6
        if how == "program_roofline":
            mod, _, fn = spec["bytes"].partition(":")
            nbytes = getattr(importlib.import_module(mod), fn)(
                ctx["config"])
            peak = peaks.of(ctx["device_kind"])["hbm_bytes_per_s"]
            return runs * nbytes / peak / seconds
    raise ValueError(f"layer metric read not understood: {spec}")
