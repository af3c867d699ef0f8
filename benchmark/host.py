"""The process that holds the chip in every run of the benchmark: a thin
host for `kme_tpu.bridge.serve.main` (the `kme-serve` entry point), the
same layout traced and untraced.

    python -m benchmark.host --report R.json [--trace-dir D --trace-flag F
        --trace-closed-flag C --trace-seconds S --spans SPANS.json]
        -- <kme-serve arguments>

Untraced it only calls `serve.main` (with the program's heartbeat
writes serialised, see `serialise_heartbeats`) and then writes what the
parent cannot know without touching jax: the device as jax reports it and
the peak device memory. With --trace-dir it also wraps the calls listed in
SPANS.json in `jax.profiler.TraceAnnotation`, waits for the parent to
create the flag file (the window has begun), records a profiler trace
for S seconds or until the parent creates the second flag file (the
window has closed: a stream that ran out closes it at the drain, and the
server's idle tail after it is the harness's, not the program's), and
after `serve.main` has returned reduces the trace
(benchmark/xplane.py) into the report. It installs no signal handler:
`--idle-exit` ends the server."""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import os
import sys
import threading
import time


def resolve(target: str):
    """'pkg.mod:Class.attr' -> (owner object, attribute name, function)."""
    mod, _, qual = target.partition(":")
    owner = importlib.import_module(mod)
    *path, name = qual.split(".")
    for p in path:
        owner = getattr(owner, p)
    return owner, name, getattr(owner, name)


def annotated(fn, label: str):
    from jax.profiler import TraceAnnotation

    @functools.wraps(fn)
    def traced(*a, **kw):
        with TraceAnnotation(label):
            return fn(*a, **kw)
    return traced


def install_spans(spans: list) -> list:
    """Wrap each span's target in a TraceAnnotation. Returns the names
    whose target no longer resolves (reported, not fatal)."""
    missing = []
    for span in spans:
        try:
            owner, name, fn = resolve(span["target"])
        except (ImportError, AttributeError):
            missing.append(span["name"])
            continue
        setattr(owner, name, annotated(fn, span["name"]))
    return missing


def serialise_heartbeats() -> bool:
    """A fault of the program, kept from costing a run: the serve loop's
    beater thread and its closing heartbeat both write
    `<health-file>.tmp` and rename it, unguarded. When they meet (about
    one run in a hundred, PR 24) the file is torn and the loser's rename
    raises; where the loser is the serve loop, the server dies after its
    work is done, without its final lines. In every run, traced or not,
    the writes go one at a time and none follows the closing one. False
    when the method is no longer there (then nothing is changed)."""
    try:
        owner, name, write = resolve(
            "kme_tpu.bridge.service:MatchService._write_heartbeat")
    except (ImportError, AttributeError):
        return False
    lock, closed = threading.Lock(), []

    @functools.wraps(write)
    def one_at_a_time(self, path, seen, tick=0, closing=False):
        with lock:
            if closed:
                return None
            if closing:
                closed.append(True)
            return write(self, path, seen, tick, closing)
    setattr(owner, name, one_at_a_time)
    return True


def trace_when_flagged(flag: str, closed_flag: str, trace_dir: str,
                       seconds: float, stop: threading.Event,
                       out: dict) -> None:
    """Wait for the parent's flag file, then trace for `seconds`, or
    until `closed_flag` exists (the measured window has closed) or the
    server has ended, whichever comes first."""
    import jax

    while not os.path.exists(flag):
        if stop.wait(0.02):
            return
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    out["t_start"] = time.time()
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline and not os.path.exists(closed_flag):
        if stop.wait(0.05):
            break
    out["t_stop"] = time.time()
    jax.profiler.stop_trace()
    out["t_saved"] = time.time()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.host")
    ap.add_argument("--report", required=True)
    ap.add_argument("--trace-dir")
    ap.add_argument("--trace-flag")
    ap.add_argument("--trace-closed-flag")
    ap.add_argument("--trace-seconds", type=float, default=5.0)
    ap.add_argument("--spans", help="JSON list of {name, target}")
    ap.add_argument("serve", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    serve_args = args.serve[1:] if args.serve[:1] == ["--"] else args.serve

    from kme_tpu.bridge import serve

    report = {"rc": None, "heartbeats_serialised": serialise_heartbeats()}
    tracer, stop, traced = None, threading.Event(), {}
    if args.trace_dir:
        spans = []
        if args.spans:
            with open(args.spans) as f:
                spans = json.load(f)
        report["spans_missing"] = install_spans(spans)
        tracer = threading.Thread(
            target=trace_when_flagged, daemon=True,
            args=(args.trace_flag, args.trace_closed_flag, args.trace_dir,
                  args.trace_seconds, stop, traced))
        tracer.start()
    try:
        report["rc"] = serve.main(serve_args)
    finally:
        stop.set()
        if tracer is not None:
            tracer.join()
    import jax

    dev = jax.local_devices()[0]
    stats = dev.memory_stats() or {}
    report["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": jax.device_count(),
                        "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    if traced.get("t_saved"):
        from benchmark import xplane

        try:
            report["trace"] = xplane.reduce_dir(
                args.trace_dir, [s["name"] for s in spans])
        except ValueError as e:     # no device operation in the trace
            report["trace"] = {"error": str(e)}
        report["trace"].update(
            traced_s=traced["t_stop"] - traced["t_start"],
            save_s=traced["t_saved"] - traced["t_stop"])
    tmp = args.report + ".tmp"
    with open(tmp, "w") as f:
        json.dump(report, f)
    os.replace(tmp, args.report)
    return report["rc"] or 0


if __name__ == "__main__":
    sys.exit(main())
