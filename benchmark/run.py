"""One run of one cell of the benchmark, from the client's side of the
served path:

    python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

finds `benchmark/traffic/<cell>.json`, which names its
`benchmark/configs/<configuration>.json`; spawns the server
(`python -m benchmark.host`, a thin host of `kme-serve`'s entry point)
on the chip; feeds it stamped binary frames over loopback TCP while a
consumer follows `MatchOut`; after the window lets it drain and stop,
then judges the durable logs against the plain reference. The last
line of stdout is the result as one JSON object.

This parent never imports jax: the chip belongs to the server. No TPU is
a failed run, never a CPU fall-back. `--allow-cpu` (with a small
`--events`) rehearses the harness under JAX_PLATFORMS=cpu: its line is
marked `"rehearsal": "cpu"` and carries no metric under a device
metric's name. `--control` judges against the configuration's control
reference, which must come out not correct."""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse    # noqa: E402
import json        # noqa: E402
import os          # noqa: E402
import re          # noqa: E402
import shutil      # noqa: E402
import subprocess  # noqa: E402
import sys         # noqa: E402
import threading   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
IDLE_EXIT_S = 5.0       # the server ends itself after this much silence
SERVER_START_S = 600    # a cold first run builds the native library too
DRAIN_S = 240
HB_OPEN_WAIT_S = 10.0   # the server writes a heartbeat every second
# A saturated window that its stream's drain closes before half of
# `--seconds` stands on the work it held, not on the seconds it took: a
# faster program serves the same stream sooner, and the streams cannot
# grow (their books fill: PERF.md section 4). 200,000 orders are about
# 100 batches of 2,048 and, at `--checkpoint-every 16384`, 12 snapshots
# or more (48 at 4,096): one snapshot more or fewer inside the window
# moves a cell whose `checkpoint` is a fifth of its batch by under 2%,
# well inside half of `orders_per_s`'s bound (7.5%). One rule for every
# saturated cell: no traffic-file field, no flag.
MIN_DRAINED_ORDERS = 200_000


class RunFailure(Exception):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(cell: str) -> tuple:
    """(traffic, configuration) of a cell, by the names in the files."""
    path = os.path.join(HERE, "traffic", f"{cell}.json")
    if not os.path.exists(path):
        raise RunFailure(f"no traffic file {path}")
    traffic = load_json(path)
    config = load_json(os.path.join(HERE, "configs",
                                    f"{traffic['config']}.json"))
    return traffic, config


def benchmark_entry(cell: str) -> tuple:
    """(workload entry, end-to-end metrics, per-layer metrics) that
    BENCHMARK.json lists for this cell."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == cell), None)
    if entry is None:
        raise RunFailure(f"BENCHMARK.json has no workload {cell!r}")

    def mine(metrics):
        return [m for m in metrics
                if cell in m.get("workloads", [cell])]

    return entry, mine(bench["end_to_end"]), mine(bench["per_layer"])


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return "(no log)"


def read_text(path: str) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()
    except OSError:
        return ""


def read_json_or_none(path: str, tries: int = 1):
    for k in range(tries):
        try:
            return load_json(path)
        except (OSError, ValueError):
            if k + 1 < tries:
                time.sleep(0.02)
    return None


class OpeningHeartbeat(threading.Thread):
    """Waits, beside the feeding loop, for the first whole heartbeat
    stamped at or after `t_wall` on its own clock (`"time"`: the
    server's wall clock). The file as it stands when the window opens is
    up to a heartbeat period old, so it may hold less than the server
    had done by then: by the first batch and its compilation, in a cell
    whose warm-up is short."""

    def __init__(self, path: str, t_wall: float, stop: threading.Event,
                 wait_s: float = HB_OPEN_WAIT_S):
        super().__init__(daemon=True)
        self.path, self.t_wall, self.stop = path, t_wall, stop
        self.wait_s, self.found = wait_s, None
        self.start()

    def run(self):
        deadline = time.monotonic() + self.wait_s
        while time.monotonic() < deadline:
            hb = read_json_or_none(self.path)
            if hb and hb.get("time", 0.0) >= self.t_wall:
                self.found = hb
                return
            if self.stop.wait(0.05):    # the run has ended
                return

    def get(self) -> dict:
        """The heartbeat; a run in which none came does not stand."""
        self.join()
        if self.found is None:
            raise RunFailure(f"no heartbeat stamped after the window "
                             f"opened came within {self.wait_s:g} s")
        return self.found


def percentile(sorted_values: list, q: float) -> float:
    """Nearest-rank percentile of an ascending list."""
    rank = max(1, -(-len(sorted_values) * q // 100))
    return sorted_values[int(rank) - 1]


def say(msg: str) -> None:
    print(msg, flush=True)


def window_numbers(facts: dict, last_t: list, sent: int,
                   seconds: float) -> tuple:
    """The window's end-to-end numbers from the client's clocks ->
    (metrics, attempted, failed, client numbers). `facts` is what the
    traffic kind returned; `last_t[k]` the fetch time of order k's last
    record. A kind that had a schedule (`due`) is judged on latency from
    the due time, one without on orders completed per second."""
    first, t_open = facts["first"], facts["t_open"]
    begun = min(len(last_t), sent)
    failed = sent - begun
    t_close = t_open + seconds
    done = [t for t in last_t[first:begun] if t_open < t <= t_close]
    metrics, numbers = {}, {}
    if facts["drained"]:
        say("note: the stream ran out inside the window: the window ends "
            "at the drain")
    if "due" not in facts:
        # the window is closed at the last completion inside it, so that
        # it holds whole batches: orders over the time actually measured.
        # It stands if that completion falls at or after half of
        # `seconds`, or if it is the stream's last order (the window
        # closed at the drain: all that was sent completed inside it)
        # and the window held MIN_DRAINED_ORDERS or more. A server that
        # stalled closes its window early without the drain, and fails
        attempted = sent - first
        measured = max(done) - t_open if done else 0.0
        at_drain = (facts["drained"] and failed == 0
                    and t_open < last_t[sent - 1] <= t_close)
        if done and measured >= 0.5 * seconds:
            rule = "closed at the last completion inside it"
        elif at_drain and len(done) >= MIN_DRAINED_ORDERS:
            rule = (f"closed at the drain: {len(done)} orders >= "
                    f"{MIN_DRAINED_ORDERS}")
        else:
            raise RunFailure(
                f"{len(done)} orders completed in the window (the last "
                f"{measured:.3f} s into it, "
                f"{'at' if at_drain else 'not at'} the stream's drain: a "
                f"window stands from {0.5 * seconds:g} s on, or closed "
                f"at the drain on {MIN_DRAINED_ORDERS} orders or more)")
        say(f"window: {len(done)} orders completed in {measured:.3f} s of "
            f"{seconds} s ({rule}); "
            f"{attempted} offered after warm-up, {failed} never completed")
        metrics["orders_per_s"] = len(done) / measured
    else:
        due = facts["due"]
        attempted = len(due)
        lat = sorted((last_t[first + j] - (t_open + d)) * 1e3
                     if first + j < begun else float("inf")
                     for j, d in enumerate(due))
        late = sorted(x * 1e3 for x in facts["late"])
        say(f"window: {len(lat)} latency samples (every order due in "
            f"{seconds} s), "
            f"{sum(1 for x in lat if x == float('inf'))} never completed; "
            f"generator late p50 {percentile(late, 50):.3f} ms "
            f"p99 {percentile(late, 99):.3f} ms max {late[-1]:.3f} ms")
        for frac in (0.25, 0.5, 0.75, 1.0):
            t = t_open + frac * seconds
            say(f"backlog at {frac:g} of the window: "
                f"{sum(1 for d in due if t_open + d <= t)} due, "
                f"{sum(1 for x in last_t[first:begun] if x <= t)} "
                f"completed")
        metrics["p50_ms"] = percentile(lat, 50)
        metrics["p99_ms"] = percentile(lat, 99)
        numbers["gen_late_p99_ms"] = percentile(late, 99)
    # stalls, so that a far-off run can be told from a slow program
    inside = sorted(set(done))
    gaps = [b - a for a, b in zip([t_open] + inside, inside)]
    say(f"longest silence between completions in the window "
        f"{max(gaps, default=0):.3f} s")
    return metrics, attempted, failed, numbers


def run_cell(cell: str, seed: int, seconds: float, trace: bool,
             allow_cpu: bool = False, events: int | None = None,
             control: bool = False, out: str | None = None,
             keep_trace: bool = False, rate: float | None = None,
             host_module: str = "benchmark.host") -> dict:
    """Drive one run; returns the result line as a dict. Raises
    RunFailure when the run cannot stand (no TPU, server died...)."""
    try:
        from kme_tpu.bridge import lease
        from kme_tpu.bridge.broker import BrokerError
        from kme_tpu.bridge.provision import provision
        from kme_tpu.native import load_library

        from benchmark import client, generators, judge, layers
    except ImportError as e:
        raise RunFailure(f"the benchmark runs from a checkout of the "
                         f"repo ({e})")
    traffic, config = load_cell(cell)
    entry, e2e, per_layer = benchmark_entry(cell)
    if rate is not None:
        traffic["rate_per_s"] = rate
    out = os.path.abspath(out or os.path.join(ROOT, "chiprun_out", "bench",
                                              cell))
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    state = os.path.join(out, "state")
    log_path = os.path.join(out, "serve.log")
    hb_path = os.path.join(out, "health.json")
    report_path = os.path.join(out, "host.json")
    trace_dir = os.path.join(out, "trace")
    flag_path = os.path.join(out, "window.open")
    closed_path = os.path.join(out, "window.closed")

    stream_spec = dict(traffic.get("stream") or config["stream"])
    if events is not None:
        stream_spec["events"] = events
    stream = client.Stream(generators.open_stream(
        stream_spec["generator"], stream_spec["events"], seed,
        stream_spec.get("params", {})))

    cmd = [sys.executable, "-m", host_module, "--report", report_path]
    if trace:
        spans = [load_json(p) for p in sorted(
            os.path.join(HERE, "spans", f) for f in os.listdir(
                os.path.join(HERE, "spans")) if f.endswith(".json"))]
        spans_path = os.path.join(out, "spans.json")
        with open(spans_path, "w") as f:
            json.dump(spans, f)
        cmd += ["--trace-dir", trace_dir, "--trace-flag", flag_path,
                "--trace-closed-flag", closed_path,
                "--trace-seconds", str(max(1.0, seconds - 3.0)),
                "--spans", spans_path]
    cmd += ["--"] + config["serve"] + [
        "--listen", "127.0.0.1:0", "--checkpoint-dir", state,
        "--health-file", hb_path, "--idle-exit", str(IDLE_EXIT_S)]
    env = dict(os.environ, PYTHONUNBUFFERED="1",
               # JAX's own compile logging: compilations inside the
               # window are counted from the server's log
               JAX_DEBUG_LOG_MODULES="jax._src.compiler")
    if load_library() is None:
        raise RunFailure("the native host library did not build")
    # ingress stamps and leader stamps share the broker's one fence: the
    # frames carry the epoch the server is about to hold. Read before the
    # server exists, so that its own lease cannot race this
    epoch = lease.current_epoch(state) + 1
    with open(log_path, "ab") as log:
        t_spawn = time.monotonic()
        proc = subprocess.Popen(cmd, stdout=log, stderr=log, env=env,
                                cwd=ROOT)
    cons = prod = None
    hb_stop = threading.Event()
    try:
        def alive():
            if proc.poll() is not None:
                raise RunFailure(f"the server exited rc={proc.returncode} "
                                 f"during the run\n{tail(log_path)}")

        def wait_for(pred, what, timeout):
            deadline = time.monotonic() + timeout
            while True:
                v = pred()
                if v:
                    return v
                alive()
                if time.monotonic() > deadline:
                    raise RunFailure(f"{what}: timed out\n{tail(log_path)}")
                time.sleep(0.02)

        m = wait_for(lambda: re.search(
            r"broker listening on ([\d.]+):(\d+)", read_text(log_path)),
            "broker endpoint", SERVER_START_S)
        host, port = m.group(1), int(m.group(2))
        marks = {"broker listening": time.monotonic()}
        cons = client.Consumer(
            host, port, traffic.get("consumer_pause_ms", 0) / 1e3)
        prod = client.Producer(host, port, stream, epoch,
                               traffic.get("chunk", 1024))
        provision(prod.cli)
        prod.send_to(min(traffic["warmup_messages"], 1024))
        # fail in seconds, not after the stream went through the Pallas
        # interpreter: the start-up line says what the server runs on
        on = wait_for(lambda: re.search(
            r"^kme-serve: engine=.* backend=(\S+) interpret=(\S+) "
            r"device_kind=.* device_count=(\d+)", read_text(log_path),
            re.M), "start-up line", SERVER_START_S).groups()
        if not (on[:2] == ("tpu", "False")
                or (allow_cpu and on[:2] == ("cpu", "True"))):
            raise RunFailure(f"the server came up with backend={on[0]} "
                             f"interpret={on[1]}: not the chip")
        marks["service up (start-up line)"] = time.monotonic()
        if int(on[2]) < entry["chips"] and not allow_cpu:
            raise RunFailure(f"{on[2]} chips, the cell asks for "
                             f"{entry['chips']}")
        # nothing is generated inside the window: the traffic kinds open
        # it only once the stream has been drawn to its end. They do not
        # leave the server idle meanwhile, which ends itself after
        # IDLE_EXIT_S of silence (a slow host drew 600k events later than
        # that after the preamble's batch, and the run was lost)
        window = {}

        def on_open(t_open):
            # the opening snapshot is the first heartbeat written after
            # the window opened; a thread waits for it, so that the
            # feeding loop is not held up
            window["log_at"] = os.path.getsize(log_path)
            if trace:
                open(flag_path, "w").close()
            window["opening"] = OpeningHeartbeat(
                hb_path, time.time() + (t_open - time.monotonic()), hb_stop)

        kind = client.KINDS[traffic["kind"]]
        facts = kind(prod, cons, traffic, seconds, alive, on_open)
        # the window has closed: at `seconds` or, where the stream ran
        # out, at the drain. The closing heartbeat is read here and the
        # trace ends here, so neither takes in the server's idle tail
        if trace:
            open(closed_path, "w").close()
        window["hb_b"] = read_json_or_none(hb_path, 3)
        window["log_end"] = os.path.getsize(log_path)
        t_open = facts["t_open"]
        if t_open is None:
            raise RunFailure("the window never opened")
        window["hb_a"] = window["opening"].get()
        say("heartbeats read, seconds after the window opened: "
            + ", ".join("none" if hb is None else
                        f"{hb['time'] - window['opening'].t_wall:.2f}"
                        for hb in (window["hb_a"], window["hb_b"])))
        setup_s = t_open - T_PROCESS
        marks["stream generated"] = stream.done_t
        marks["first MatchOut record"] = cons.first_t
        marks["window opens"] = t_open
        say("set-up, seconds after the run command started: "
            + ", ".join(f"{k} {v - T_PROCESS:.2f}" for k, v in sorted(
                marks.items(), key=lambda kv: kv[1])))
        # ---- drain: the server ends itself once the input is silent
        try:
            proc.wait(timeout=DRAIN_S)
        except subprocess.TimeoutExpired:
            raise RunFailure(f"the server did not drain and stop in "
                             f"{DRAIN_S} s\n{tail(log_path)}")
        if proc.returncode != 0:
            raise RunFailure(f"the server exited rc={proc.returncode}\n"
                             f"{tail(log_path)}")
    except (BrokerError, OSError) as e:
        raise RunFailure(f"feeding failed ({e!r})\n{tail(log_path)}")
    finally:
        hb_stop.set()
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        if cons is not None:
            cons.stop()
        if prod is not None:
            prod.close()
        if proc.returncode != 0:
            shutil.rmtree(state, ignore_errors=True)    # gigabytes

    metrics, attempted, failed, client_numbers = window_numbers(
        facts, cons.last_t, prod.sent, seconds)
    metrics["setup_s"] = setup_s
    client_numbers["first_output_s"] = (
        None if cons.first_t is None else cons.first_t - t_spawn)
    say(f"slowest produce acknowledgement of the run "
        f"{prod.longest_call_s:.3f} s")

    # ---- what the server says of itself
    log = read_text(log_path)
    hb = read_json_or_none(hb_path) or {}
    report = read_json_or_none(report_path)
    if report is None:
        raise RunFailure(f"no host report\n{tail(log_path)}")
    if not hb.get("closing"):
        # the program's beater thread and its closing heartbeat both
        # write health.json.tmp unguarded (benchmark/host.py serialises
        # them; this is for a host that does not). Then: what the server
        # ran on from the newest whole heartbeat, the final offset from
        # its "processed N records" line (a fresh state directory:
        # records processed == offset)
        done = re.search(r"^kme-serve: processed (\d+) records", log, re.M)
        newest = hb or window["hb_b"] or window["hb_a"]
        if not newest or not done:
            raise RunFailure(f"no final heartbeat\n{tail(log_path)}")
        hb = dict(newest, offset=int(done.group(1)))
        say("note: the server's closing heartbeat lost a race with its "
            "beater thread (a fault of the program); judged on the "
            "newest whole heartbeat and the log's final count")
    fm = re.search(r"kme-serve: metrics (\{.*\})", log)
    final_metrics = json.loads(fm.group(1)) if fm else {}
    with open(log_path, "rb") as f:
        f.seek(window["log_at"])
        in_window = f.read(window["log_end"] - window["log_at"]).decode(
            errors="replace")
    compiles = len(re.findall(r"Finished XLA compilation|CACHE MISS",
                              in_window))
    say(f"compilations inside the window (server log): {compiles}")
    for name in report.get("spans_missing", []):
        say(f"note: span {name!r} no longer resolves in the program")

    # ---- correct
    reference = (config["control"]["reference"] if control
                 else config["reference"])
    checks = judge.judge(state, stream.msgs[:prod.sent], reference,
                         config["expect"], hb, final_metrics, log,
                         allow_cpu)
    for what, value, limit, ok in checks:
        say(f"check {'ok  ' if ok else 'MISS'} {what}: {value!r} "
            f"(limit {limit!r})")
    correct = all(ok for *_, ok in checks)
    shutil.rmtree(state, ignore_errors=True)

    device = dict(report["device"])
    breakdown = None
    if trace:
        tr = report.get("trace") or {"error": "no trace was recorded"}
        if "error" in tr and not allow_cpu:
            raise RunFailure(f"the traced run gave no device numbers: "
                             f"{tr['error']}")
        if "error" in tr:
            say(f"note: {tr['error']}")
            tr = None
        else:
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            breakdown = {"device_ops": tr["device_ops"],
                         "idle_gaps": tr["idle_gaps"]}
            say(f"trace: {tr['traced_s']:.2f} s traced, saved in "
                f"{tr['save_s']:.2f} s; programs "
                f"{json.dumps(tr['programs'])}; host spans "
                f"{json.dumps(tr['span_s'])}")
        ctx = {"hb_a": window["hb_a"], "hb_b": window["hb_b"],
               "client": client_numbers, "trace": tr, "config": config,
               "device_kind": device["kind"]}
        listed = {m["name"]: m for m in per_layer}
        metrics = {}
        for spec in layers.load_for(cell, {m["name"] for m in e2e}):
            value = layers.read(spec["read"], ctx)
            if value is not None:
                metrics[spec["name"]] = value
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        listed = {m["name"]: m for m in e2e}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": {k: {"value": v, "unit": listed[k]["unit"]}
                          for k, v in metrics.items() if k in listed},
              "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    if allow_cpu and device["platform"] != "tpu":
        result["rehearsal"] = "cpu"
        result["metrics"] = {f"cpu_rehearsal.{k}": v
                             for k, v in result["metrics"].items()}
    # every number compared beside its limit, last in the line: what a
    # record of a run that read `correct: false` keeps of it
    result["compared"] = {what: {"value": value, "limit": limit}
                          for what, value, limit, _ok in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal under JAX_PLATFORMS=cpu: waive the "
                         "backend == tpu check, mark the output")
    ap.add_argument("--events", type=int, default=None,
                    help="rehearsal: a shorter stream")
    ap.add_argument("--control", action="store_true",
                    help="judge against the configuration's control "
                         "reference (must report correct: false)")
    ap.add_argument("--rate", type=float, default=None,
                    help="the sweep that fixes a paced cell's rate: "
                         "offer this many orders/s instead of the "
                         "traffic file's")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the profiler's trace in the run "
                         "directory, to be read by hand "
                         "(python -m benchmark.xplane <file>)")
    ap.add_argument("--out", default=None,
                    help="run directory (default chiprun_out/bench/<cell>)")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), args.allow_cpu, args.events,
                          args.control, args.out, args.keep_trace,
                          args.rate)
    except RunFailure as e:
        print(f"benchmark: FAILED: {e}", file=sys.stderr)
        return 1
    if "jax" in sys.modules:
        print("benchmark: FAILED: the parent imported jax", file=sys.stderr)
        return 1
    for what, c in result["compared"].items():
        print(f"compared {what}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
