#!/usr/bin/env python3
"""chip_smoke.py — the served seq-kernel path, once, on the chip.

The quickest proof that the system still STARTS on a TPU: fresh
`kme-serve --engine seq` children, one at a time (a chip belongs to one
process), fed over loopback TCP by the normal clients and judged on the
bytes of the durable MatchOut log against the native oracle.

  A  fixed-zipf-1k, full size (BASELINE.json config 3): 1024 symbols x
     8192-deep books in HBM, exactly-once, --pipeline 2; the first ~60%
     of the 105,120-message zipf stream as stamped binary frames.
  B  the same command on the same directories: must RESUME at A's
     offset from A's snapshot, take the scan program from the compile
     cache A filled, and finish the stream. Then A+B: byte parity,
     zero duplicate stamps, rej_capacity == 0, offset == messages sent.
  C  java-harness: --compat java, fed by `kme-loadgen --connections 8
     --binary` (the stock exchange_test.js stream); must still be on
     the device session at the end.
  D  kme-serve's default shape (books in VMEM, positions in HBM),
     --pipeline 0, JSON wire from kme-loadgen: 20,000 events of the
     validated harness stream over 1024 symbols x 2048 accounts. A
     start-up proof, not the deployment: that stream is half refusals
     (52% of its trades in the first 49k messages, 89% by 300k: once-
     funded accounts, 513 of the 1024 symbols created) and ends long
     before the position store matters. The deployment is the
     benchmark's configuration fixed-vmem-default (uniform
     zipf_symbol_stream, cell vmem-default-sat).
  (--chips 4)  seqmesh.shard_proof on four chips: parity at shards
     1/2/4, four distinct devices, the lockstep shard_map leg.

This parent never imports jax. Stdout: one JSON line per phase, one
summary line (versions, cache directory, per-phase set-up facts), and
as the LAST line the verdict alone,
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}
— printed, with exit 0, only when every phase passed on a TPU; any
failure, timeout or missing TPU exits non-zero without it. --allow-cpu (a switch of THIS script, for rehearsing it under
JAX_PLATFORMS=cpu at a tiny --events) waives only the backend check.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BUDGET_S = 1150          # the contract allows 1200 s, compile included
T_START = time.monotonic()


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def remaining() -> float:
    return BUDGET_S - (time.monotonic() - T_START)


def tail(path, n=60):
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return "(no log)"


class Children:
    """Every process the smoke starts, so none outlives it."""

    def __init__(self):
        self.procs = []

    def spawn(self, cmd, log_path, env):
        log = open(log_path, "ab")
        try:
            p = subprocess.Popen(cmd, stdout=log, stderr=log, env=env,
                                 cwd=HERE)
        finally:
            log.close()
        self.procs.append(p)
        return p

    def stop_all(self):
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def wait_for(pred, what, proc, log_path, timeout):
    """Poll pred() until truthy; fail if `proc` dies or time runs out."""
    deadline = time.monotonic() + min(timeout, max(remaining(), 1))
    while True:
        v = pred()
        if v:
            return v
        if proc.poll() is not None:
            raise SmokeFailure(
                f"{what}: kme-serve exited rc={proc.returncode} first\n"
                f"{tail(log_path)}")
        if time.monotonic() > deadline:
            raise SmokeFailure(f"{what}: timed out\n{tail(log_path)}")
        time.sleep(0.05)


def wait_exit(proc, what, log_path):
    """Wait for a child to end by itself, inside what is left of the
    budget, and demand exit code 0."""
    try:
        rc = proc.wait(timeout=max(remaining(), 1))
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"{what}: did not finish in time\n"
                           f"{tail(log_path)}")
    check(rc == 0, f"{what}: exited rc={rc}\n{tail(log_path)}")


def listen_addr(log_path):
    try:
        with open(log_path, errors="replace") as f:
            m = re.search(r"broker listening on ([\d.]+):(\d+)", f.read())
    except OSError:
        return None
    return (m.group(1), int(m.group(2))) if m else None


def runs_on(log_path):
    """(backend, interpret) from kme-serve's one start-up line."""
    try:
        with open(log_path, errors="replace") as f:
            m = re.search(r"^kme-serve: engine=.* backend=(\S+) "
                          r"interpret=(\S+)", f.read(), re.M)
    except OSError:
        return None
    return m.groups() if m else None


def read_json(path):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def scan_entries(cache_dir):
    """The compile cache's entries for the seq scan program
    (build_seq_scan's jitted `call_scan`)."""
    return {os.path.basename(p) for p in glob.glob(
        os.path.join(cache_dir, "jit_call_scan-*-cache"))}


def scan_cache_verdict(log):
    """'hit' / 'miss' from JAX's own compile-cache logging in a child's
    log (JAX_DEBUG_LOG_MODULES=jax._src.compiler)."""
    if "CACHE MISS for 'jit_call_scan'" in log:
        return "miss"
    if "Persistent compilation cache hit for 'jit_call_scan'" in log:
        return "hit"
    return None


def serve_phase(name, state_name, kids, out, env, serve_args, feed,
                expect_pipeline, allow_cpu, idle_exit=5.0):
    """One fresh kme-serve child: start, feed, let it drain and exit.
    Returns a dict of what it reported."""
    from kme_tpu.bridge.broker import BrokerError
    from kme_tpu.bridge.tcp import TcpBroker

    state = os.path.join(out, "state", state_name)
    os.makedirs(state, exist_ok=True)
    log_path = os.path.join(out, f"{name}.serve.log")
    hb_path = os.path.join(out, f"{name}.health.json")
    cmd = [sys.executable, "-m", "kme_tpu.cli", "serve",
           "--engine", "seq", "--auto-provision",
           "--listen", "127.0.0.1:0", "--checkpoint-dir", state,
           "--health-file", hb_path, "--idle-exit", str(idle_exit),
           ] + serve_args
    # MatchOut records already durable here (phase B starts on A's
    # log): "first output" means the first one past them
    try:
        with open(os.path.join(state, "broker-log", "MatchOut.log"),
                  "rb") as f:
            out_base = sum(1 for _ in f)
    except OSError:
        out_base = 0
    t0 = time.monotonic()
    proc = kids.spawn(cmd, log_path, env)
    host, port = wait_for(lambda: listen_addr(log_path),
                          f"{name}: broker endpoint", proc, log_path, 120)
    first = {}

    def watch_first_output():
        cli = TcpBroker(host, port)
        try:
            while proc.poll() is None:
                try:
                    if cli.end_offset("MatchOut") > out_base:
                        first["t"] = time.monotonic() - t0
                        return
                except BrokerError as e:   # topic not provisioned yet
                    first["err"] = repr(e)
                time.sleep(0.02)
        finally:
            cli.close()

    watcher = threading.Thread(target=watch_first_output, daemon=True)
    watcher.start()
    try:
        sent = feed(host, port, state)
    except (BrokerError, OSError) as e:
        raise SmokeFailure(f"{name}: feeding failed ({e!r})\n"
                           f"{tail(log_path)}")
    # fail early, not after the whole stream went through the Pallas
    # interpreter: kme-serve's start-up line says what it runs on
    on = wait_for(lambda: runs_on(log_path), f"{name}: start-up line",
                  proc, log_path, 300)
    check(on == ("tpu", "False") or (allow_cpu and on == ("cpu", "True")),
          f"{name}: kme-serve came up with backend={on[0]} "
          f"interpret={on[1]} — not the chip")
    wait_exit(proc, f"{name}: kme-serve (drain + idle exit)", log_path)
    watcher.join(timeout=5)
    wall = time.monotonic() - t0
    hb = read_json(hb_path)
    check(hb is not None and hb.get("closing"),
          f"{name}: no final heartbeat in {hb_path}")
    want_backend = ("tpu",) if not allow_cpu else ("tpu", "cpu")
    check(hb.get("backend") in want_backend
          and hb.get("interpret") is (hb.get("backend") != "tpu"),
          f"{name}: heartbeat says backend={hb.get('backend')!r} "
          f"interpret={hb.get('interpret')!r} — not the chip")
    check(hb.get("engine") == "seq",
          f"{name}: engine in effect is {hb.get('engine')!r}, not the "
          f"device session")
    check(hb.get("pipeline") == expect_pipeline,
          f"{name}: pipeline depth in effect {hb.get('pipeline')!r} != "
          f"{expect_pipeline}")
    check(hb.get("degraded") is None,
          f"{name}: heartbeat degraded: {hb.get('degraded')!r}")
    with open(log_path, errors="replace") as f:
        log = f.read()
    m = re.search(r"kme-serve: metrics (\{.*\})", log)
    check(m is not None, f"{name}: no final metrics line in the log")
    check("t" in first, f"{name}: never saw a MatchOut record "
                        f"({first.get('err', 'no error')})")
    gauges = hb.get("metrics", {}).get("gauges", {})
    return {"phase": name, "sent": sent, "offset": hb["offset"],
            "heartbeat": {k: hb.get(k) for k in (
                "backend", "interpret", "device_kind", "device_count",
                "engine", "pipeline", "compile_cache_dir", "epoch")},
            "metrics": json.loads(m.group(1)),
            # the newest snapshot's device -> host half (fixed mode:
            # engine/seq.py:export_snapshot), as the loop published it
            "snapshot_fetch": {k: gauges[k] for k in (
                "snapshot_fetch_bytes", "snapshot_live_rows",
                "snapshot_fetch_calls", "snapshot_pos_fetch_bytes",
                "snapshot_pos_calls") if k in gauges},
            "start_to_first_matchout_s": round(first["t"], 3),
            "wall_s": round(wall, 3), "state": state, "log": log}


def snapshot_file(state, offset):
    """What the snapshot at `offset` is on disk: its bytes and, from
    its meta, the version and the sections written by their live
    entries (runtime/checkpoint.py:save_seq_session)."""
    import numpy as np

    path = os.path.join(state, f"ckpt-{offset}.npz")
    with np.load(path) as z:
        meta = json.loads(bytes(z["meta"]).decode())
    layout = meta.get("layout", {})
    return {"bytes": os.path.getsize(path), "version": meta["version"],
            "sparse": layout.get("sparse", []),
            "live_slots": layout.get("live_slots"),
            "live_positions": layout.get("live_positions")}


def verify_log(name, state, oracle, expect_in=None):
    """The durable MatchOut log against the oracle run over the durable
    MatchIn log — bytes, order, stamps."""
    from kme_tpu.bridge.chaos import read_matchout_records
    from kme_tpu.wire import dumps_order, parse_order

    log_dir = os.path.join(state, "broker-log")
    rin = read_matchout_records(log_dir, topic="MatchIn")
    if expect_in is not None:
        check([r.value for r in rin] == [dumps_order(m) for m in expect_in],
              f"{name}: the MatchIn log is not the stream that was sent "
              f"({len(rin)} records vs {len(expect_in)})")
    msgs = [parse_order(r.value) for r in rin]
    want = [ln for g in oracle.process_wire(msgs) for ln in g]
    rout = read_matchout_records(log_dir)
    got = [f"{r.key} {r.value}" for r in rout]
    if got != want:
        bad = next((i for i, (a, b) in enumerate(zip(got, want)) if a != b),
                   min(len(got), len(want)))
        raise SmokeFailure(
            f"{name}: MatchOut diverges from the oracle at record {bad} "
            f"of {len(want)} (got {len(got)}):\n  got  "
            f"{got[bad] if bad < len(got) else '<end>'}\n  want "
            f"{want[bad] if bad < len(want) else '<end>'}")
    stamps = [(r.epoch, r.out_seq) for r in rout]
    check(all(e is not None and s is not None for e, s in stamps),
          f"{name}: unstamped MatchOut records (exactly-once is off?)")
    check(len(set(stamps)) == len(stamps),
          f"{name}: {len(stamps) - len(set(stamps))} duplicate "
          f"(epoch, out_seq) stamps on MatchOut")
    return {"messages": len(msgs), "records": len(got),
            "parity": "byte-exact", "duplicate_stamps": 0}


def frame_feeder(msgs, lo, hi):
    """Send msgs[lo:hi] as stamped binary frames, the call kme-loadgen
    --binary --connections uses. The stamp epoch is the one the serving
    leader is about to hold: ingress stamps and leader stamps share the
    broker's one fence."""
    def feed(host, port, state):
        from kme_tpu.bridge import lease
        from kme_tpu.bridge.provision import provision
        from kme_tpu.bridge.tcp import TcpBroker
        from kme_tpu.wire import encode_frames

        epoch = lease.current_epoch(state) + 1
        cli = TcpBroker(host, port)
        try:
            provision(cli)      # idempotent, as kme-loadgen does
            for i in range(lo, hi, 1024):
                chunk = msgs[i:min(i + 1024, hi)]
                n, _last = cli.produce_frames(
                    "MatchIn", None, encode_frames(chunk), epoch=epoch,
                    seq0=i)
                check(n == len(chunk),
                      f"broker kept {n} of {len(chunk)} frames at {i}")
        finally:
            cli.close()
        return hi - lo
    return feed


def loadgen_feeder(kids, out, name, env, args):
    """Feed through a kme-loadgen child (jax-free, like this parent)."""
    def feed(host, port, _state):
        log_path = os.path.join(out, f"{name}.loadgen.log")
        p = kids.spawn([sys.executable, "-m", "kme_tpu.cli", "loadgen",
                        "--broker", f"{host}:{port}"] + args, log_path, env)
        wait_exit(p, f"{name}: kme-loadgen", log_path)
        m = re.search(r"(?:produced|:) (\d+) records", tail(log_path))
        check(m is not None, f"{name}: kme-loadgen reported no count")
        return int(m.group(1))
    return feed


def shards_phase(kids, out, env):
    """Four chips: the only entry that reaches SeqMeshSession. A child
    runs seqmesh.shard_proof (8 symbols x 128 accounts x 128 slots,
    VMEM books), which raises unless the bytes match the oracle at
    shards 1/2/4 with migrations above one shard, the four shard
    states sit on four devices, and the lockstep leg matches too."""
    log_path = os.path.join(out, "S.proof.log")
    p = kids.spawn([sys.executable, "-c",
                    "import json; from kme_tpu.parallel.seqmesh import "
                    "shard_proof; print(json.dumps(shard_proof()))"],
                   log_path, env)
    wait_exit(p, "S: shard_proof", log_path)
    proof = None
    with open(log_path, errors="replace") as f:
        for line in f:
            if line.startswith('{"backend"'):
                proof = json.loads(line)
    check(proof is not None, "S: shard_proof printed no result")
    check(proof["backend"] == "tpu",
          f"S: shard_proof ran on {proof['backend']!r}")
    devs = proof["shard_devices"]
    check(len(devs) == 4 and len(set(devs)) == 4,
          f"S: shard states not on four distinct devices: {devs}")
    check(proof["dispatch"] == "async"
          and proof["shard_counts"] == [1, 2, 4]
          and proof["parity"] == proof["lockstep_parity"] == "byte-exact",
          f"S: async parity at shards 1/2/4 or the lockstep leg "
          f"missing: {proof}")
    return {"phase": "S", "shard_devices": devs,
            "device_kind": proof["device_kind"],
            "shard_counts": proof["shard_counts"],
            "parity": proof["parity"], "dispatch": proof["dispatch"],
            "migrations": proof["migrations"],
            "lockstep_leg": proof["lockstep_parity"],
            "size": "8 symbols x 128 accounts x 128 slots"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--events", type=int, default=100_000,
                    help="zipf events in A+B (C and D use min(events, "
                         "20000)); cut only to rehearse on the CPU")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--out", default=os.path.join(HERE, "chip_smoke_out"))
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal under JAX_PLATFORMS=cpu: waive the "
                         "backend == tpu check (and nothing else)")
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    try:
        from kme_tpu.native import load_library
        from kme_tpu.native.oracle import NativeOracleEngine
        from kme_tpu.workload import zipf_symbol_stream
    except ImportError as e:
        print(f"chip_smoke: this script runs from a checkout of the "
              f"repo ({e})", file=sys.stderr)
        return 2
    out = os.path.abspath(args.out)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    kids = Children()
    env = dict(os.environ, PYTHONUNBUFFERED="1",
               # JAX's own cache-hit/miss logging, for phase B's check
               JAX_DEBUG_LOG_MODULES="jax._src.compiler")
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or os.path.join(HERE, ".jax_cache"))
    phases = []

    def done(rec):
        rec = {k: v for k, v in rec.items() if k not in ("log", "state")}
        phases.append(rec)
        print(json.dumps(rec), flush=True)

    try:
        check(load_library() is not None,
              "the native host library did not build (g++ output above)")
        n_small = min(args.events, 20_000)

        # ---- A + B: fixed-zipf-1k at full size, stopped and resumed
        msgs = zipf_symbol_stream(args.events, num_symbols=1024,
                                  num_accounts=2048, seed=args.seed,
                                  zipf_a=1.2)
        cut = int(len(msgs) * 0.6)
        # a snapshot of this state is ~450 MB and takes seconds (see
        # CHANGES.md, PR 21): every 16384 records, not the default 4096
        ab_args = ["--compat", "fixed", "--symbols", "1024",
                   "--accounts", "2048", "--slots", "8192",
                   "--max-fills", "16", "--batch", "2048",
                   "--pipeline", "2", "--checkpoint-every", "16384"]
        scans_0 = scan_entries(cache_dir)
        a = serve_phase("A", "AB", kids, out, env, ab_args,
                        frame_feeder(msgs, 0, cut), 2, args.allow_cpu)
        check(a["offset"] == cut,
              f"A: committed offset {a['offset']} != {cut} sent")
        check(a["heartbeat"]["compile_cache_dir"] == cache_dir,
              f"A: compile cache at "
              f"{a['heartbeat']['compile_cache_dir']!r}, expected "
              f"{cache_dir!r}")
        # cold (the driver's fresh checkout): A compiles the scan
        # program and must leave it in the cache; a cache that was
        # already warm is a hit here too
        scans_a = scan_entries(cache_dir)
        a["scan_cache"] = scan_cache_verdict(a["log"])
        check(a["scan_cache"] is not None and scans_a
              and (a["scan_cache"] == "hit" or len(scans_a) > len(scans_0)),
              f"A: scan program compile was a {a['scan_cache']} and the "
              f"compile cache {cache_dir} holds {len(scans_0)} -> "
              f"{len(scans_a)} scan entries")
        # (none published where the stream ended before the first
        # snapshot inside it: a rehearsal's 900 messages)
        check(a["snapshot_fetch"].get("snapshot_fetch_calls", 1) >= 1,
              f"A: the books of the newest snapshot did not cross by "
              f"their live rows ({a['snapshot_fetch']})")
        check(a["snapshot_fetch"].get("snapshot_pos_calls", 1) >= 1,
              f"A: the positions of the newest snapshot did not cross "
              f"by their live entries ({a['snapshot_fetch']})")
        done(a)

        # read before B's own snapshots prune it
        a_snapshot = snapshot_file(a["state"], cut)
        b = serve_phase("B", "AB", kids, out, env, ab_args,
                        frame_feeder(msgs, cut, len(msgs)), 2,
                        args.allow_cpu)
        m = re.search(r"resumed from snapshot at offset (\d+)", b["log"])
        check(m is not None and "skipping unreadable snapshot"
              not in b["log"],
              "B: did not resume from A's snapshot (started over?)")
        b["resumed_at"] = int(m.group(1))
        b["resumed_from"] = a_snapshot
        check(b["resumed_from"]["sparse"] == ["books", "positions"],
              f"B: A's snapshot was not written by its live entries "
              f"({b['resumed_from']})")
        check(b["resumed_at"] == cut > 0,
              f"B: resumed at {b['resumed_at']}, A committed {cut}")
        check(b["offset"] == len(msgs),
              f"B: committed offset {b['offset']} != {len(msgs)} sent")
        b["scan_cache"] = scan_cache_verdict(b["log"])
        check(b["scan_cache"] == "hit"
              and scan_entries(cache_dir) == scans_a,
              f"B: the scan program did not come from the compile "
              f"cache ({b['scan_cache']}; scan entries {len(scans_a)} "
              f"-> {len(scan_entries(cache_dir))})")
        check(b["metrics"].get("rej_capacity") == 0,
              f"A+B: rej_capacity = {b['metrics'].get('rej_capacity')}")
        b["rej_capacity"] = 0
        b["max_book_depth"] = b["metrics"].get("max_book_depth")
        b.update(verify_log("A+B", b["state"], NativeOracleEngine(
            "fixed", book_slots=8192, max_fills=16), expect_in=msgs))
        done(b)
        shutil.rmtree(b["state"], ignore_errors=True)   # ~1.4 GB
        del msgs

        # ---- C: java-harness through the normal binary client
        c = serve_phase(
            "C", "C", kids, out, env,
            ["--compat", "java", "--symbols", "8", "--accounts", "128",
             "--slots", "8192", "--max-fills", "128"],
            loadgen_feeder(kids, out, "C", env,
                           ["--events", str(n_small), "--seed",
                            str(args.seed), "--connections", "8",
                            "--binary"]), 0, args.allow_cpu)
        check("continuing on the native engine" not in c["log"],
              "C: the java stream left the device session")
        check(c["offset"] == c["sent"],
              f"C: committed offset {c['offset']} != {c['sent']} sent")
        c.update(verify_log("C", c["state"], NativeOracleEngine("java")))
        done(c)

        # ---- D: kme-serve's default shape, serial, JSON wire (a start-up
        # proof on a stream that is half refusals; see the docstring)
        d = serve_phase(
            "D", "D", kids, out, env, ["--pipeline", "0"],
            loadgen_feeder(kids, out, "D", env,
                           ["--events", str(n_small), "--seed",
                            str(args.seed), "--symbols", "1024",
                            "--accounts", "2048", "--validate",
                            "--fix-payout-opcode"]), 0, args.allow_cpu)
        check(d["offset"] == d["sent"],
              f"D: committed offset {d['offset']} != {d['sent']} sent")
        d.update(verify_log("D", d["state"], NativeOracleEngine(
            "fixed", book_slots=128, max_fills=16)))
        done(d)

        if args.chips == 4:
            done(shards_phase(kids, out, env))

        check("jax" not in sys.modules, "the parent imported jax")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        kids.stop_all()
        shutil.rmtree(os.path.join(out, "state"), ignore_errors=True)

    from importlib import metadata

    def version(pkg):
        try:
            return metadata.version(pkg)
        except metadata.PackageNotFoundError:
            return None

    hb = phases[0]["heartbeat"]
    device = {"platform": hb["backend"], "kind": hb["device_kind"],
              "count": hb["device_count"]}
    summary = {
        "summary": "chip_smoke",
        "device": device,
        "chips": args.chips,
        "versions": {p: version(p) for p in ("jax", "jaxlib", "libtpu")},
        "compile_cache_dir": cache_dir,
        "seed": args.seed, "events": args.events,
        "wall_s": round(time.monotonic() - T_START, 1),
        "phases": {p["phase"]: {k: p.get(k) for k in (
            "messages", "records", "parity", "start_to_first_matchout_s",
            "wall_s", "resumed_at", "resumed_from", "snapshot_fetch",
            "scan_cache",
            "rej_capacity",
            "max_book_depth", "duplicate_stamps", "shard_devices")
            if p.get(k) is not None} for p in phases},
    }
    with open(os.path.join(out, "summary.json"), "w") as f:
        json.dump({"summary": summary, "phases": phases}, f, indent=1)
    print(json.dumps(summary), flush=True)
    # the LAST stdout line is the verdict alone: exactly these keys, the
    # device as the serving child's jax reported it
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
