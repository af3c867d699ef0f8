"""What decides `correct`: after the window has closed and the server
has stopped, the durable logs against the plain reference, and the
server's own account of what it ran on. Every number compared is printed
beside its limit; any miss is `correct: false`.

The plain reference is the program's `NativeOracleEngine` (sequential
C++, independent of the device path; safety code, called and not
copied). The control (`--control`) puts the reference of the
configuration's `control` in its place and must come out not correct."""

from __future__ import annotations

import os
import re

from kme_tpu.bridge.chaos import read_matchout_records
from kme_tpu.native.oracle import NativeOracleEngine
from kme_tpu.wire import dumps_order


def make_reference(spec: dict) -> NativeOracleEngine:
    kw = {k: spec[k] for k in ("book_slots", "max_fills") if k in spec}
    return NativeOracleEngine(spec["compat"], **kw)


def differing(got: list, want: list) -> int:
    """Positions at which two sequences differ, length difference
    included."""
    return (sum(1 for a, b in zip(got, want) if a != b)
            + abs(len(got) - len(want)))


def judge(state: str, sent: list, reference: dict, expect: dict,
          heartbeat: dict, final_metrics: dict, log: str,
          allow_cpu: bool) -> list:
    """-> [(what, value, limit, ok)]. `sent` is every message the broker
    acknowledged, in order."""
    log_dir = os.path.join(state, "broker-log")
    checks = []

    def check(what, value, limit, ok=None):
        checks.append((what, value, limit,
                       value == limit if ok is None else ok))

    rin = read_matchout_records(log_dir, topic="MatchIn")
    check("acknowledged MatchIn records not durable as sent",
          differing([r.value for r in rin], [dumps_order(m) for m in sent]),
          0)
    want = [ln for g in make_reference(reference).process_wire(sent)
            for ln in g]
    rout = read_matchout_records(log_dir)
    got = [f"{r.key} {r.value}" for r in rout]
    check("MatchOut records differing from the reference "
          f"(of {len(want)})", differing(got, want), 0)
    stamps = [(r.epoch, r.out_seq) for r in rout]
    check("unstamped MatchOut records",
          sum(1 for e, s in stamps if e is None or s is None), 0)
    check("duplicate (epoch, out_seq) stamps",
          len(stamps) - len(set(stamps)), 0)
    check("committed input offset - messages acknowledged",
          heartbeat.get("offset", -1) - len(sent), 0)
    backend = (heartbeat.get("backend"), heartbeat.get("interpret"))
    check("backend, interpret", backend, ("tpu", False),
          backend == ("tpu", False)
          or (allow_cpu and backend == ("cpu", True)))
    check("engine in effect", heartbeat.get("engine"), expect["engine"])
    check("pipeline depth in effect", heartbeat.get("pipeline"),
          expect["pipeline"])
    check("heartbeat degraded", heartbeat.get("degraded"), None)
    check("left the device session",
          len(re.findall("continuing on the native engine", log)), 0)
    for key, most in expect.get("metrics_at_most", {}).items():
        v = final_metrics.get(key)
        check(f"final {key}", v, most, v is not None and v <= most)
    return checks
