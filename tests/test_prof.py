"""Continuous profiling & telemetry history (ISSUE 16): the on-disk
TSDB (roundtrip, rotation + sha256 prune, torn-tail recovery, restart
dedup via sample_seq), the sampling stage profiler, trigger captures
with kme-trace-resolvable exemplars, the per-backend transfer artifact,
stage-level regression attribution (kme-prof --diff naming a planted
slowdown), and the planes' invisibility to the MatchOut bytes.
"""

import json
import os
import threading
import time

import pytest

from kme_tpu.bridge.broker import InProcessBroker
from kme_tpu.bridge.provision import provision
from kme_tpu.bridge.service import TOPIC_IN, TOPIC_OUT, MatchService
from kme_tpu.telemetry.profiler import (StageProfiler, TriggerCapture,
                                        read_transfer_artifact,
                                        write_transfer_artifact)
from kme_tpu.telemetry.tsdb import (MAGIC, REC_SIZE, TSDB,
                                    attribute_regression,
                                    format_attribution, iter_samples,
                                    query, read_samples, verify_store,
                                    window_summary)
from kme_tpu.wire import dumps_order
from kme_tpu.workload import harness_stream


def snap(**gauges):
    return {"gauges": gauges}


# ---------------------------------------------------------------------------
# TSDB: append / read roundtrip


def test_tsdb_roundtrip_snapshot_and_values(tmp_path):
    store = str(tmp_path)
    db = TSDB(store, source="serve")
    assert db.append_snapshot(
        {"counters": {"service_records": 10},
         "gauges": {"pipeline_depth": 2},
         "latencies": {"lat_e2e": {"count": 4, "sum_s": 0.1,
                                   "p50_ms": 1.0, "p99_ms": 3.0}}},
        sample_seq=0, ts_us=1_000)
    assert db.append_snapshot(
        {"counters": {"service_records": 25},
         "gauges": {"pipeline_depth": 3},
         "latencies": {"lat_e2e": {"count": 9, "sum_s": 0.3,
                                   "p50_ms": 1.5, "p99_ms": 7.0}}},
        sample_seq=1, ts_us=2_000)
    db.close()

    series = query(store)
    assert series["service_records"] == [(1_000, 10.0), (2_000, 25.0)]
    assert series["pipeline_depth"] == [(1_000, 2.0), (2_000, 3.0)]
    assert series["lat_e2e.p99_ms"] == [(1_000, 3.0), (2_000, 7.0)]
    # per-source reader agrees and names the writer
    rows = list(read_samples(store, source="serve"))
    assert all(r[0] == "serve" for r in rows)
    assert {r[3] for r in rows} >= {"service_records", "lat_e2e.count",
                                    "lat_e2e.p50_ms"}

    # window summary: monotonic names collapse to last-first deltas,
    # plain gauges to the mean
    summ = window_summary(store)
    assert summ["service_records"] == 15.0        # 25 - 10
    assert summ["lat_e2e.count"] == 5.0           # 9 - 4
    assert summ["pipeline_depth"] == 2.5          # mean(2, 3)
    assert summ["lat_e2e.p99_ms"] == 5.0          # mean(3, 7)


def test_tsdb_values_writer_and_dedup(tmp_path):
    db = TSDB(str(tmp_path), source="loadgen")
    assert db.append_values({"loadgen_produced_total": 100,
                             "skipped_bool": True}, db.next_seq())
    # same seq again: the crash-replay dedup drops the whole snapshot
    assert not db.append_values({"loadgen_produced_total": 999}, 0)
    assert db.dup_skipped == 1
    db.close()
    series = query(str(tmp_path))
    assert series["loadgen_produced_total"] == [
        (series["loadgen_produced_total"][0][0], 100.0)]
    assert "skipped_bool" not in series   # bools are not metrics


def test_tsdb_sources_are_isolated_files(tmp_path):
    store = str(tmp_path)
    a = TSDB(store, source="serve")
    b = TSDB(store, source="feed")
    a.append_values({"x": 1}, 0)
    b.append_values({"x": 2}, 0)
    a.close(), b.close()
    assert query(store, source="serve")["x"] == [
        (query(store, source="serve")["x"][0][0], 1.0)]
    assert query(store, source="feed")["x"][0][1] == 2.0
    with pytest.raises(ValueError):
        TSDB(store, source="../evil")


# ---------------------------------------------------------------------------
# rotation, sha256 sidecars, retention prune


def test_tsdb_rotation_prune_and_digests(tmp_path):
    store = str(tmp_path)
    db = TSDB(store, source="serve", rotate_bytes=REC_SIZE * 8, retain=2)
    for i in range(40):
        db.append_values({"service_records": float(i)}, i)
    db.close()

    segs = [p for p in os.listdir(store) if ".kmet." in p
            and not p.endswith(".sha256")]
    assert segs, "rotation never happened"
    # retention: at most `retain` rotated segments survive
    assert len(segs) <= 2
    # every finalized segment carries a verifying sha256 sidecar
    rep = verify_store(store)
    assert rep["segments"] == len(segs)
    assert rep["verified"] == rep["segments"]
    assert rep["mismatched"] == []
    # readers see one continuous, deduplicated series across segments
    pts = query(store, names=["service_records"])["service_records"]
    seqs = [s for _src, _ts, s, _n, _v in read_samples(store)]
    assert len(pts) == len(set(seqs))  # no replays survived rotation

    # corrupt a finalized segment: the audit names it
    seg = os.path.join(store, sorted(segs)[0])
    with open(seg, "r+b") as f:
        f.seek(len(MAGIC) + 4)
        f.write(b"\xff")
    rep = verify_store(store)
    assert rep["mismatched"] == [seg]


def test_tsdb_rotated_cursor_survives_fresh_live_segment(tmp_path):
    """Rotation right before a crash: the fresh live segment is empty,
    so the dedup cursor must be adopted from the newest rotated file."""
    store = str(tmp_path)
    db = TSDB(store, source="serve", rotate_bytes=REC_SIZE * 4)
    for i in range(20):
        db.append_values({"v": float(i)}, i)
    db.close()
    db2 = TSDB(store, source="serve", rotate_bytes=REC_SIZE * 4)
    assert db2.next_seq() == 20
    assert not db2.append_values({"v": 0.0}, 19)  # replay: dropped
    db2.close()


# ---------------------------------------------------------------------------
# torn-tail recovery


def test_tsdb_torn_tail_truncates_to_last_whole_record(tmp_path):
    store = str(tmp_path)
    db = TSDB(store, source="serve")
    db.append_values({"a": 1.0, "b": 2.0}, 0)
    db.append_values({"a": 3.0, "b": 4.0}, 1)
    db.close()
    path = os.path.join(store, "serve.kmet")
    whole = os.path.getsize(path)
    # crash mid-record: append half a record of garbage
    with open(path, "ab") as f:
        f.write(b"\x00" * (REC_SIZE // 2))

    db2 = TSDB(store, source="serve")
    assert db2._torn_bytes == REC_SIZE // 2
    assert os.path.getsize(path) == whole     # tail truncated away
    assert db2.last_seq == 1                  # committed records survive
    db2.append_values({"a": 5.0}, db2.next_seq())
    db2.close()
    series = query(store)
    assert [v for _ts, v in series["a"]] == [1.0, 3.0, 5.0]


def test_tsdb_header_stub_restarts_segment(tmp_path):
    store = str(tmp_path)
    path = os.path.join(store, "serve.kmet")
    os.makedirs(store, exist_ok=True)
    with open(path, "wb") as f:
        f.write(MAGIC[:3])                    # crash inside the header
    db = TSDB(store, source="serve")
    db.append_values({"a": 1.0}, 0)
    db.close()
    assert query(store)["a"][0][1] == 1.0


def test_tsdb_bad_magic_refuses(tmp_path):
    path = os.path.join(str(tmp_path), "serve.kmet")
    with open(path, "wb") as f:
        f.write(b"NOTATSDB" + b"\x00" * 64)
    with pytest.raises(ValueError, match="bad magic"):
        TSDB(str(tmp_path), source="serve")
    with pytest.raises(ValueError, match="not a TSDB segment"):
        list(iter_samples(path))


# ---------------------------------------------------------------------------
# restart dedup: sample_seq rides the checkpoint, TSDB drops replays


def _feed(broker, n=60, seed=3):
    msgs = harness_stream(n, seed=seed, num_accounts=4, num_symbols=2,
                          payout_opcode_bug=False, validate=True)
    for m in msgs:
        broker.produce(TOPIC_IN, None, dumps_order(m))
    return len(msgs)


def test_service_restart_dedups_replayed_heartbeats(tmp_path):
    """A service killed after heartbeating but before checkpointing
    replays its post-snapshot heartbeats on resume; the checkpoint's
    sample_seq cursor makes the TSDB drop them exactly the way the
    broker drops replayed (epoch, out_seq) stamps."""
    ck, store, logd = (str(tmp_path / d) for d in ("ck", "tsdb", "logs"))
    b = InProcessBroker(persist_dir=logd)
    provision(b)
    n = _feed(b)

    svc = MatchService(b, engine="oracle", compat="fixed", batch=16,
                       slots=64, max_fills=32, checkpoint_dir=ck,
                       exactly_once=True, tsdb=store)
    assert svc.run(max_messages=32) == 32
    svc._write_heartbeat(None, 32)            # TSDB-only heartbeats
    svc._write_heartbeat(None, 32)
    svc.checkpoint()                          # snapshot carries the cursor
    seq_at_ckpt = svc.sample_seq
    svc._write_heartbeat(None, 32)            # past the snapshot...
    svc._write_heartbeat(None, 32)
    svc.tsdb.close()
    del svc                                   # ...then SIGKILL

    b2 = InProcessBroker(persist_dir=logd)
    svc2 = MatchService(b2, engine="oracle", compat="fixed", batch=16,
                        slots=64, max_fills=32, checkpoint_dir=ck,
                        exactly_once=True, tsdb=store)
    # the cursor came back from checkpoint extra, NOT the disk tip
    assert svc2.sample_seq == seq_at_ckpt
    svc2._write_heartbeat(None, 32)           # replays seqs 2, 3...
    svc2._write_heartbeat(None, 32)
    assert svc2.tsdb.dup_skipped == 2
    svc2._write_heartbeat(None, 32)           # ...then new ground
    assert svc2.run(max_messages=n - 32) == n - 32
    svc2.close()

    seqs = [s for _src, _ts, s, name, _v in read_samples(store)
            if name == "service_records"]
    assert len(seqs) == len(set(seqs)), "duplicate sample_seq on disk"
    assert max(seqs) >= seq_at_ckpt + 1       # fresh samples landed


def test_plain_restart_adopts_disk_cursor(tmp_path):
    """No checkpoint to continue from: a restarted writer adopts the
    store's high-water mark instead of deduping against itself."""
    store = str(tmp_path / "tsdb")
    counts = []
    for _round in range(2):
        b = InProcessBroker()
        provision(b)
        _feed(b, n=20)
        svc = MatchService(b, engine="oracle", compat="fixed", batch=16,
                           slots=64, max_fills=32, tsdb=store)
        svc.run(max_messages=20)        # run() heartbeats on its own
        svc._write_heartbeat(None, 20)
        assert svc.tsdb.dup_skipped == 0
        svc.close()
        seqs = [s for _src, _ts, s, name, _v in read_samples(store)
                if name == "service_records"]
        assert len(seqs) == len(set(seqs)), "restart replayed a seq"
        counts.append(len(seqs))
    assert counts[1] > counts[0]        # round two kept appending


# ---------------------------------------------------------------------------
# host sampling profiler


def test_stage_profiler_attributes_synthetic_stage():
    """A thread parked inside a function named like the plan scope must
    be attributed to `plan`; unrelated stacks never count."""
    stop = threading.Event()

    def _plan():                       # name matches STAGE_FUNCS["plan"]
        stop.wait(5.0)

    def innocuous():
        stop.wait(5.0)

    threads = [threading.Thread(target=_plan, daemon=True),
               threading.Thread(target=innocuous, daemon=True)]
    for t in threads:
        t.start()
    prof = StageProfiler(interval_s=0.001)
    try:
        for _ in range(50):
            prof.sample_once()
    finally:
        stop.set()
    for t in threads:
        t.join(timeout=2.0)
    assert prof.total >= 50
    fr = prof.stage_fractions()
    assert fr["plan"] == 1.0           # only the _plan stack counted
    assert fr["dispatch"] == 0.0


def test_stage_profiler_publishes_gauges():
    from kme_tpu.telemetry import Registry

    reg = Registry()
    prof = StageProfiler(registry=reg, interval_s=0.001)
    prof.start()
    time.sleep(0.05)
    prof.stop()
    g = reg.snapshot()["gauges"]
    assert g["prof_wall_samples_total"] >= 1
    assert "prof_stage_frac_plan" in g
    assert set(k for k in g if k.startswith("prof_stage_frac_")) == {
        f"prof_stage_frac_{s}"
        for s in ("parse", "plan", "dispatch", "collect", "produce")}


# ---------------------------------------------------------------------------
# trigger capture


def test_trigger_capture_fires_on_p99_exemplar(tmp_path):
    cap = TriggerCapture(str(tmp_path / "caps"), p99_us=1_000,
                         cooldown_s=0.0, max_captures=2)
    # below threshold: armed but silent
    assert cap.maybe_fire(None, [{"e2e_us": 500, "tid": "aa" * 8}]) is None
    ex = {"e2e_us": 5_000, "tid": "deadbeef" * 4, "aid": 3, "oid": 7}
    path = cap.maybe_fire(None, [ex])
    assert path and os.path.exists(path)
    doc = json.load(open(path))
    assert doc["trigger"] == "p99_exemplar" and doc["e2e_us"] == 5_000
    # the exemplar's deterministic tid rides along — kme-trace resolves it
    assert doc["exemplars"][0]["tid"] == "deadbeef" * 4
    assert "kme-trace" in doc["resolve_with"]


def test_trigger_capture_slo_burn_cooldown_and_budget(tmp_path):
    cap = TriggerCapture(str(tmp_path), cooldown_s=3600.0, max_captures=2)
    p1 = cap.maybe_fire("checkpoint_lag", [])
    assert p1 and json.load(open(p1))["trigger"] == "slo_burn"
    # cooldown holds even under a sustained burn
    assert cap.maybe_fire("checkpoint_lag", []) is None
    cap._last_fire = -float("inf")
    assert cap.maybe_fire("checkpoint_lag", [])    # second capture
    cap._last_fire = -float("inf")
    assert cap.maybe_fire("checkpoint_lag", []) is None  # budget spent
    assert cap.captures == 2


# ---------------------------------------------------------------------------
# per-backend transfer artifact


def test_transfer_artifact_merges_in_place(tmp_path):
    path = str(tmp_path / "transfer.json")
    # a previously recorded TPU ratio is already on disk
    with open(path, "w") as f:
        json.dump({"tpu": {"transfer_compute_ratio": 0.4,
                           "h2d_bytes_per_s": 1e10}}, f)
    doc = write_transfer_artifact(path, {"backend": "cpu",
                                         "h2d_bytes_per_s": 2e9,
                                         "flops_per_batch": 1e6})
    assert set(doc) == {"cpu", "tpu"}
    back = read_transfer_artifact(path)
    # CPU CI recorded its own key; the TPU entry is untouched
    assert back["tpu"]["transfer_compute_ratio"] == 0.4
    assert back["cpu"]["h2d_bytes_per_s"] == 2e9
    assert "recorded_at" in back["cpu"]

    with pytest.raises(OSError):
        read_transfer_artifact(str(tmp_path / "missing.json"))
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        f.write("[1, 2]")
    with pytest.raises(ValueError):
        read_transfer_artifact(bad)


# ---------------------------------------------------------------------------
# stage-level regression attribution


def _window(p99_device=2.0, p99_e2e=5.0, frac_dispatch=0.3):
    return {"lat_ingress.p99_ms": 0.4, "lat_plan.p99_ms": 0.6,
            "lat_device.p99_ms": p99_device, "lat_produce.p99_ms": 0.8,
            "lat_e2e.p99_ms": p99_e2e, "prof_stage_frac_parse": 0.1,
            "prof_stage_frac_plan": 0.2,
            "prof_stage_frac_dispatch": frac_dispatch,
            "prof_stage_frac_produce": 0.3}


def test_attribution_names_planted_device_regression():
    """Plant a 2x device-stage slowdown (which also moves e2e): the
    verdict must name `device`, never the e2e symptom."""
    att = attribute_regression(
        _window(), _window(p99_device=4.0, p99_e2e=8.5, frac_dispatch=0.55))
    assert att["suspect"] == "device"
    assert att["stages"][0]["stage"] == "device"
    ev = {e["name"]: e["ratio"] for e in att["stages"][0]["evidence"]}
    assert ev["lat_device.p99_ms"] == 2.0
    txt = format_attribution(att)
    assert "the device stage moved the most" in txt

    # unchanged windows: nobody accused
    att = attribute_regression(_window(), _window())
    assert att["suspect"] is None


def test_kme_prof_diff_names_planted_regression(tmp_path, capsys):
    """End-to-end over real TSDB history: two windows, a planted
    produce-stage slowdown, kme-prof --diff names the stage."""
    from kme_tpu.cli import prof_main

    base, cur = str(tmp_path / "base"), str(tmp_path / "cur")
    for store, p99, frac in ((base, 1.0, 0.2), (cur, 3.0, 0.6)):
        db = TSDB(store, source="serve")
        for i in range(4):
            db.append_snapshot(
                {"gauges": {"prof_stage_frac_produce": frac,
                            "prof_stage_frac_plan": 0.1},
                 "latencies": {
                     "lat_produce": {"p99_ms": p99},
                     "lat_plan": {"p99_ms": 0.5},
                     "lat_e2e": {"p99_ms": 2.0 + p99}}},
                i)
        db.close()
    assert prof_main(["--diff", base, cur, "--json"]) == 0
    att = json.loads(capsys.readouterr().out)
    assert att["suspect"] == "produce"


# ---------------------------------------------------------------------------
# kme-prof query surfaces over a real store


def test_kme_prof_query_csv_and_verify(tmp_path, capsys):
    from kme_tpu.cli import prof_main

    store = str(tmp_path)
    db = TSDB(store, source="serve", rotate_bytes=REC_SIZE * 8)
    for i in range(12):
        db.append_values({"service_records": float(i * 10),
                          "pipeline_depth": 2.0}, i)
    db.close()
    assert prof_main([store, "--names", "service_records"]) == 0
    out = capsys.readouterr().out
    assert "service_records" in out and "n=12" in out
    assert prof_main([store, "--csv", "--names", "pipeline_depth"]) == 0
    rows = capsys.readouterr().out.strip().splitlines()
    assert rows[0] == "name,ts_us,value" and len(rows) == 13
    assert prof_main([store, "--verify"]) == 0
    assert "segment digests verified" in capsys.readouterr().out
    assert prof_main([str(tmp_path / "empty"), "--names", "zzz"]) == 1


def test_kme_top_history_lines(tmp_path):
    from kme_tpu.telemetry.top import history_lines, sparkline

    assert sparkline([]) == ""
    assert len(sparkline(list(range(100)), width=24)) <= 24
    store = str(tmp_path)
    db = TSDB(store, source="serve")
    for i in range(6):
        db.append_snapshot(
            {"counters": {"service_records": i * 100},
             "latencies": {"lat_e2e": {"p99_ms": 1.0 + i}}}, i)
    db.close()
    lines = history_lines(store)
    joined = "\n".join(lines)
    assert "service_records" in joined and "lat_e2e.p99_ms" in joined
    # absent store degrades to a note, never a crash
    assert history_lines(str(tmp_path / "nope")) == []


# ---------------------------------------------------------------------------
# the observability planes are invisible to the matched stream


def test_prof_planes_leave_matchout_byte_identical(tmp_path):
    """One stream served twice, bare and with every always-on plane
    (host sampling profiler, heartbeat + TSDB history, transfer
    artifact, an armed watchpoint): the MatchOut records must be
    byte-identical (COMPAT.md: the wire contract does not move), and
    the history must really have been written."""
    lines = [dumps_order(m) for m in harness_stream(
        1500, seed=7, num_accounts=64, num_symbols=16, validate=True)]

    def serve(**planes):
        broker = InProcessBroker()
        provision(broker)
        for ln in lines:
            broker.produce(TOPIC_IN, None, ln)
        svc = MatchService(broker, engine="oracle", compat="fixed",
                           batch=256, **planes)
        assert svc.run(
            max_messages=len(lines), idle_exit=5.0, health_every=0.05,
            health_file=(str(tmp_path / "serve.health") if planes
                         else None)) == len(lines)
        svc.close()
        out, off = [], 0
        while True:
            recs = broker.fetch(TOPIC_OUT, off, 4096)
            if not recs:
                return out
            out.extend((r.key, r.value) for r in recs)
            off = recs[-1].offset + 1

    store = str(tmp_path / "tsdb")
    bare = serve()
    observed = serve(tsdb=store, profile=True,
                     profile_artifact=str(tmp_path / "xfer.json"),
                     watch=["balance[1]<0"],
                     capture_dir=str(tmp_path / "captures"))
    assert len(bare) > len(lines)
    assert observed == bare, "profiling altered the MatchOut records"
    assert sum(1 for _ in read_samples(store, source="serve")) > 0
