"""kme-top: source scraping (metrics URL vs heartbeat file), view
derivation (rates, replica lag), the pure renderer, and a live smoke
against a running leader + standby pair."""

import json
import os
import threading
import time

import pytest

from kme_tpu.bridge.broker import InProcessBroker
from kme_tpu.bridge.provision import provision
from kme_tpu.bridge.replica import Replica
from kme_tpu.bridge.service import TOPIC_IN, MatchService
from kme_tpu.telemetry import start_metrics_server
from kme_tpu.telemetry.top import (build_view, collect, main, render,
                                   scrape)
from kme_tpu.wire import dumps_order
from kme_tpu.workload import harness_stream


# ---------------------------------------------------------------------------
# scraping


def test_scrape_heartbeat_file_vs_registry_snapshot(tmp_path):
    hb = str(tmp_path / "hb.json")
    with open(hb, "w") as f:
        json.dump({"role": "leader", "offset": 7, "degraded": None,
                   "metrics": {"counters": {"service_records": 7},
                               "gauges": {}, "latencies": {}}}, f)
    out = scrape(hb)
    assert out["ok"] and out["hb"]["offset"] == 7
    assert out["metrics"]["counters"]["service_records"] == 7

    snap = str(tmp_path / "snap.json")
    with open(snap, "w") as f:
        json.dump({"counters": {"service_records": 3}, "gauges": {},
                   "histograms": {}, "latencies": {}}, f)
    out = scrape(snap)              # bare registry snapshot, no hb
    assert out["ok"] and "hb" not in out
    assert out["metrics"]["counters"]["service_records"] == 3


def test_scrape_missing_sources_are_soft():
    assert scrape(None)["ok"] is False
    out = scrape("/nonexistent/path.json")
    assert out["ok"] is False and "error" in out
    out = scrape("http://127.0.0.1:9/", timeout=0.2)   # closed port
    assert out["ok"] is False and "error" in out
    # an unreachable node must not crash the frame
    view = build_view(collect("/nonexistent", None, None))
    assert any("unreachable" in ln for ln in render(view))


# ---------------------------------------------------------------------------
# view derivation + rendering (pure)


def _node(records=None, gauges=None, lats=None, hb=None):
    m = {"counters": ({} if records is None
                      else {"service_records": records}),
         "gauges": gauges or {}, "latencies": lats or {}}
    out = {"source": "x", "ok": True, "metrics": m}
    if hb is not None:
        out["hb"] = hb
    return out


def test_build_view_rate_and_lag():
    prev = {"t": 0.0, "leader": _node(records=100),
            "standby": _node(), "supervisor": None}
    cur = {"t": 2.0, "leader": _node(records=300),
           "standby": _node(gauges={"replica_lag_records": 5}),
           "supervisor": None}
    view = build_view(cur, prev)
    assert view["records_per_s"] == pytest.approx(100.0)
    assert view["replica_lag"] == 5
    # lag falls back to heartbeat applied/leader_offset
    cur["standby"] = _node(hb={"applied": 40, "leader_offset": 52})
    assert build_view(cur, prev)["replica_lag"] == 12
    # no prev sample -> no rate, never a crash
    assert build_view(cur)["records_per_s"] is None


def test_serve_row_shows_cpu_beside_wall_where_published():
    def at(t, loop, cpu, proc):
        return {"t": t, "leader": _node(records=10, gauges={
            "serve_loop_s": loop, "serve_cpu_s": cpu,
            "process_cpu_s": proc}), "standby": _node(),
            "supervisor": None}

    prev, cur = at(0.0, 10.0, 4.0, 11.0), at(2.0, 12.0, 5.5, 13.2)
    view = build_view(cur, prev)
    # between the refreshes: per second of the loop's own wall
    assert view["serve_cpu_share"] == pytest.approx(0.75)
    assert view["process_cpu_cores"] == pytest.approx(1.1)
    lead = next(ln for ln in render(view) if ln.startswith("leader"))
    assert "loop_cpu=75%" in lead and "process=1.10cores" in lead
    # one frame: since the service started
    assert build_view(cur)["serve_cpu_share"] == pytest.approx(5.5 / 12)
    # a restarted leader (the gauges ran backwards) reads its own start
    assert build_view(at(3.0, 1.0, 0.5, 0.9), cur)["serve_cpu_share"] \
        == pytest.approx(0.5)
    # a leader that publishes neither: the row is as it was
    old = build_view({"t": 1.0, "leader": _node(records=10),
                      "standby": _node(), "supervisor": None})
    assert old["serve_cpu_share"] is None
    assert "loop_cpu" not in "\n".join(render(old))


def test_render_shows_stages_slo_and_supervisor():
    lats = {"lat_e2e": {"count": 10, "sum_s": 0.1, "p50_ms": 4.0,
                        "p90_ms": 8.0, "p99_ms": 9.0, "p999_ms": 9.5},
            "lat_ingress": {"count": 10, "sum_s": 0.01, "p50_ms": 0.5,
                            "p90_ms": 1.0, "p99_ms": 2.0,
                            "p999_ms": 2.5}}
    view = build_view({
        "t": 1.0,
        "leader": _node(records=10,
                        gauges={"slo_ok": 0, "slo_burn_rate": 3.5},
                        lats=lats,
                        hb={"epoch": 2, "offset": 9,
                            "degraded": "slo burn 3.5x"}),
        "standby": _node(hb={"applied": 8, "leader_offset": 9,
                             "out_seq": 4, "discarded": 0}),
        "supervisor": {"restarts_total": 1, "budget_used": 1,
                       "max_restarts": 5, "standby_restarts": 0,
                       "recoveries": [{"t": 1.0, "kind": "leader"}]}})
    text = "\n".join(render(view))
    assert "epoch=2" in text and "offset=9" in text
    assert "DEGRADED: slo burn 3.5x" in text
    assert "slo=BREACH burn=3.50x" in text
    assert "e2e" in text and "ingress" in text and "9.500" in text
    assert "applied=8" in text and "lag=1" in text
    assert "restarts=1" in text and "kind=leader" in text
    # empty view renders too (all sources down)
    assert render(build_view(collect(None, None, None)))


def test_render_shows_shard_rows():
    """Per-shard straggler attribution (SeqMeshSession gauges): the
    shard section appears iff shard_count is present, with occupancy
    and the device_shard{N} quantiles per row."""
    lats = {"device_shard0": {"count": 90, "sum_s": 0.4, "p50_ms": 3.0,
                              "p90_ms": 5.0, "p99_ms": 6.0,
                              "p999_ms": 6.5},
            "device_shard1": {"count": 30, "sum_s": 0.1, "p50_ms": 1.0,
                              "p90_ms": 1.5, "p99_ms": 2.0,
                              "p999_ms": 2.2}}
    node = _node(records=120,
                 gauges={"shard_count": 2, "shard_imbalance": 1.5,
                         "shard0_occupancy": 90,
                         "shard1_occupancy": 30})
    node["metrics"]["counters"].update(
        {"shard_migrations_total": 3, "shard_rebalances_total": 1})
    node["metrics"]["latencies"] = lats
    view = build_view({"t": 1.0, "leader": node, "standby": _node(),
                       "supervisor": None})
    text = "\n".join(render(view))
    assert "shards=2" in text
    assert "imbalance=1.500" in text
    assert "migrations=3" in text and "rebalances=1" in text
    assert "occupancy" in text
    # one row per shard: occupancy gauge + p50/p99 from the summary
    row0 = next(ln for ln in text.splitlines()
                if ln.strip().startswith("0 "))
    assert "90" in row0 and "3.000" in row0 and "6.000" in row0
    row1 = next(ln for ln in text.splitlines()
                if ln.strip().startswith("1 "))
    assert "30" in row1 and "2.000" in row1
    # without the gauge the section stays hidden
    plain = "\n".join(render(build_view(
        {"t": 1.0, "leader": _node(records=1), "standby": _node(),
         "supervisor": None})))
    assert "shards=" not in plain


def test_render_shows_group_section():
    """Multi-leader shard group (bridge/front.py): the group line
    appears iff group_count > 1, with the leader's lag and the
    cross-shard transfer gauges + RTT quantiles."""
    node = _node(records=50,
                 gauges={"group_id": 1, "group_count": 4,
                         "group1_lag": 7,
                         "cross_shard_transfers_total": 12,
                         "cross_shard_transfer_volume": 90000,
                         "balance_broadcasts_total": 3})
    node["metrics"]["latencies"] = {
        "transfer_rtt": {"count": 12, "sum_s": 0.02, "p50_ms": 1.1,
                         "p90_ms": 2.0, "p99_ms": 3.3, "p999_ms": 3.5}}
    text = "\n".join(render(build_view(
        {"t": 1.0, "leader": node, "standby": _node(),
         "supervisor": None})))
    assert "group=1/4" in text
    assert "lag=7" in text
    assert "xfers=12" in text and "volume=90,000" in text
    assert "transfer_rtt" in text and "p99=3.300ms" in text
    # a single-group leader renders no group section
    solo = _node(records=1, gauges={"group_id": 0, "group_count": 1})
    plain = "\n".join(render(build_view(
        {"t": 1.0, "leader": solo, "standby": _node(),
         "supervisor": None})))
    assert "group=" not in plain


def test_main_once_plain_frame_with_shards(tmp_path, capsys):
    """--once over a heartbeat file carrying the mesh session's shard
    gauges prints the shard rows in the plain frame."""
    hb = str(tmp_path / "serve.health")
    with open(hb, "w") as f:
        json.dump({"role": "leader", "offset": 5, "epoch": 1,
                   "degraded": None,
                   "metrics": {
                       "counters": {"service_records": 5,
                                    "shard_migrations_total": 2,
                                    "shard_rebalances_total": 1},
                       "gauges": {"shard_count": 2,
                                  "shard_imbalance": 1.18,
                                  "shard0_occupancy": 40,
                                  "shard1_occupancy": 60},
                       "latencies": {
                           "device_shard0": {"count": 40, "sum_s": 0.1,
                                             "p50_ms": 2.0,
                                             "p90_ms": 3.0,
                                             "p99_ms": 4.0,
                                             "p999_ms": 4.4},
                           "device_shard1": {"count": 60, "sum_s": 0.2,
                                             "p50_ms": 2.5,
                                             "p90_ms": 3.5,
                                             "p99_ms": 4.5,
                                             "p999_ms": 5.0}}}}, f)
    rc = main(["--leader", hb, "--once", "--no-rate-sample"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "shards=2" in out and "imbalance=1.180" in out
    assert "migrations=2" in out


def test_main_requires_a_source():
    with pytest.raises(SystemExit):
        main(["--once"])


def test_main_state_root_once_over_files(tmp_path, capsys):
    root = str(tmp_path)
    with open(os.path.join(root, "serve.health"), "w") as f:
        json.dump({"role": "leader", "offset": 3, "epoch": 1,
                   "degraded": None,
                   "metrics": {"counters": {"service_records": 3},
                               "gauges": {}, "latencies": {}}}, f)
    with open(os.path.join(root, "supervisor.json"), "w") as f:
        json.dump({"restarts_total": 0, "budget_used": 0,
                   "max_restarts": 5, "standby_restarts": 0,
                   "recoveries": []}, f)
    rc = main(["--state-root", root, "--once", "--no-rate-sample"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "offset=3" in out and "restarts=0" in out
    assert "standby" in out      # missing standby.health shown as down


# ---------------------------------------------------------------------------
# live smoke: leader + standby pair (ISSUE acceptance)


def test_top_live_leader_standby_pair(tmp_path, capsys):
    ck = str(tmp_path / "ck")
    os.makedirs(ck)
    log_dir = os.path.join(ck, "broker-log")
    msgs = [dumps_order(m) for m in harness_stream(
        80, seed=7, num_accounts=4, num_symbols=2,
        payout_opcode_bug=False, validate=True)]

    br = InProcessBroker(persist_dir=log_dir)
    provision(br)
    for m in msgs:
        br.produce(TOPIC_IN, None, m)
    leader = MatchService(br, engine="oracle", compat="fixed",
                          batch=16, slots=64, max_fills=32,
                          checkpoint_dir=ck, exactly_once=True)
    leader.run(max_messages=len(msgs))
    serve_health = os.path.join(ck, "serve.health")
    leader._write_heartbeat(serve_health, len(msgs))
    msrv = start_metrics_server(leader.telemetry, 0, host="127.0.0.1")
    lh, lp = msrv.server_address[:2]

    standby_health = os.path.join(ck, "standby.health")
    rep = Replica(ck, listen="127.0.0.1:0", engine="oracle", batch=16,
                  slots=64, max_fills=32, poll=0.02, health_every=0.05,
                  idle_exit=0.4, health_file=standby_health,
                  metrics_port=0)
    rc = [None]
    t = threading.Thread(target=lambda: rc.__setitem__(0, rep.run()),
                         daemon=True)
    t.start()
    try:
        deadline = time.monotonic() + 10.0
        while (not os.path.exists(standby_health)
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert os.path.exists(standby_health), "standby never heartbeat"

        code = main(["--leader", f"http://{lh}:{lp}",
                     "--standby", standby_health,
                     "--supervisor", os.path.join(ck,
                                                  "supervisor.json"),
                     "--once", "--interval", "0.05"])
        assert code == 0
        out = capsys.readouterr().out
        # leader metrics surface: throughput + the stage table
        assert f"records={len(msgs):,}" in out
        assert "e2e" in out and "p99 ms" in out
        # standby heartbeat surfaced with applied offset + lag
        assert "standby  applied=" in out
        assert "unreachable" not in out.split("standby")[1]

        # the standby's own metrics URL also scrapes (replica gauges)
        sh, sp = rep.metrics_server.server_address[:2]
        node = scrape(f"http://{sh}:{sp}")
        assert node["ok"]
        assert "replica_applied_offset" in node["metrics"]["gauges"]
    finally:
        # the follow loop only exits via promotion: issue a pid-less
        # (manual) promote order, after which idle_exit winds it down
        leader.close()
        msrv.shutdown()
        with open(rep.promote_file, "w") as f:
            json.dump({"failed_at": time.time()}, f)
        t.join(timeout=30)
        if rep.metrics_server is not None:
            rep.metrics_server.shutdown()
    assert rc[0] == 0


def test_feed_section_gated_on_feed_gauges():
    """The feed tier renders iff a scraped feed source carries the
    feed gauges (ISSUE 13); absent feeds leave the frame unchanged."""
    from kme_tpu.telemetry.top import feed_lines

    feed = _node(gauges={"feed_subscribers": 12, "feed_group": 0,
                         "feed_offset": 900})
    feed["metrics"]["counters"] = {
        "feed_frames_total": 300, "feed_delivered_total": 3600,
        "feed_conflated_frames_total": 400,
        "feed_conflations_total": 2, "feed_resyncs_total": 2,
        "feed_snapshots_served_total": 12,
        "feed_disconnects_total": 1}
    feed["metrics"]["latencies"] = {
        "feed_lag": {"count": 3600, "sum_s": 1.0, "p50_ms": 0.8,
                     "p90_ms": 2.0, "p99_ms": 4.5, "p999_ms": 9.0}}
    view = build_view({"t": 1.0, "leader": _node(records=5),
                       "standby": _node(), "supervisor": None,
                       "feed": feed})
    text = "\n".join(render(view))
    assert "feed     subs=12" in text
    assert "conflation rate=10.0%" in text     # 400 / (3600 + 400)
    assert "feed_lag p50=0.800ms p99=4.500ms" in text
    assert "snapshots=12" in text and "disconnects=1" in text
    # indent-prefixed variant used by the --cluster frame
    assert feed_lines(feed, indent="  ")[0].startswith("  feed")
    # no feed source (or one without the gauges): section absent
    view = build_view({"t": 1.0, "leader": _node(records=5),
                       "standby": _node(), "supervisor": None,
                       "feed": _node()})
    assert "feed " not in "\n".join(render(view))


def test_discover_endpoints_include_feed_surfaces(tmp_path):
    from kme_tpu.telemetry.top import discover_endpoints

    os.makedirs(tmp_path / "group0" / "state")
    eps = discover_endpoints(str(tmp_path))
    assert eps["feed"] == str(tmp_path / "feed.health")
    assert eps["groups"][0]["feed"] == str(
        tmp_path / "group0" / "state" / "feed.health")
