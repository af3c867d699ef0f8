"""The unified telemetry subsystem: registry semantics, Prometheus
exposition, phase timers / trace recording, the metrics HTTP surface,
cross-engine counter-name parity, and checkpoint round-trips of the
observability state."""

import json
import urllib.request

import pytest

from kme_tpu.telemetry import (BUCKET_LE, N_BUCKETS, PhaseTimer, Registry,
                               TraceRecorder, bucket_index, get_tracer,
                               install, start_metrics_server)


# ---------------------------------------------------------------------------
# registry semantics


def test_counter_gauge_semantics():
    reg = Registry()
    c = reg.counter("msgs", help="messages")
    c.inc()
    c.inc(41)
    assert c.value == 42
    assert reg.counter("msgs") is c          # same instance on re-access
    g = reg.gauge("depth")
    g.set(7)
    g.set(3)
    assert g.value == 3
    with pytest.raises(TypeError):
        reg.gauge("msgs")                    # kind mismatch is loud


def test_histogram_semantics():
    reg = Registry()
    h = reg.histogram("fills")
    for v in (0, 1, 1, 2, 3, 4, 100, 20000):
        h.observe(v)
    assert h.count == 8
    assert h.sum == 0 + 1 + 1 + 2 + 3 + 4 + 100 + 20000
    assert h.buckets[0] == 1                  # v <= 0
    assert h.buckets[1] == 2                  # v == 1
    assert h.buckets[2] == 2                  # v in [2, 4)
    assert h.buckets[3] == 1                  # v in [4, 8)
    assert h.buckets[7] == 1                  # 100 in [64, 128)
    assert h.buckets[15] == 1                 # 20000 >= 2^14
    counts = [0] * N_BUCKETS
    counts[5] = 9
    h.set_buckets(counts)
    assert h.buckets == counts
    with pytest.raises(ValueError):
        h.set_buckets([0] * (N_BUCKETS - 1))


def test_bucket_index_boundaries():
    # idx = #{k in 0..14 : v >= 2^k}: 0 for v<=0, 1 for v==1,
    # i for v in [2^(i-1), 2^i), 15 for v >= 2^14
    assert bucket_index(-5) == 0
    assert bucket_index(0) == 0
    assert bucket_index(1) == 1
    assert bucket_index(2) == 2
    assert bucket_index(3) == 2
    assert bucket_index(4) == 3
    assert bucket_index(2 ** 14 - 1) == 14
    assert bucket_index(2 ** 14) == 15
    assert bucket_index(10 ** 9) == 15
    assert len(BUCKET_LE) == N_BUCKETS
    assert BUCKET_LE[0] == "0" and BUCKET_LE[-1] == "+Inf"


def test_prometheus_exposition():
    reg = Registry()
    reg.counter("trades_ok", help="accepted trades").inc(5)
    reg.gauge("open_orders").set(3)
    h = reg.histogram("fills_per_order")
    h.observe(1)
    h.observe(3)
    text = reg.prometheus_text()
    assert "# TYPE trades_ok counter" in text
    assert "trades_ok 5" in text
    assert "# HELP trades_ok accepted trades" in text
    assert "# TYPE open_orders gauge" in text
    assert "# TYPE fills_per_order histogram" in text
    # cumulative buckets: le="1" holds 1 obs, le="3" holds both
    assert 'fills_per_order_bucket{le="1"} 1' in text
    assert 'fills_per_order_bucket{le="3"} 2' in text
    assert 'fills_per_order_bucket{le="+Inf"} 2' in text
    assert "fills_per_order_sum 4" in text
    assert "fills_per_order_count 2" in text


def test_publish_and_snapshot():
    reg = Registry()
    reg.publish_counters({"msgs": 10, "fills": 2})
    reg.publish_gauges({"books": 4})
    reg.publish_histograms({"depth": [1] + [0] * (N_BUCKETS - 1)})
    snap = reg.snapshot()
    assert snap["counters"] == {"msgs": 10, "fills": 2}
    assert snap["gauges"] == {"books": 4}
    assert snap["histograms"]["depth"]["count"] == 1
    assert json.loads(reg.to_json())  # valid JSON export


# ---------------------------------------------------------------------------
# phase timing + tracing


def test_phase_timer_accumulates():
    t = PhaseTimer(track="test")
    with t.phase("plan_s"):
        pass
    first = t.totals["plan_s"]
    with t.phase("plan_s"):
        pass
    assert t.totals["plan_s"] > first    # cumulative, not overwritten
    t.add("fetch_s", 1.5)
    assert t.totals["fetch_s"] == 1.5
    t.reset()
    assert t.totals == {}


def test_trace_recorder(tmp_path):
    rec = TraceRecorder()
    install(rec)
    try:
        assert get_tracer() is rec
        t = PhaseTimer(track="unit")
        with t.phase("dispatch_s", batch=3):
            pass
        out = tmp_path / "trace.json"
        rec.save(str(out))
    finally:
        install(None)
    doc = json.loads(out.read_text())
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert evs and evs[0]["name"] == "dispatch_s"
    assert evs[0]["args"] == {"batch": 3}
    assert any(e.get("name") == "thread_name"
               for e in doc["traceEvents"])


# ---------------------------------------------------------------------------
# session integration: the legacy phase keys are load-bearing
# (bridge/service.py's stage attribution) and must ACCUMULATE across
# batches — the bug this PR fixes was SeqSession overwriting them


def _stream(n=300):
    from kme_tpu.workload import zipf_symbol_stream

    return zipf_symbol_stream(n, num_symbols=8, num_accounts=24, seed=3,
                              zipf_a=1.0, payout_per_mille=4)


PHASE_KEYS = {"plan_s", "dispatch_s", "fetch_s", "recon_s"}


def test_seq_phases_accumulate():
    from kme_tpu.engine import seq as SQ
    from kme_tpu.runtime.seqsession import SeqSession

    ses = SeqSession(SQ.SeqConfig(lanes=8, slots=128, accounts=128,
                                  max_fills=16))
    msgs = _stream()
    ses.process_wire([m.copy() for m in msgs])
    assert PHASE_KEYS <= set(ses.phases)
    first = dict(ses.phases)
    ses.process_wire([m.copy() for m in msgs[:100]])
    assert ses.phases["dispatch_s"] > first["dispatch_s"]


@pytest.mark.slow
def test_counter_names_identical_seqmesh():
    from kme_tpu.engine import seq as SQ
    from kme_tpu.parallel.seqmesh import SeqMeshSession
    from kme_tpu.runtime.seqsession import SeqSession

    msgs = _stream()
    cfg = SQ.SeqConfig(lanes=8, slots=128, accounts=128, max_fills=16)
    seq = SeqSession(cfg)
    seq.process_wire([m.copy() for m in msgs])
    seq.metrics()
    seq.histograms()
    mesh = SeqMeshSession(cfg, shards=2)
    mesh.process_wire([m.copy() for m in msgs])
    mesh.metrics()
    mesh.histograms()
    a, b = seq.telemetry.snapshot(), mesh.telemetry.snapshot()
    assert set(a["counters"]) == set(b["counters"])
    assert set(a["histograms"]) == set(b["histograms"])
    assert PHASE_KEYS <= set(mesh.phases)
    # seqmesh phase totals accumulate too (it used to zero recon_s)
    first = dict(mesh.phases)
    mesh.process_wire([m.copy() for m in msgs[:100]])
    assert mesh.phases["dispatch_s"] > first["dispatch_s"]


# ---------------------------------------------------------------------------
# the live HTTP surface


def test_metrics_http_server():
    reg = Registry()
    reg.counter("msgs").inc(3)
    reg.histogram("depth").observe(2)
    srv = start_metrics_server(reg, 0, host="127.0.0.1")
    try:
        host, port = srv.server_address[:2]
        text = urllib.request.urlopen(
            f"http://{host}:{port}/metrics").read().decode()
        assert "msgs 3" in text
        assert 'depth_bucket{le="+Inf"} 1' in text
        doc = json.loads(urllib.request.urlopen(
            f"http://{host}:{port}/metrics.json").read().decode())
        assert doc["counters"]["msgs"] == 3
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://{host}:{port}/nope")
    finally:
        srv.shutdown()


def test_metrics_http_concurrent_scrape_with_engine_steps():
    """Scrapers hammering /metrics and /metrics.json WHILE the engine
    steps and republishes must never see an error or torn exposition —
    the registry surface is read concurrently with session writes."""
    import threading

    from kme_tpu.engine import seq as SQ
    from kme_tpu.runtime.seqsession import SeqSession

    ses = SeqSession(SQ.SeqConfig(lanes=8, slots=128, accounts=128,
                                  max_fills=16))
    msgs = _stream(400)
    srv = start_metrics_server(ses.telemetry, 0, host="127.0.0.1")
    host, port = srv.server_address[:2]
    stop = threading.Event()
    errs, bodies = [], []

    def scrape():
        while not stop.is_set():
            try:
                bodies.append(urllib.request.urlopen(
                    f"http://{host}:{port}/metrics",
                    timeout=5).read().decode())
                json.loads(urllib.request.urlopen(
                    f"http://{host}:{port}/metrics.json",
                    timeout=5).read().decode())
            except Exception as e:  # noqa: BLE001 - collected + asserted
                errs.append(e)
                return

    threads = [threading.Thread(target=scrape) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for lo in range(0, len(msgs), 50):
            ses.process_wire([m.copy() for m in msgs[lo:lo + 50]])
            ses.metrics()        # republishes counters mid-scrape
            ses.histograms()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        srv.shutdown()
    assert errs == []
    assert bodies
    # post-publish scrapes carry complete histogram families
    final = bodies[-1]
    assert "# TYPE" in final
    for text in bodies:
        # exposition is never torn mid-family: every bucket line that
        # appears belongs to a family whose _count line also appears
        if "fills_per_order_bucket" in text:
            assert "fills_per_order_count" in text


# ---------------------------------------------------------------------------
# checkpoint round-trips: counters and histogram buckets are part of the
# resume contract (a restart must not zero the operator's dashboards)


def test_seq_checkpoint_roundtrip_telemetry(tmp_path):
    from kme_tpu.engine import seq as SQ
    from kme_tpu.runtime import checkpoint as ck
    from kme_tpu.runtime.seqsession import SeqSession

    ses = SeqSession(SQ.SeqConfig(lanes=8, slots=128, accounts=128,
                                  max_fills=16))
    ses.process_wire(_stream())
    met, hist = ses.metrics(), ses.histograms()
    assert sum(hist["book_depth"]) > 0
    ck.save_seq_session(str(tmp_path), ses, 300)
    ses2, off = ck.load_seq_session(str(tmp_path))
    assert off == 300
    assert ses2.metrics() == met
    assert ses2.histograms() == hist


# ---------------------------------------------------------------------------
# Chrome trace flow arrows: the serve pipeline links each batch's engine
# span to its produce span


def test_trace_flow_events():
    tr = TraceRecorder()
    tr.flow("batch", "s", 7, track="serve")
    tr.flow("batch", "f", 7, track="serve")
    evs = [e for e in tr.trace_events() if e.get("cat") == "flow"]
    assert [e["ph"] for e in evs] == ["s", "f"]
    assert all(e["id"] == 7 and e["name"] == "batch" for e in evs)
    assert "bp" not in evs[0]
    assert evs[1]["bp"] == "e"          # bind finish to enclosing slice
    assert evs[1]["ts"] >= evs[0]["ts"]
    with pytest.raises(ValueError):
        tr.flow("batch", "x", 1)


def test_serve_emits_flow_arrows_per_batch(tmp_path):
    from kme_tpu.bridge.broker import InProcessBroker
    from kme_tpu.bridge.provision import provision
    from kme_tpu.bridge.service import TOPIC_IN, MatchService
    from kme_tpu.wire import dumps_order
    from kme_tpu.workload import harness_stream

    tr = TraceRecorder()
    install(tr)
    try:
        br = InProcessBroker()
        provision(br)
        msgs = harness_stream(60, seed=2, num_accounts=4,
                              num_symbols=2, payout_opcode_bug=False,
                              validate=True)
        for m in msgs:
            br.produce(TOPIC_IN, None, dumps_order(m))
        svc = MatchService(br, engine="oracle", compat="fixed",
                           batch=16)
        svc.run(max_messages=len(msgs))
        svc.close()
    finally:
        install(None)
    evs = tr.trace_events()
    starts = [e for e in evs
              if e.get("cat") == "flow" and e["ph"] == "s"]
    finishes = [e for e in evs
                if e.get("cat") == "flow" and e["ph"] == "f"]
    # one arrow per batch, start/finish ids pair up
    assert starts and len(starts) == len(finishes)
    assert ([e["id"] for e in starts] ==
            [e["id"] for e in finishes])
    # arrows bind to real spans: engine + produce phase slices exist
    names = {e["name"] for e in evs if e["ph"] == "X"}
    assert "process_wire" in names and "produce_lines" in names
