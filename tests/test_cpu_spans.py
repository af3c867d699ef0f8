"""What PR 46 added to the program's own spans: the stage an order
spends in flight (`lat_inflight`), the per-order stamping under one
name (`_stamp_latency`, span `latency_stamp`) with its histograms as
they were, and the TCP front door's CPU seconds booked by role on its
handler threads. CPU, small shapes, a virtual clock: nothing here is a timing
of the device."""

import threading

import pytest

from kme_tpu.bridge.broker import InProcessBroker
from kme_tpu.bridge.clock import VirtualClock
from kme_tpu.bridge.provision import provision
from kme_tpu.bridge.service import TOPIC_IN, TOPIC_OUT, MatchService
from kme_tpu.bridge.tcp import TcpBroker, serve_broker
from kme_tpu.telemetry.registry import LatencyHistogram
from kme_tpu.wire import FRAME_SIZE, dumps_order, encode_frames
from kme_tpu.workload import zipf_symbol_stream

BATCH = 64


def _fed_service(n, pipeline, tmp_path, feed=None, **kw):
    """A seq service on a virtual clock that only the test moves, its
    broker stamping admissions on the same clock: message k is admitted
    at (k + 1) ms."""
    clock = VirtualClock(start=1000.0)
    br = InProcessBroker(clock=clock)
    provision(br)
    msgs = list(zipf_symbol_stream(n, 8, 64, seed=11))
    for m in msgs:
        clock.advance(0.001)
        (feed or br.produce)(TOPIC_IN, None, dumps_order(m))
    svc = MatchService(br, engine="seq", compat="fixed", batch=BATCH,
                       symbols=8, accounts=128, slots=128, max_fills=16,
                       pipeline=pipeline, clock=clock,
                       checkpoint_dir=str(tmp_path / "ck"),
                       checkpoint_every=10 * BATCH, **kw)
    return svc, clock, len(msgs)


def _batches(n):
    """Sizes of the batches a backlog of n messages is served in."""
    return [BATCH] * (n // BATCH) + ([n % BATCH] if n % BATCH else [])


def _move_the_clock_around_the_session(svc, clock, monkeypatch):
    """Virtual time passes inside the session's calls and nowhere else:
    3 ms and a little more each submit (or serial process_wire), 5 ms
    each collect. Returns the lists the wrappers fill: the clock as
    each submit returned and as each collect was entered."""
    ses = svc._session
    submitted, collecting = [], []
    submit, collect, wire = ses.submit, ses.collect, ses.process_wire

    def slow_submit(wb):
        handle = submit(wb)
        clock.advance(0.003 + 0.0001 * len(submitted))
        submitted.append(clock.time_us())
        return handle

    def slow_collect(handle):
        collecting.append(clock.time_us())
        clock.advance(0.005)
        return collect(handle)

    def slow_wire(msgs):
        clock.advance(0.003)
        return wire(msgs)

    monkeypatch.setattr(ses, "submit", slow_submit)
    monkeypatch.setattr(ses, "collect", slow_collect)
    monkeypatch.setattr(ses, "process_wire", slow_wire)
    return submitted, collecting


def test_inflight_is_submit_returned_to_collect_begun(tmp_path,
                                                      monkeypatch):
    # (the stream adds its preamble to the events asked for)
    svc, clock, n = _fed_service(5 * BATCH + 17, 2, tmp_path)
    assert svc.pipeline == 2
    submitted, collecting = _move_the_clock_around_the_session(
        svc, clock, monkeypatch)
    assert svc.run(max_messages=n) == n
    sizes = _batches(n)
    assert len(submitted) == len(collecting) == len(sizes) > 5
    h = svc._lat["inflight"]
    assert h.count == n                 # every order served, once
    # batches are collected in the order submitted; a batch waits while
    # the loop submits the next two (the last ones: until the drain)
    waits = [c - s for s, c in zip(submitted, collecting)]
    assert all(w > 0 for w in waits) and waits[0] >= 6000
    assert h.sum == pytest.approx(
        sum(w * 1e-6 * k for w, k in zip(waits, sizes)), rel=1e-9)
    # one observation a batch, with its count: the buckets hold whole
    # batches
    want = LatencyHistogram("want")
    for w, k in zip(waits, sizes):
        want.observe(w * 1e-6, k)
    assert h.state() == want.state()
    assert svc._ptimer.counts["latency_stamp"] == len(sizes)
    svc.close()


def test_the_serial_path_has_no_such_stage(tmp_path, monkeypatch):
    svc, clock, n = _fed_service(3 * BATCH, 0, tmp_path)
    assert svc._pipe is None
    _move_the_clock_around_the_session(svc, clock, monkeypatch)
    assert svc.run(max_messages=n) == n
    assert svc._lat["inflight"].count == 0
    assert svc._lat["e2e"].count == n
    assert svc._ptimer.counts["latency_stamp"] == len(_batches(n))
    svc.close()


# ---------------------------------------------------------------------------
# _stamp_latency: the walks as they stood before they were moved, kept
# here as the reference


def _walks_as_before(batches, group_id):
    """lat_ingress, lat_e2e and the exemplars as the serve loop made
    them until PR 44: `for r in recs: ... observe(max(0, fetch_us -
    ats) * 1e-6)` at the fetch, `for ats in atss: ... observe(d)` after
    the produce, and _stamp_orders' exemplar walk (eight slowest, worst
    first, first come first among equals)."""
    from kme_tpu.telemetry.dtrace import local_tid

    ingress, e2e, slow = (LatencyHistogram("i"), LatencyHistogram("e"),
                          [])
    for (in_atss, atss, offs, oids, aids, fetch_us, done_us) in batches:
        for ats in in_atss:
            if ats is not None:
                ingress.observe(max(0, fetch_us - ats) * 1e-6)
        for ats in atss:
            if ats is not None:
                e2e.observe(max(0, done_us - ats) * 1e-6)
        floor = slow[-1]["e2e_us"] if len(slow) >= 8 else -1
        for i, ats in enumerate(atss):
            if ats is None:
                continue
            d = max(0, done_us - ats)
            if d > floor or len(slow) < 8:
                slow.append({"tid": local_tid(group_id, offs[i]),
                             "off": offs[i], "oid": oids[i],
                             "aid": aids[i], "g": group_id, "e2e_us": d})
        slow.sort(key=lambda x: -x["e2e_us"])
        del slow[8:]
    return ingress, e2e, slow


def _record_stamping(svc, monkeypatch):
    batches = []
    stamp = svc._stamp_latency

    def recording(in_atss, atss, offs, oids, aids, fetch_us, done_us,
                  *rest):
        batches.append((list(in_atss), list(atss), list(offs), list(oids),
                        list(aids), fetch_us, done_us))
        return stamp(in_atss, atss, offs, oids, aids, fetch_us, done_us,
                     *rest)

    monkeypatch.setattr(svc, "_stamp_latency", recording)
    return batches


@pytest.mark.parametrize("pipeline", [2, 0])
def test_ingress_e2e_and_exemplars_are_bucket_for_bucket_what_they_were(
        pipeline, tmp_path, monkeypatch):
    svc, clock, n = _fed_service(4 * BATCH + 9, pipeline, tmp_path)
    if pipeline == 0:
        # a record the serial path drops: it waited at the door like
        # the others (ingress), and nobody served it (no e2e)
        clock.advance(0.001)
        svc.broker.produce(TOPIC_IN, None, "{not json")
        n += 1
    _move_the_clock_around_the_session(svc, clock, monkeypatch)
    batches = _record_stamping(svc, monkeypatch)
    assert svc.run(max_messages=n) == n
    assert len(batches) == len(_batches(n)) >= 5
    ingress, e2e, slow = _walks_as_before(batches, svc.group_id)
    assert svc._lat["ingress"].state() == ingress.state()
    assert svc._lat["e2e"].state() == e2e.state()
    assert ingress.count == n and e2e.count == n - (pipeline == 0)
    # the clock moved: the stamps are not all in one bucket
    assert sum(1 for c in e2e.state()[2] if c) > 1
    assert svc.telemetry.exemplars() == slow and len(slow) == 8
    assert slow[0]["e2e_us"] >= slow[-1]["e2e_us"] > 0
    # the stage gauges the walks fed are whole batches' too
    for stage in ("plan", "produce"):
        assert svc._lat[stage].count in (0, n - (pipeline == 0))
    svc.close()


# ---------------------------------------------------------------------------
# the front door: a handler thread books its CPU seconds by role


def _front_door():
    br = InProcessBroker()
    provision(br)
    srv, br = serve_broker("127.0.0.1", 0, br)
    host, port = srv.server_address
    return srv, br, host, port


def _roles(br):
    return {role: sum(b[role] for b in br.tcp_cpu_books)
            for role in ("ingress", "egress")}


class _TickingCpuClock:
    """Stands in for bridge/tcp.py's `time`: every thread's CPU clock
    moves one second a reading, so a sum counts the readings booked."""

    def __init__(self):
        self._at = threading.local()

    def thread_time(self):
        self._at.v = getattr(self._at, "v", 0.0) + 1.0
        return self._at.v


def _settled(srv, idle, timeout=10.0):
    """Wait until `idle` CPU books are back in the server's pool: a
    handler returns its book on its own thread after the client has
    closed."""
    import time

    end = time.monotonic() + timeout
    while len(srv.idle_cpu_books) < idle and time.monotonic() < end:
        time.sleep(0.005)
    assert len(srv.idle_cpu_books) == idle


def _produce_and_consume(host, port, msgs, chunks, reads):
    """Two clients at once over real sockets: `chunks` produce_frames
    requests, `reads` fetch_bin requests (the last three long polls
    that wait their time out). Neither closes before both have been
    served: a handler that had wound up before the other's began would
    hand it its book, and the server would hold one where two
    connections were open at once (on a loaded machine the second
    client's thread can start that late)."""
    failed = []
    frames, per = encode_frames(msgs), FRAME_SIZE
    both_served = threading.Barrier(2)

    def producer():
        try:
            c = TcpBroker(host, port)
            step = len(msgs) // chunks
            for k in range(chunks):
                c.produce_frames(
                    TOPIC_IN, None,
                    frames[k * step * per:(k + 1) * step * per])
            both_served.wait(timeout=30)
            c.close()
        except Exception as e:      # a thread's failure fails the test
            both_served.abort()
            failed.append(e)

    def consumer():
        try:
            c = TcpBroker(host, port)
            for k in range(reads):
                c.fetch_bin(TOPIC_IN, 0 if k < reads - 3 else 10 ** 6, 64,
                            timeout=0.01 if k < reads - 3 else 0.03)
            both_served.wait(timeout=30)
            c.close()
        except Exception as e:
            both_served.abort()
            failed.append(e)

    threads = [threading.Thread(target=producer),
               threading.Thread(target=consumer)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    assert not failed, failed


def test_two_handler_threads_book_every_request_to_its_role(monkeypatch):
    from kme_tpu.bridge import tcp

    monkeypatch.setattr(tcp, "time", _TickingCpuClock())
    srv, br, host, port = _front_door()
    chunks, reads = 20, 30
    msgs = list(zipf_symbol_stream(400, 8, 64, seed=2))
    del msgs[len(msgs) // chunks * chunks:]
    try:
        _produce_and_consume(host, port, msgs, chunks, reads)
        assert br.end_offset(TOPIC_IN) == len(msgs)
        # both connections are closed; their handlers wind up on their
        # own threads, and nothing they booked left the sums with them:
        # one reading a request and one as the connection closes, each
        # booked to the role of the request last served
        _settled(srv, 2)
        assert _roles(br) == {"ingress": chunks + 1, "egress": reads + 1}
        assert len(br.tcp_cpu_books) == 2
        # requests of neither role are served and book nothing (what
        # ran before a connection's first request of a role is nobody's
        # either); a JSON produce is the producers' role; a later
        # connection takes up an idle book, so the list holds the most
        # open at once
        for k in range(1, 4):
            c = TcpBroker(host, port)
            assert c.end_offset(TOPIC_IN) == len(msgs)
            c.produce(TOPIC_OUT, "K", "v")
            c.fetch(TOPIC_OUT, 0, 10)
            c.close()
            _settled(srv, 2)
            assert _roles(br) == {"ingress": chunks + 1 + k,
                                  "egress": reads + 1 + 2 * k}
        assert len(br.tcp_cpu_books) == 2
    finally:
        srv.shutdown()
        srv.server_close()


def test_a_long_poll_is_a_wait_and_not_work():
    """On the real clocks: each role's handler thread ran something,
    and far less than the wall its long polls waited."""
    import time

    srv, br, host, port = _front_door()
    msgs = list(zipf_symbol_stream(400, 8, 64, seed=2))
    del msgs[len(msgs) // 20 * 20:]
    try:
        t0 = time.perf_counter()
        _produce_and_consume(host, port, msgs, 20, 30)
        _settled(srv, 2)
        wall = time.perf_counter() - t0
        g = _roles(br)
        assert g["ingress"] >= 0 and g["egress"] >= 0
        assert g["ingress"] + g["egress"] > 0
        # three polls of 30 ms waited their time out: CPU <= wall, and
        # the consumer's handler did not run while it waited
        assert wall > 0.09 and g["egress"] <= wall - 0.06
        assert g["ingress"] <= wall
    finally:
        srv.shutdown()
        srv.server_close()


def test_a_service_publishes_its_front_doors_seconds(tmp_path):
    srv, br, host, port = _front_door()
    try:
        svc = MatchService(br, engine="oracle", compat="fixed", batch=8)
        g0 = svc.telemetry.snapshot()["gauges"]
        # in the registry before anyone has connected: at 0
        for k in ("tcp_ingress_cpu_s", "tcp_egress_cpu_s",
                  "wire_parse_s", "wire_binary_records"):
            assert g0[k] == 0, k
        msgs = list(zipf_symbol_stream(40, 4, 16, seed=1))
        c = TcpBroker(host, port)
        c.produce_frames(TOPIC_IN, None, encode_frames(msgs))
        assert svc.run(max_messages=len(msgs)) == len(msgs)
        c.fetch_bin(TOPIC_OUT, 0, 1000)
        c.close()
        # a request's CPU is booked after its reply is written, on
        # the handler's own thread; all of it by the close
        _settled(srv, 1)
        svc._publish_spans()
        g = svc.telemetry.snapshot()["gauges"]
        assert g["tcp_ingress_cpu_s"] == round(_roles(br)["ingress"], 6)
        assert g["tcp_egress_cpu_s"] == round(_roles(br)["egress"], 6)
        assert not [k for k in g if k.startswith("tcp_")
                    and not k.endswith("_cpu_s")]
        assert g["wire_binary_records"] == len(msgs)
        assert g["wire_parse_s"] == pytest.approx(
            br.wire_parse_ns * 1e-9, abs=1e-9) and g["wire_parse_s"] > 0
        assert 0 < g["serve_cpu_s"] <= g["serve_loop_s"] + 1e-3
        assert g["serve_cpu_s"] + g["tcp_ingress_cpu_s"] \
            + g["tcp_egress_cpu_s"] <= g["process_cpu_s"] * 1.01 + 1e-3
        svc.close()
        # a broker in another process has no such counters: absent,
        # not 0
        far = TcpBroker(host, port)
        remote = MatchService(far, engine="oracle", compat="fixed",
                              batch=8)
        gr = remote.telemetry.snapshot()["gauges"]
        assert "serve_cpu_s" in gr and "process_cpu_s" in gr
        assert not [k for k in gr if k.startswith(("tcp_", "wire_parse"))]
        remote.close()
        far.close()
    finally:
        srv.shutdown()
        srv.server_close()


def test_serve_cpu_counts_only_what_one_thread_polled():
    """A service built on one thread and run on another: the CPU
    between two reads on different threads is in no thread_time()
    difference, so it is left out and the gauge never runs backwards."""
    br = InProcessBroker()
    provision(br)
    svc = MatchService(br, engine="oracle", compat="fixed", batch=8)
    seen = []

    def elsewhere():
        seen.append(svc._thread_gauges()["serve_cpu_s"])
        x = 0
        for i in range(200000):
            x += i
        seen.append(svc._thread_gauges()["serve_cpu_s"])

    first = svc._thread_gauges()["serve_cpu_s"]
    t = threading.Thread(target=elsewhere)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    assert first == seen[0] < seen[1]
    assert svc._thread_gauges()["serve_cpu_s"] == seen[1]
    svc.close()
