"""`fetch_bin` packs a stamped run from its buffer (PR 44).

The serve loop hands a batch's output to `InProcessBroker` as one
buffer (`produce_stamped_buffer`), the log holds it as one `Run`, and
`tcp.py`'s `fetch_bin` packs its reply rows from that buffer in one
native call (`kme_run_pack`) — no `Record` is made on the way to a
consumer's socket. `_pack_records` over `fetch()`'s Records stays the
definition of the bytes, and these tests hold the run path to it:

- the reply tail from runs is byte-equal to the tail from Records, for
  fetches that start and end inside a run, span two runs, and span a
  run and single records;
- over a real socket `TcpBroker.fetch_bin` and `TcpBroker.fetch` return
  the Records `InProcessBroker.fetch` does;
- awkward keys and values (null key, empty, non-ASCII, a space in the
  key) pack alike; a key too long for its length byte raises as before;
- without the native library the twin gives the same bytes;
- the deliver observer sees one `Run` with its count, and the service's
  `lat_consume` histogram counts every record of it;
- many consumers packing while a producer appends runs: every reply is
  the bytes its records pack to (each thread packs into its own native
  buffer).
"""

import numpy as np
import pytest

from kme_tpu.bridge import tcp
from kme_tpu.bridge.broker import (InProcessBroker, Record, Run,
                                   run_of_pairs)
from kme_tpu.bridge.provision import provision
from kme_tpu.bridge.service import TOPIC_OUT
from kme_tpu.bridge.tcp import TcpBroker, serve_broker


def _buffer_of(lines):
    off = np.zeros(len(lines) + 1, np.int64)
    np.cumsum([len(ln.encode()) for ln in lines], out=off[1:])
    return "".join(lines).encode(), off


def _lines(n, start=0):
    return ['%s {"action":2,"oid":%d,"next":null}'
            % ("IN" if i % 3 == 0 else "OUT", i)
            for i in range(start, start + n)]


def _mixed_broker():
    """offsets 0-1 single, 2-11 a run, 12-31 a run, 32 a stamped
    single, 33 an unstamped one with a trace word."""
    b = InProcessBroker()
    provision(b)
    b.produce(TOPIC_OUT, "PRE", "single before")
    b.produce(TOPIC_OUT, None, "keyless single")
    buf, off = _buffer_of(_lines(30))
    assert b.produce_stamped_buffer(TOPIC_OUT, buf, off[:11], 4, 0) == 10
    assert b.produce_stamped_buffer(TOPIC_OUT, buf, off[10:], 4, 10) \
        == 20
    b.produce(TOPIC_OUT, "OUT", "stamped single", epoch=4, out_seq=30)
    b.produce(TOPIC_OUT, "T", "traced", tid=77)
    assert b.end_offset(TOPIC_OUT) == 34
    return b


SPANS = [(0, 1000), (2, 10), (5, 3), (3, 9), (11, 1), (12, 20), (7, 12),
         (0, 5), (30, 4), (31, 1), (32, 2), (1, 33), (34, 5)]


@pytest.mark.parametrize("lo,n", SPANS)
def test_reply_from_runs_is_byte_equal_to_the_reply_from_records(lo, n):
    b = _mixed_broker()
    recs = b.fetch(TOPIC_OUT, lo, n)
    pieces = b.fetch_runs(TOPIC_OUT, lo, n)
    count, tail = tcp._pack_pieces(pieces)
    assert count == len(recs)
    assert tail == tcp._pack_records(recs)
    # what the fetch asked for was inside runs where the log has runs
    kinds = [type(p) for p in pieces]
    assert all(k in (Run, list) for k in kinds)
    if 2 <= lo and lo + n <= 32 and n:
        assert set(kinds) == {Run}


def test_over_the_socket_both_fetches_return_the_brokers_records():
    b = _mixed_broker()
    srv, _ = serve_broker("127.0.0.1", 0, b)
    host, port = srv.server_address[:2]
    cli = TcpBroker(host, port)
    try:
        for lo, n in SPANS:
            want = b.fetch(TOPIC_OUT, lo, n)
            assert cli.fetch_bin(TOPIC_OUT, lo, n) == want, (lo, n)
            assert cli.fetch(TOPIC_OUT, lo, n) == want, (lo, n)
        # a long poll is woken by a run
        import threading

        got = []
        th = threading.Thread(target=lambda: got.extend(
            cli.fetch_bin(TOPIC_OUT, 34, 8192, timeout=10.0)))
        th.start()
        buf, off = _buffer_of(_lines(300, 1000))
        b.produce_stamped_buffer(TOPIC_OUT, buf, off, 4, 31)
        th.join(timeout=10.0)
        assert not th.is_alive() and len(got) == 300
        assert got == b.fetch(TOPIC_OUT, 34, 8192)
    finally:
        cli.close()
        srv.shutdown()
        srv.server_close()


_AWKWARD = [("IN", '{"a":"q\\"uote\\\\"}'), (None, "keyless"),
            ("", ""), ("k y", " leading space"), ("é", "ü ☃ \U0001f600"),
            ("OUT", ""), ("K" * 254, "v"), (None, ""),
            ("a", "x" * 70000)]


def _run(pairs, base=7, epoch=3, seq0=1 << 40, ats=1234567):
    buf, off, klen = run_of_pairs(pairs)
    return Run(buf, off, klen, 0, len(pairs), base, epoch, seq0, ats)


def test_awkward_records_pack_alike_and_round_trip():
    run = _run(_AWKWARD)
    recs = run.records()
    assert [(r.key, r.value) for r in recs] == _AWKWARD
    assert [(r.offset, r.epoch, r.out_seq, r.ats, r.tid)
            for r in recs[:2]] == [(7, 3, 1 << 40, 1234567, None),
                                   (8, 3, (1 << 40) + 1, 1234567, None)]
    assert tcp._pack_run(run) == tcp._pack_records(recs)
    part = run.slice(2, 6)
    assert [r.offset for r in part.records()] == [9, 10, 11, 12]
    assert tcp._pack_run(part) == tcp._pack_records(recs[2:6])
    assert tcp._pack_run(run.slice(4, 4)) == b""


def test_a_key_too_long_for_its_length_byte_raises_as_before():
    pairs = [("OUT", "v"), ("K" * 300, "v")]
    with pytest.raises(ValueError):
        tcp._pack_records([Record(i, k, v) for i, (k, v)
                           in enumerate(pairs)])
    with pytest.raises(ValueError):
        tcp._pack_run(_run(pairs))


def test_without_the_native_library_the_twin_gives_the_same_bytes(
        monkeypatch):
    run = _run(_AWKWARD)
    native_tail = tcp._pack_run(run)
    import kme_tpu.native as native

    monkeypatch.setattr(native, "load_library", lambda: None)
    assert tcp._pack_run(run) == native_tail
    b = _mixed_broker()
    assert tcp._pack_pieces(b.fetch_runs(TOPIC_OUT, 0, 100))[1] \
        == tcp._pack_records(b.fetch(TOPIC_OUT, 0, 100))


def test_a_broker_without_fetch_runs_is_served_records():
    class Plain:
        def fetch(self, topic, offset, max_records, timeout):
            return [Record(offset, "K", "v", 1, 2, 3)]

    resp, tail = tcp._Handler._dispatch(
        None, Plain(), {"op": "fetch_bin", "topic": "t", "offset": 5})
    assert resp == {"ok": True, "n": 1, "nbytes": len(tail)}
    assert tail == tcp._pack_records([Record(5, "K", "v", 1, 2, 3)])


def test_the_observer_sees_a_run_once_with_its_count(tmp_path):
    from kme_tpu.bridge.service import MatchService

    b = InProcessBroker()
    provision(b)
    svc = MatchService(b, engine="oracle", compat="fixed", batch=16,
                       slots=64, max_fills=32,
                       checkpoint_dir=str(tmp_path), exactly_once=True)
    buf, off = _buffer_of(_lines(500))
    svc._produce_buffer(buf, off)
    lat = svc._lat["consume"]
    seen = []
    observe = b.deliver_observer

    def tap(topic, recs, now_us):
        seen.append([(type(r), getattr(r, "n", 1)) for r in recs])
        observe(topic, recs, now_us)

    b.deliver_observer = tap
    assert lat.count == 0
    n, _ = tcp._pack_pieces(b.fetch_runs(TOPIC_OUT, 0, 8192))
    assert n == 500 and seen == [[(Run, 500)]]
    assert lat.count == 500
    # fetch() hands the observer the Records it made, one each
    assert len(b.fetch(TOPIC_OUT, 10, 20)) == 20
    assert seen[1] == [(Record, 1)] * 20 and lat.count == 520
    svc.close()


def test_many_consumers_pack_while_a_producer_appends_runs():
    """More fetching threads than cores, a shortened switch interval:
    a reply that held another thread's bytes, or a run seen before its
    offsets were whole, would not equal the pack of its own Records."""
    import sys
    import threading

    b = InProcessBroker()
    provision(b)
    runs, per = 40, 50
    total = runs * per
    stop = threading.Event()
    errors, seen = [], []

    def consume():
        at = 0
        try:
            while at < total and not stop.is_set():
                pieces = b.fetch_runs(TOPIC_OUT, at, 137, timeout=0.2)
                n, tail = tcp._pack_pieces(pieces)
                want = b.fetch(TOPIC_OUT, at, n)
                assert len(want) == n
                assert tail == tcp._pack_records(want)
                assert [r.offset for r in want] == list(
                    range(at, at + n))
                at += n
            seen.append(at)
        except Exception as e:      # reported by the main thread
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=consume) for _ in range(24)]
        for th in threads:
            th.start()
        for k in range(runs):
            buf, off = _buffer_of(_lines(per, k * per))
            assert b.produce_stamped_buffer(TOPIC_OUT, buf, off, 1,
                                            k * per) == per
        for th in threads:
            th.join(timeout=60.0)
        stop.set()
        assert not any(th.is_alive() for th in threads)
    finally:
        stop.set()
        sys.setswitchinterval(old)
    assert not errors, errors[:1]
    assert seen == [total] * 24
