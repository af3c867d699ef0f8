"""One stamped batch produce per collected batch (PR 33).

`InProcessBroker.produce_stamped` is the egress twin of
`produce_frames`; `MatchService._produce_records` hands it each run of
a batch's organic output lines when the service is a stamping leader
and the broker has the call. The per-record walk (`_produce_out` ->
`_produce_retry` -> `produce`) stays the definition, and these tests
hold the batch path to it:

- the same stream served through a broker with the call and through
  one with it hidden leaves byte-identical MatchOut and Xfer topic
  logs, stamps included;
- the broker call alone: dense stamps, replayed prefixes, fencing, a
  torn final row, one flush and one wake a call, rows byte-equal to
  produce()'s;
- an injected `broker.produce` fault on the batch call is retried and
  leaves no duplicate and no gap;
- a follower, and a leader on a broker without the call, count
  `out_seq` as before;
- the heartbeat carries `matchout_records` / `matchout_produce_calls`.
"""

import json
import os

import pytest

from kme_tpu import faults
from kme_tpu.bridge import front
from kme_tpu.bridge.broker import (BrokerError, BrokerFenced,
                                   BrokerOverload, InProcessBroker,
                                   OverloadController)
from kme_tpu.bridge.provision import group_topics, provision
from kme_tpu.bridge.service import TOPIC_IN, TOPIC_OUT, MatchService
from kme_tpu.native import load_library
from kme_tpu.wire import dumps_order
from kme_tpu.workload import cross_account_stream, harness_stream

needs_native = pytest.mark.skipif(
    load_library() is None,
    reason="native host runtime unavailable (KME_NATIVE=0 or no "
           "toolchain); pipelined serving gates on it")


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


class _PerRecordBroker(InProcessBroker):
    """The same broker with the batch call hidden: the service reads
    the path off the broker object, so this one is served record by
    record."""

    produce_stamped = None


def _log(persist_dir, topic) -> bytes:
    with open(os.path.join(persist_dir, f"{topic}.log"), "rb") as f:
        return f.read()


# ---------------------------------------------------------------------------
# (a) service parity: batch path against the per-record path


def _harness_lines(compat):
    # the fixed engines serve the validated stream; java the stock one
    fixed = compat == "fixed"
    msgs = harness_stream(300, seed=3, payout_opcode_bug=not fixed,
                          validate=True)
    return [dumps_order(m) for m in msgs]


def _grouped_lines():
    """Group 0's substream of a cross-account stream: the front's
    injected transfer legs carry the Xfer mark, so batches hold
    Xfer-marked lines between organic ones."""
    msgs = cross_account_stream(300, 32, 16, 2, seed=4, cross_frac=1.0)
    per, router = front.split_lines([dumps_order(m) for m in msgs], 2)
    assert router.counters["cross_shard_transfers_total"] > 0
    return per[0]


_SEQ_FIXED = dict(engine="seq", compat="fixed", batch=128, symbols=8,
                  accounts=128, slots=128, max_fills=32)
_SEQ_JAVA = dict(engine="seq", compat="java", batch=64, symbols=8,
                 accounts=128, slots=256, max_fills=64)
_ORACLE = dict(engine="oracle", compat="fixed", batch=16, slots=64,
               max_fills=32)

PARITY_CASES = [
    pytest.param(_ORACLE, None, id="oracle-fixed-serial"),
    pytest.param(_ORACLE, (0, 2), id="oracle-fixed-serial-xfer"),
    pytest.param(dict(engine="native", compat="java", batch=64), None,
                 id="native-java-serial"),
    pytest.param(dict(_SEQ_FIXED, pipeline=0), None,
                 id="seq-fixed-serial"),
    pytest.param(dict(_SEQ_FIXED, pipeline=2), None,
                 id="seq-fixed-pipelined", marks=needs_native),
    pytest.param(dict(_SEQ_FIXED, pipeline=2, symbols=32), (0, 2),
                 id="seq-fixed-pipelined-xfer", marks=needs_native),
    pytest.param(_SEQ_JAVA, None, id="seq-java-serial"),
]


def _serve(tmp_path, name, broker_cls, kw, group, lines):
    logd = str(tmp_path / f"{name}-logs")
    b = broker_cls(persist_dir=logd)
    topics = group_topics(group[0]) if group else None
    provision(b, topics=topics)
    topic_in = topics[0] if group else TOPIC_IN
    for ln in lines:
        b.produce(topic_in, None, ln)
    svc = MatchService(b, checkpoint_dir=str(tmp_path / f"{name}-ck"),
                       checkpoint_every=10 ** 9, exactly_once=True,
                       group=group, **kw)
    assert svc.epoch == 1
    assert svc.run(max_messages=len(lines)) == len(lines)
    snap = svc.telemetry.snapshot()
    svc.close()
    return logd, svc, snap


@pytest.mark.parametrize("kw,group", PARITY_CASES)
def test_batch_path_leaves_the_per_record_paths_bytes(tmp_path, kw,
                                                      group):
    lines = (_grouped_lines() if group
             else _harness_lines(kw["compat"]))
    want_d, want_svc, want_snap = _serve(
        tmp_path, "record", _PerRecordBroker, kw, group, lines)
    got_d, got_svc, got_snap = _serve(
        tmp_path, "batch", InProcessBroker, kw, group, lines)
    topics = group_topics(group[0])[1:] if group else (TOPIC_OUT,)
    for topic in topics:
        want = _log(want_d, topic)
        assert want, topic
        assert _log(got_d, topic) == want, topic
    if group:
        xfer = _log(want_d, topics[1]).splitlines()
        out = _log(want_d, topics[0]).splitlines()
        # an Xfer-marked line sat between organic ones: the stamp
        # stream is shared, so MatchOut's stamps have holes where the
        # Xfer topic holds them
        first_x = json.loads(xfer[0])[3]
        stamps = [json.loads(r)[3] for r in out]
        assert stamps[0] < first_x < stamps[-1]
    assert got_svc.out_seq == want_svc.out_seq > 0
    # the counters say which path ran: a call a record on the broker
    # without the call, a call a run on the one with it
    wc, gc = want_snap["counters"], got_snap["counters"]
    assert wc["matchout_records"] == gc["matchout_records"] \
        == want_svc.out_seq
    assert wc["matchout_produce_calls"] == wc["matchout_records"]
    assert gc["matchout_produce_calls"] < gc["matchout_records"] / 4
    if not group:
        assert gc["matchout_produce_calls"] == gc["service_batches"]


# ---------------------------------------------------------------------------
# (b) the broker call alone


def _pairs(n, start=0):
    return [("IN" if i % 3 == 0 else "OUT",
             '{"action":2,"oid":%d,"note":"q\\"uote\\\\ é"}' % i)
            for i in range(start, start + n)]


def test_rows_are_byte_equal_to_produces_and_stamps_dense(tmp_path):
    a = InProcessBroker(persist_dir=str(tmp_path / "a"))
    b = InProcessBroker(persist_dir=str(tmp_path / "b"))
    pairs = _pairs(50) + [(None, "keyless")]
    for br in (a, b):
        provision(br)
    for i, (k, v) in enumerate(pairs):
        a.produce(TOPIC_OUT, k, v, epoch=3, out_seq=7 + i)
    assert b.produce_stamped(TOPIC_OUT, pairs, 3, 7) == len(pairs)
    assert _log(str(tmp_path / "b"), TOPIC_OUT) \
        == _log(str(tmp_path / "a"), TOPIC_OUT)
    ra = a.fetch(TOPIC_OUT, 0, 1000)
    rb = b.fetch(TOPIC_OUT, 0, 1000)
    strip = [(r.offset, r.key, r.value, r.epoch, r.out_seq) for r in ra]
    assert strip == [(r.offset, r.key, r.value, r.epoch, r.out_seq)
                     for r in rb]
    assert [r.out_seq for r in rb] == list(range(7, 7 + len(pairs)))
    # one admission stamp for the run
    assert len({r.ats for r in rb}) == 1 and rb[0].ats is not None
    assert b.fence_epoch == 3


@pytest.mark.parametrize("below", [0, 4, 10],
                         ids=["none-below", "partly-below",
                              "wholly-below"])
def test_replayed_run_suppresses_its_prefix_and_appends_the_rest(below):
    b = InProcessBroker()
    provision(b)
    pairs = _pairs(10)
    assert b.produce_stamped(TOPIC_OUT, pairs[:below], 1, 100) == below
    # the replay: the same run from the same seq0
    assert b.produce_stamped(TOPIC_OUT, pairs, 1, 100) == 10 - below
    assert b.dup_suppressed == below
    recs = b.fetch(TOPIC_OUT, 0, 100)
    assert [(r.key, r.value) for r in recs] == pairs
    assert [r.out_seq for r in recs] == list(range(100, 110))
    # and once more, wholly below the watermark now
    assert b.produce_stamped(TOPIC_OUT, pairs, 1, 100) == 0
    assert b.dup_suppressed == below + 10
    assert b.end_offset(TOPIC_OUT) == 10


def test_stale_epoch_is_fenced_with_nothing_appended(tmp_path):
    d = str(tmp_path)
    b = InProcessBroker(persist_dir=d)
    provision(b)
    assert b.produce_stamped(TOPIC_OUT, _pairs(3), 2, 0) == 3
    before = _log(d, TOPIC_OUT)
    with pytest.raises(BrokerFenced) as ei:
        b.produce_stamped(TOPIC_OUT, _pairs(5, 3), 1, 3)
    assert ei.value.code == "fenced"
    assert b.fenced_produces == 1 and b.fence_epoch == 2
    assert b.end_offset(TOPIC_OUT) == 3
    assert _log(d, TOPIC_OUT) == before
    with pytest.raises(BrokerError, match="unknown topic"):
        b.produce_stamped("NoSuchTopic", _pairs(1), 2, 0)


def test_torn_final_row_is_repaired_and_the_replay_completes_it(
        tmp_path):
    d = str(tmp_path / "torn")
    b = InProcessBroker(persist_dir=d)
    provision(b)
    pairs = _pairs(8)
    b.produce_stamped(TOPIC_OUT, pairs, 1, 0)
    whole = _log(d, TOPIC_OUT)
    del b
    # the crash tore the one write inside its sixth row: a partial
    # write is a prefix, so only the final line can be incomplete
    rows = whole.split(b"\n")
    cut = sum(len(r) + 1 for r in rows[:5]) + len(rows[5]) // 2
    with open(os.path.join(d, f"{TOPIC_OUT}.log"), "r+b") as f:
        f.truncate(cut)
    b2 = InProcessBroker(persist_dir=d)
    assert b2.end_offset(TOPIC_OUT) == 5
    # the restarted leader re-produces the run under its next epoch
    assert b2.produce_stamped(TOPIC_OUT, pairs, 2, 0) == 3
    assert b2.dup_suppressed == 5
    # the five rows that survived, then the rest under epoch 2 as
    # produce() would have written them
    ref_d = str(tmp_path / "ref")
    ref = InProcessBroker(persist_dir=ref_d)
    provision(ref)
    for i, (k, v) in enumerate(pairs[5:], 5):
        ref.produce(TOPIC_OUT, k, v, epoch=2, out_seq=i)
    assert _log(d, TOPIC_OUT) \
        == b"".join(r + b"\n" for r in rows[:5]) + _log(ref_d, TOPIC_OUT)
    assert [r.out_seq for r in b2.fetch(TOPIC_OUT, 0, 100)] \
        == list(range(8))


def test_one_write_one_flush_and_one_wake_a_call(tmp_path):
    b = InProcessBroker(persist_dir=str(tmp_path))
    provision(b)
    t = b._topics[TOPIC_OUT]
    calls = {"write": 0, "flush": 0, "notify": 0}

    class _CountingFile:
        def __init__(self, f):
            self._f = f

        def write(self, s):
            calls["write"] += 1
            return self._f.write(s)

        def flush(self):
            calls["flush"] += 1
            return self._f.flush()

        def __getattr__(self, name):
            return getattr(self._f, name)

    t.logfile = _CountingFile(t.logfile)
    notify_all = b._data.notify_all

    def counting_notify():
        calls["notify"] += 1
        notify_all()

    b._data.notify_all = counting_notify
    assert b.produce_stamped(TOPIC_OUT, _pairs(500), 1, 0) == 500
    assert calls == {"write": 1, "flush": 1, "notify": 1}
    # a run wholly below the watermark writes and wakes nothing
    assert b.produce_stamped(TOPIC_OUT, _pairs(500), 1, 0) == 0
    assert calls == {"write": 1, "flush": 1, "notify": 1}
    for i, (k, v) in enumerate(_pairs(3, 500)):
        b.produce(TOPIC_OUT, k, v, epoch=1, out_seq=500 + i)
    assert calls == {"write": 4, "flush": 4, "notify": 4}


def test_fault_point_is_asked_once_before_anything_is_appended():
    b = InProcessBroker()
    provision(b)
    faults.configure("broker.produce:n=1")
    with pytest.raises(BrokerError, match="injected fault"):
        b.produce_stamped(TOPIC_OUT, _pairs(20), 1, 0)
    assert b.end_offset(TOPIC_OUT) == 0
    assert b.produce_stamped(TOPIC_OUT, _pairs(20), 1, 0) == 20
    assert faults.fired_total() == 1       # once a call, not a record


@pytest.mark.parametrize("controller", [False, True],
                         ids=["max-lag", "controller"])
def test_a_refusal_keeps_the_admitted_prefix_like_produce_frames(
        controller):
    kw = ({"overload": OverloadController(high_lag=4, low_lag=1,
                                          drain_lag=4)}
          if controller else {"max_lag": 4})
    b = InProcessBroker(**kw)
    provision(b)
    b.commit(TOPIC_OUT, 0)      # a committed watermark arms the bound
    with pytest.raises(BrokerOverload) as ei:
        b.produce_stamped(TOPIC_OUT, _pairs(10), 1, 0)
    assert ei.value.admitted == 4
    assert b.overload_rejects == 1
    assert b.end_offset(TOPIC_OUT) == 4
    assert (ei.value.detail is not None) == controller
    # the retry of the whole run from the same seq0 is idempotent
    b.commit(TOPIC_OUT, 4)
    with pytest.raises(BrokerOverload) as ei:
        b.produce_stamped(TOPIC_OUT, _pairs(10), 1, 0)
    assert ei.value.admitted == 4 and b.dup_suppressed == 4
    b.commit(TOPIC_OUT, 8)
    assert b.produce_stamped(TOPIC_OUT, _pairs(10), 1, 0) == 2
    assert [r.out_seq for r in b.fetch(TOPIC_OUT, 0, 100)] \
        == list(range(10))


def test_a_long_polling_consumer_is_woken_by_the_batch():
    import threading

    b = InProcessBroker()
    provision(b)
    got = []
    th = threading.Thread(
        target=lambda: got.extend(b.fetch(TOPIC_OUT, 0, 8192,
                                          timeout=10.0)))
    th.start()
    b.produce_stamped(TOPIC_OUT, _pairs(300), 1, 0)
    th.join(timeout=10.0)
    assert not th.is_alive()
    assert len(got) == 300


# ---------------------------------------------------------------------------
# (c) the retry, (d) the paths that keep the per-record call, (e) counters


def _feed(broker, n=80, seed=5):
    msgs = harness_stream(n, seed=seed, num_accounts=4, num_symbols=2,
                          payout_opcode_bug=False, validate=True)
    for m in msgs:
        broker.produce(TOPIC_IN, None, dumps_order(m))
    return len(msgs)


def _oracle_svc(broker, ck, **kw):
    return MatchService(broker, engine="oracle", compat="fixed",
                        batch=16, slots=64, max_fills=32,
                        checkpoint_dir=ck, exactly_once=True, **kw)


def test_injected_fault_on_the_batch_call_leaves_no_dup_and_no_gap(
        tmp_path):
    ref = InProcessBroker()
    provision(ref)
    n = _feed(ref)
    _oracle_svc(ref, str(tmp_path / "ck-ref")).run(max_messages=n)
    want = [(r.key, r.value, r.out_seq)
            for r in ref.fetch(TOPIC_OUT, 0, 10 ** 6)]

    b = InProcessBroker(persist_dir=str(tmp_path / "logs"))
    provision(b)
    _feed(b)
    # after seeding: the second and third batch calls fail once each
    faults.configure("broker.produce:n=2:after=1")
    svc = _oracle_svc(b, str(tmp_path / "ck"))
    assert svc.run(max_messages=n) == n
    recs = b.fetch(TOPIC_OUT, 0, 10 ** 6)
    assert [(r.key, r.value, r.out_seq) for r in recs] == want
    assert [r.out_seq for r in recs] == list(range(len(recs)))
    assert b.dup_suppressed == 0        # nothing had been appended
    snap = svc.telemetry.snapshot()
    assert snap["counters"]["broker_retries"] == 2
    assert snap["gauges"]["faults_injected"] == 2


def test_follower_and_callless_broker_count_out_seq_as_before(tmp_path):
    lead = InProcessBroker()
    provision(lead)
    n = _feed(lead, n=40)
    leader = _oracle_svc(lead, str(tmp_path / "ck-lead"))
    leader.run(max_messages=n)

    fb = InProcessBroker()
    provision(fb)
    _feed(fb, n=40)
    calls = []
    fb.produce_stamped = lambda *a, **k: calls.append(a)
    follower = _oracle_svc(fb, str(tmp_path / "ck-fol"), follower=True)
    assert follower.epoch is None
    assert follower.run(max_messages=n) == n
    assert not calls            # a follower never takes the batch call
    assert follower.out_seq == leader.out_seq > 0
    # unstamped, as a follower's records always were
    assert all(r.out_seq is None for r in fb.fetch(TOPIC_OUT, 0, 10 ** 6))

    pb = _PerRecordBroker()
    provision(pb)
    _feed(pb, n=40)
    plain = _oracle_svc(pb, str(tmp_path / "ck-plain"))
    plain.run(max_messages=n)
    assert plain.out_seq == leader.out_seq
    assert [(r.key, r.value, r.epoch, r.out_seq)
            for r in pb.fetch(TOPIC_OUT, 0, 10 ** 6)] \
        == [(r.key, r.value, r.epoch, r.out_seq)
            for r in lead.fetch(TOPIC_OUT, 0, 10 ** 6)]


def test_every_record_of_the_batch_path_is_routed_by_produce_out(
        tmp_path, monkeypatch):
    """`_produce_out` stays the one gate a record passes on its way
    out, on the batch path too: the benchmark's broken host
    (benchmark/broken_host.py) alters one record there, and a run on
    it must come out incorrect."""
    seen = []
    produce_out = MatchService._produce_out

    def altered(self, key, value):
        seen.append(value)
        if len(seen) == 7:
            value = value + " "
        return produce_out(self, key, value)

    monkeypatch.setattr(MatchService, "_produce_out", altered)
    b = InProcessBroker()
    provision(b)
    n = _feed(b, n=40)
    svc = _oracle_svc(b, str(tmp_path / "ck"))
    assert svc.run(max_messages=n) == n
    recs = b.fetch(TOPIC_OUT, 0, 10 ** 6)
    assert len(recs) == len(seen) == svc.out_seq
    assert [r.value for r in recs] == [
        v + " " if i == 6 else v for i, v in enumerate(seen)]
    c = svc.telemetry.snapshot()["counters"]
    assert c["matchout_produce_calls"] == c["service_batches"]


def test_at_least_once_service_keeps_the_per_record_call():
    b = InProcessBroker()
    provision(b)
    n = _feed(b, n=40)
    calls = []
    b.produce_stamped = lambda *a, **k: calls.append(a)
    svc = MatchService(b, engine="oracle", compat="fixed", batch=16,
                       slots=64, max_fills=32)
    assert svc.run(max_messages=n) == n
    assert not calls and svc.epoch is None
    recs = b.fetch(TOPIC_OUT, 0, 10 ** 6)
    assert recs and all(r.out_seq is None for r in recs)
    c = svc.telemetry.snapshot()["counters"]
    assert c["matchout_records"] == c["matchout_produce_calls"] \
        == len(recs)


def test_heartbeat_carries_the_produce_counters(tmp_path):
    b = InProcessBroker()
    provision(b)
    n = _feed(b)
    hb = tmp_path / "hb.json"
    svc = _oracle_svc(b, str(tmp_path / "ck"))
    assert svc.run(max_messages=n, health_file=str(hb)) == n
    svc._write_heartbeat(str(hb), n)
    counters = json.loads(hb.read_text())["metrics"]["counters"]
    nrec = b.end_offset(TOPIC_OUT)
    assert counters["matchout_records"] == nrec > 0
    assert counters["matchout_produce_calls"] \
        == counters["service_batches"] == -(-n // 16)
    # the benchmark's matchout_records_per_produce.sat is this ratio
    assert counters["matchout_records"] \
        / counters["matchout_produce_calls"] > 1
