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


# ---------------------------------------------------------------------------
# (f) PR 44: a run stays one buffer — `produce_stamped_buffer` and the
# serve loop's `_produce_runs` against the per-record walk, which stays
# the definition. (The parity cases of (a) now run the buffer path on
# their `InProcessBroker` side.)

import numpy as np  # noqa: E402

from kme_tpu.bridge.broker import (Record, Run, _run_rows,  # noqa: E402
                                   _stamped_rows, run_of_pairs,
                                   split_run)
from kme_tpu.bridge.front import _MARK_SUB  # noqa: E402


class _Clock:
    """Every admission stamp the same, so that two brokers' Records
    compare equal field for field."""

    def time_us(self):
        return 1_700_000_000_000_000

    def monotonic(self):
        return 0.0

    def sleep(self, s):
        pass


def _buffer_of(lines):
    """"KEY value" lines as `SeqSession.collect` returns them."""
    off = np.zeros(len(lines) + 1, np.int64)
    np.cumsum([len(ln.encode()) for ln in lines], out=off[1:])
    return "".join(lines).encode(), off


def _lines(n, start=0):
    return ['%s {"action":2,"oid":%d,"next":null}'
            % ("IN" if i % 3 == 0 else "OUT", i)
            for i in range(start, start + n)]


BUFFER_PARITY_CASES = [
    pytest.param(dict(_SEQ_FIXED, pipeline=2, symbols=32), (0, 2),
                 id="seq-fixed-pipelined-xfer", marks=needs_native),
    pytest.param(dict(_SEQ_FIXED, pipeline=0, symbols=32), (0, 2),
                 id="seq-fixed-serial-xfer"),
    pytest.param(_SEQ_JAVA, None, id="seq-java-serial"),
    pytest.param(dict(_SEQ_FIXED, pipeline=2), None,
                 id="seq-fixed-pipelined", marks=needs_native),
]


@pytest.mark.parametrize("kw,group", BUFFER_PARITY_CASES)
def test_buffer_path_engages_and_leaves_the_walks_bytes(tmp_path, kw,
                                                        group):
    lines = (_grouped_lines() if group
             else _harness_lines(kw["compat"]))
    want_d, want_svc, want_snap = _serve(
        tmp_path, "record", _PerRecordBroker, kw, group, lines)
    got_d, got_svc, got_snap = _serve(
        tmp_path, "buffer", InProcessBroker, kw, group, lines)
    topics = group_topics(group[0])[1:] if group else (TOPIC_OUT,)
    for topic in topics:
        assert _log(got_d, topic) == _log(want_d, topic) != b"", topic
    wc, gc = want_snap["counters"], got_snap["counters"]
    # the counter says how often the buffer path engaged: never on the
    # broker that hides the batch call, for every record that is not an
    # Xfer leg on the one that has it
    assert wc["matchout_records_buffered"] == 0
    n_xfer = (len(_log(got_d, topics[1]).splitlines()) if group else 0)
    assert gc["matchout_records_buffered"] \
        == gc["matchout_records"] - n_xfer > 0
    assert (n_xfer > 0) == bool(group)
    assert got_svc.out_seq == want_svc.out_seq


def _grouped_oracle(tmp_path, name, broker_cls):
    b = broker_cls(persist_dir=str(tmp_path / f"{name}-logs"),
                   clock=_Clock())
    provision(b, topics=group_topics(0))
    svc = MatchService(b, checkpoint_dir=str(tmp_path / f"{name}-ck"),
                       exactly_once=True, group=(0, 2), **_ORACLE)
    return b, svc


@pytest.mark.parametrize("marked", [
    (0,), (5,), (11,), (0, 11), (4, 5), tuple(range(12)), ()],
    ids=["first", "mid", "last", "first-and-last", "adjacent", "all",
         "none"])
@pytest.mark.parametrize("entry", ["buffer", "lines"])
def test_an_xfer_marked_line_splits_the_buffer_where_it_stands(
        tmp_path, marked, entry):
    lines = _lines(12)
    for i in marked:
        lines[i] = lines[i][:-1] + ',%s}' % _MARK_SUB
    # a match that is no mark: in a key, and across two lines
    lines.append('%s {"action":2}' % _MARK_SUB)
    half = len(_MARK_SUB) // 2
    lines += ['OUT {"a":1}' + _MARK_SUB[:half],
              _MARK_SUB[half:] + ' {"b":2}']
    logs = {}
    for name, cls in (("walk", _PerRecordBroker),
                      ("buffer", InProcessBroker)):
        b, svc = _grouped_oracle(tmp_path, name, cls)
        if entry == "buffer":
            svc._produce_buffer(*_buffer_of(lines))
        else:
            svc._produce_lines([lines[:7], [], lines[7:]])
        assert svc.out_seq == len(lines)
        logs[name] = (
            _log(b._persist_dir, svc.topic_out),
            _log(b._persist_dir, svc.topic_xfer),
            [dataclasses_astuple(r)
             for t in (svc.topic_out, svc.topic_xfer)
             for r in b.fetch(t, 0, 100)])
        runs = svc._out_calls - len(marked)
        if name == "buffer":
            assert svc._out_buffered == len(lines) - len(marked)
            # one call a run between two marked lines
            assert runs <= len(marked) + 1
        else:
            assert svc._out_buffered == 0
        svc.close()
    assert logs["buffer"] == logs["walk"]
    assert len(logs["walk"][1].splitlines()) == len(marked)


def dataclasses_astuple(r):
    return (r.offset, r.key, r.value, r.epoch, r.out_seq, r.ats, r.tid)


_AWKWARD = [
    ("IN", '{"a":"q\\"uote\\\\ back"}'), (None, "keyless"),
    ("OUT", "ctl \x00\x01\x1f\x7f \n\r\t\b\f end"), ("", ""),
    ("k y", " leading space"), ("é", "ü ☃ \U0001f600 \ud800"),
    ("OUT", ""), ("K" * 254, "v"), (None, ""), ("a", "x" * 5000)]


def test_native_rows_are_byte_equal_to_stamped_rows():
    buf, off, klen = run_of_pairs(_AWKWARD)
    run = Run(buf, off, klen, 0, len(_AWKWARD), 0, 9, 1 << 40, None)
    assert run.pairs() == _AWKWARD
    want = _stamped_rows(_AWKWARD, 9, 1 << 40)
    wantb = [r.encode("ascii") for r in want]
    assert _run_rows(run, 0, len(_AWKWARD)) == b"".join(wantb)
    assert [json.loads(r) for r in want] == [
        [k, v, 9, (1 << 40) + i] for i, (k, v) in enumerate(_AWKWARD)]
    # any slice, stamped from where it starts
    assert _run_rows(run, 3, 7) == b"".join(wantb[3:7])
    assert _run_rows(run, 4, 4) == b""
    # bytes that are not utf-8 take the twin, which says so
    bad = Run(b"OUT \xff\xfe", np.array([0, 6], np.int64),
              np.array([3], np.int32), 0, 1, 0, 1, 0, None)
    with pytest.raises(UnicodeDecodeError):
        _run_rows(bad, 0, 1)


def test_split_run_is_partition_at_the_first_space():
    lines = ["IN {}", "OUT a b c", "nospace", "", " x", "tail "]
    buf, off = _buffer_of(lines)
    assert split_run(buf, off).tolist() == [2, 3, 7, 0, 0, 4]
    run = Run(buf, off, split_run(buf, off), 0, len(lines), 0, 1, 0, 5)
    assert run.pairs() == [(k, v) for k, _, v in
                           (ln.partition(" ") for ln in lines)]


@pytest.fixture
def no_native(monkeypatch):
    import kme_tpu.native as native

    monkeypatch.setattr(native, "load_library", lambda: None)


def test_the_fallback_without_the_native_library(tmp_path, no_native):
    lines = _lines(40) + ["nospace", 'OUT {"q":"\\"\\\\"}']
    buf, off = _buffer_of(lines)
    assert split_run(buf, off).tolist() == [
        len(ln.partition(" ")[0]) for ln in lines]
    a = InProcessBroker(persist_dir=str(tmp_path / "a"), clock=_Clock())
    b = InProcessBroker(persist_dir=str(tmp_path / "b"), clock=_Clock())
    for br in (a, b):
        provision(br)
    for i, ln in enumerate(lines):
        key, _, value = ln.partition(" ")
        a.produce(TOPIC_OUT, key, value, epoch=2, out_seq=i)
    assert b.produce_stamped_buffer(TOPIC_OUT, buf, off, 2, 0) \
        == len(lines)
    assert _log(str(tmp_path / "b"), TOPIC_OUT) \
        == _log(str(tmp_path / "a"), TOPIC_OUT)
    assert b.fetch(TOPIC_OUT, 0, 100) == a.fetch(TOPIC_OUT, 0, 100)


def test_fetch_returns_equal_records_either_way(tmp_path):
    """A run in the log and the same records one by one: fetch() makes
    equal Records — offset, key, value, epoch, out_seq, ats — whole,
    from inside a run, across a run's end and across single records."""
    lines = _lines(30)
    pairs = [tuple(ln.split(" ", 1)) for ln in lines]
    a = InProcessBroker(clock=_Clock())
    b = InProcessBroker(clock=_Clock())
    for br in (a, b):
        provision(br)
        br.produce(TOPIC_OUT, "PRE", "single before")
    for i, (k, v) in enumerate(pairs):
        a.produce(TOPIC_OUT, k, v, epoch=4, out_seq=i)
    buf, off = _buffer_of(lines)
    assert b.produce_stamped_buffer(TOPIC_OUT, buf[:int(off[10])],
                                    off[:11], 4, 0) == 10
    assert b.produce_stamped_buffer(TOPIC_OUT, buf, off[10:], 4, 10) \
        == 20
    for br in (a, b):
        br.produce(TOPIC_OUT, None, "single after", epoch=4, out_seq=30)
    assert a.end_offset(TOPIC_OUT) == b.end_offset(TOPIC_OUT) == 32
    for lo, n in ((0, 1000), (0, 1), (1, 10), (3, 4), (5, 12), (10, 1),
                  (11, 25), (30, 5), (31, 1), (32, 5), (99, 1)):
        got = b.fetch(TOPIC_OUT, lo, n)
        assert got == a.fetch(TOPIC_OUT, lo, n), (lo, n)
        assert all(type(r) is Record for r in got)
    # the log holds two runs and three single records, not 32 objects
    log = b._topics[TOPIC_OUT].log
    assert [type(s) for s in log._segs] == [list, Run, Run, list]
    pieces = b.fetch_runs(TOPIC_OUT, 5, 12)
    assert [(type(p), p.base, p.n) for p in pieces] \
        == [(Run, 5, 6), (Run, 11, 6)]
    # a stretch of single Records comes back as one list of them
    pieces = b.fetch_runs(TOPIC_OUT, 0, 3)
    assert [type(p) for p in pieces] == [list, Run]
    assert pieces[0] == a.fetch(TOPIC_OUT, 0, 1) and pieces[1].n == 2


@pytest.mark.parametrize("below", [0, 4, 10],
                         ids=["none-below", "partly-below",
                              "wholly-below"])
def test_replayed_buffer_suppresses_its_prefix(tmp_path, below):
    d = str(tmp_path / "logs")
    b = InProcessBroker(persist_dir=d)
    provision(b)
    lines = _lines(10)
    buf, off = _buffer_of(lines)
    assert b.produce_stamped_buffer(
        TOPIC_OUT, buf, off[:below + 1], 1, 100) == below
    assert b.produce_stamped_buffer(TOPIC_OUT, buf, off, 1, 100) \
        == 10 - below
    assert b.dup_suppressed == below
    recs = b.fetch(TOPIC_OUT, 0, 100)
    assert [f"{r.key} {r.value}" for r in recs] == lines
    assert [r.out_seq for r in recs] == list(range(100, 110))
    assert [r.offset for r in recs] == list(range(10))
    assert b.produce_stamped_buffer(TOPIC_OUT, buf, off, 1, 100) == 0
    assert b.dup_suppressed == below + 10
    # the rows on disk are those of ten single produces, and a restart
    # reads the same log back
    ref = InProcessBroker(persist_dir=str(tmp_path / "ref"))
    provision(ref)
    for i, ln in enumerate(lines):
        ref.produce(TOPIC_OUT, *ln.split(" ", 1), epoch=1,
                    out_seq=100 + i)
    assert _log(d, TOPIC_OUT) == _log(str(tmp_path / "ref"), TOPIC_OUT)
    del b
    again = InProcessBroker(persist_dir=d)
    strip = [(r.offset, r.key, r.value, r.epoch, r.out_seq)
             for r in again.fetch(TOPIC_OUT, 0, 100)]
    assert strip == [(r.offset, r.key, r.value, r.epoch, r.out_seq)
                     for r in recs]
    assert again.produce_stamped_buffer(TOPIC_OUT, buf, off, 2, 100) == 0


def test_buffer_call_fencing_fault_and_one_write(tmp_path):
    d = str(tmp_path)
    b = InProcessBroker(persist_dir=d)
    provision(b)
    buf, off = _buffer_of(_lines(6))
    faults.configure("broker.produce:n=1")
    with pytest.raises(BrokerError, match="injected fault"):
        b.produce_stamped_buffer(TOPIC_OUT, buf, off, 2, 0)
    assert b.end_offset(TOPIC_OUT) == 0 and _log(d, TOPIC_OUT) == b""
    assert b.produce_stamped_buffer(TOPIC_OUT, buf, off, 2, 0) == 6
    assert faults.fired_total() == 1
    before = _log(d, TOPIC_OUT)
    with pytest.raises(BrokerFenced):
        b.produce_stamped_buffer(TOPIC_OUT, buf, off, 1, 6)
    assert b.fenced_produces == 1 and b.end_offset(TOPIC_OUT) == 6
    assert _log(d, TOPIC_OUT) == before
    with pytest.raises(BrokerError, match="unknown topic"):
        b.produce_stamped_buffer("NoSuchTopic", buf, off, 2, 6)
    # an offsets array of another dtype is refused before the C call
    from kme_tpu.native import BoundaryError

    with pytest.raises(BoundaryError):
        b.produce_stamped_buffer(TOPIC_OUT, buf, off.astype(np.int32),
                                 2, 6)
    # and so are offsets that leave the buffer or fall
    for bad in (off + 1, off - 1, off[::-1].copy()):
        with pytest.raises(BoundaryError):
            b.produce_stamped_buffer(TOPIC_OUT, buf, bad, 2, 6)
    assert b.end_offset(TOPIC_OUT) == 6
    # an empty run appends, writes and wakes nothing
    assert b.produce_stamped_buffer(TOPIC_OUT, b"", off[:1], 2, 6) == 0
    assert _log(d, TOPIC_OUT) == before


# what Python's utf-8 decoder refuses and a lenient one would take:
# a byte that starts nothing, overlong forms, a code point past
# U+10FFFF, a sequence cut short by the line's end
_NOT_UTF8 = [b"\xff\xfe", b"\xc0\x80", b"\xe0\x80\x80",
             b"\xf4\x90\x80\x80", b"\xe2\x82"]


@pytest.mark.parametrize("tail", _NOT_UTF8, ids=[t.hex() for t in _NOT_UTF8])
@pytest.mark.parametrize("persist", [True, False],
                         ids=["persisted", "in-memory"])
def test_bytes_that_are_not_utf8_never_enter_the_log(tmp_path, tail,
                                                     persist):
    """The native rows and the twin refuse the same bytes, and the
    buffer call refuses them at the door whether or not a log file
    would have asked for rows: nothing enters the log that fetch()
    could not make a Record of."""
    line = b"OUT " + tail
    off = np.array([0, len(line)], np.int64)
    with pytest.raises(UnicodeDecodeError):
        _run_rows(Run(line, off, np.array([3], np.int32), 0, 1, 0, 1, 0,
                      None), 0, 1)
    b = InProcessBroker(persist_dir=str(tmp_path) if persist else None)
    provision(b)
    # one good line ahead of it: the whole run is refused, not a suffix
    buf = b"IN {} " + line
    off = np.array([0, 6, len(buf)], np.int64)
    with pytest.raises(UnicodeDecodeError):
        b.produce_stamped_buffer(TOPIC_OUT, buf, off, 1, 0)
    assert b.end_offset(TOPIC_OUT) == 0 and b.fetch(TOPIC_OUT, 0) == []
    if persist:
        assert _log(str(tmp_path), TOPIC_OUT) == b""
    # and what is utf-8, surrogates passed, goes in and comes back
    good = "IN \u00e9\u20ac\U0001f600\ud800".encode("utf-8", "surrogatepass")
    assert b.produce_stamped_buffer(
        TOPIC_OUT, good, np.array([0, len(good)], np.int64), 1, 0) == 1
    assert [(r.key, r.value) for r in b.fetch(TOPIC_OUT, 0)] == [
        ("IN", "\u00e9\u20ac\U0001f600\ud800")]


@pytest.mark.parametrize("controller", [False, True],
                         ids=["max-lag", "controller"])
def test_a_bounded_topic_keeps_its_prefix_on_the_buffer_call(controller):
    kw = ({"overload": OverloadController(high_lag=4, low_lag=1,
                                          drain_lag=4)}
          if controller else {"max_lag": 4})
    b = InProcessBroker(**kw)
    provision(b)
    b.commit(TOPIC_OUT, 0)
    buf, off = _buffer_of(_lines(10))
    with pytest.raises(BrokerOverload) as ei:
        b.produce_stamped_buffer(TOPIC_OUT, buf, off, 1, 0)
    assert ei.value.admitted == 4 and b.end_offset(TOPIC_OUT) == 4
    assert b.overload_rejects == 1 and b.wire_json_records == 4
    assert (ei.value.detail is not None) == controller
    b.commit(TOPIC_OUT, 4)
    with pytest.raises(BrokerOverload) as ei:
        b.produce_stamped_buffer(TOPIC_OUT, buf, off, 1, 0)
    assert ei.value.admitted == 4 and b.dup_suppressed == 4
    b.commit(TOPIC_OUT, 8)
    assert b.produce_stamped_buffer(TOPIC_OUT, buf, off, 1, 0) == 2
    recs = b.fetch(TOPIC_OUT, 0, 100)
    assert [r.out_seq for r in recs] == list(range(10))
    assert [f"{r.key} {r.value}" for r in recs] == _lines(10)


def test_injected_fault_on_the_buffer_call_is_retried(tmp_path):
    ref = InProcessBroker()
    provision(ref)
    n = _feed(ref)
    _oracle_svc(ref, str(tmp_path / "ck-ref")).run(max_messages=n)
    want = [(r.key, r.value, r.out_seq)
            for r in ref.fetch(TOPIC_OUT, 0, 10 ** 6)]
    b = InProcessBroker(persist_dir=str(tmp_path / "logs"))
    provision(b)
    _feed(b)
    faults.configure("broker.produce:n=2:after=1")
    svc = _oracle_svc(b, str(tmp_path / "ck"))
    assert svc.run(max_messages=n) == n
    recs = b.fetch(TOPIC_OUT, 0, 10 ** 6)
    assert [(r.key, r.value, r.out_seq) for r in recs] == want
    assert [r.out_seq for r in recs] == list(range(len(recs)))
    assert b.dup_suppressed == 0
    snap = svc.telemetry.snapshot()
    assert snap["counters"]["broker_retries"] == 2
    assert snap["counters"]["matchout_records_buffered"] == len(recs)


def test_heartbeat_carries_matchout_records_buffered(tmp_path):
    b = InProcessBroker()
    provision(b)
    n = _feed(b)
    hb = tmp_path / "hb.json"
    svc = _oracle_svc(b, str(tmp_path / "ck"))
    # there from the first heartbeat, at 0: a reader of two snapshots
    # needs the key in both
    svc._write_heartbeat(str(hb), 0)
    assert json.loads(hb.read_text())["metrics"]["counters"][
        "matchout_records_buffered"] == 0
    assert svc.run(max_messages=n, health_file=str(hb)) == n
    svc._write_heartbeat(str(hb), n)
    counters = json.loads(hb.read_text())["metrics"]["counters"]
    # the benchmark's matchout_buffer_share.sat is this ratio
    assert counters["matchout_records_buffered"] \
        == counters["matchout_records"] == b.end_offset(TOPIC_OUT) > 0


def test_a_replaced_gate_sees_every_record_and_nothing_is_buffered(
        tmp_path):
    """An instance whose `_produce_out` is not the class's own (here a
    subclass) is served by the walk, as a monkeypatched class is."""
    seen = []

    class Tapped(MatchService):
        def _produce_out(self, key, value):
            seen.append((key, value))
            return super()._produce_out(key, value)

    b = InProcessBroker()
    provision(b)
    n = _feed(b, n=40)
    svc = Tapped(b, engine="oracle", compat="fixed", batch=16, slots=64,
                 max_fills=32, checkpoint_dir=str(tmp_path / "ck"),
                 exactly_once=True)
    assert svc.run(max_messages=n) == n
    recs = b.fetch(TOPIC_OUT, 0, 10 ** 6)
    assert [(r.key, r.value) for r in recs] == seen and seen
    c = svc.telemetry.snapshot()["counters"]
    assert c["matchout_records_buffered"] == 0
    assert c["matchout_produce_calls"] == c["service_batches"]


@pytest.mark.parametrize("rows, want", [
    (b'["OUT","v",1,2]\n', b'["OUT","v",1,2]\n'),
    (['["IN","a"]\n', '["OUT","b",1,0]\n'],
     b'["IN","a"]\n["OUT","b",1,0]\n')], ids=["run-bytes", "row-strings"])
def test_the_log_file_is_binary_and_takes_a_runs_rows_as_they_are(
        tmp_path, rows, want):
    """A run's rows go from the native call to the file as bytes — no
    `str` between them — and every other writer's ASCII rows through
    the same one write + flush."""
    from kme_tpu.bridge.broker import _flush_log_lines

    b = InProcessBroker(persist_dir=str(tmp_path))
    provision(b)
    f = b._topics[TOPIC_OUT].logfile
    assert "b" in f.mode
    wrote = []
    write = f.write

    class _Tap:
        def write(self, s):
            wrote.append(s)
            return write(s)

        flush = staticmethod(f.flush)

    _flush_log_lines(_Tap(), rows)
    assert wrote == [want]
    assert _log(str(tmp_path), TOPIC_OUT) == want
