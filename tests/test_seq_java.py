"""Java-compat mode ON the sequential kernel vs the java oracle.

The round-3 COMPAT.md argument proved quirk-exact PARALLEL execution
impossible under Q11; the sequential kernel has no such obstacle — it
executes the reference's own serial semantics, quirks included: Q1
(merged sid-0 book), Q2 (ghost trades), Q9 (prev echo), Q11
(value-as-key position corruption via a 128-bit-key tombstoned hash).
Scope: the stock wire surface (no barriers / negative sids — dead or
broken reference paths, COMPAT.md); the java ORACLE is the judge.
"""

import pytest

import kme_tpu.opcodes as op
from kme_tpu.engine import seq as SQ
from kme_tpu.oracle import OracleEngine
from kme_tpu.runtime.seqsession import SeqSession, UnsupportedJavaOp
from kme_tpu.wire import OrderMsg
from kme_tpu.workload import harness_stream

JCFG = SQ.SeqConfig(lanes=8, slots=128, accounts=128, max_fills=64,
                    batch=256, pos_cap=1 << 12, fill_cap=1 << 13,
                    probe_max=16, compat="java")


def assert_java_parity(msgs, cfg=JCFG):
    ses = SeqSession(cfg)
    ora = OracleEngine("java")
    got = ses.process_wire(msgs)
    for i, m in enumerate(msgs):
        want = [r.wire() for r in ora.process(m.copy())]
        g = got[i]
        assert g == want, (f"java stream diverged at message {i}: {m}\n"
                           f"got  {g}\nwant {want}")
    exp = ses.export_state()
    assert exp["balances"] == dict(ora.balances)
    assert exp["positions"] == dict(ora.positions)
    oorders = {oid: {"aid": r.aid, "sid": r.sid, "price": r.price,
                     "size": r.size, "is_buy": r.action == op.BUY}
               for oid, r in ora.orders.items()}
    assert exp["orders"] == oorders
    return ses, ora


def test_java_basic_and_q9():
    msgs = [OrderMsg(action=op.CREATE_BALANCE, aid=1),
            OrderMsg(action=op.TRANSFER, aid=1, size=100000),
            OrderMsg(action=op.CREATE_BALANCE, aid=2),
            OrderMsg(action=op.TRANSFER, aid=2, size=100000),
            OrderMsg(action=op.ADD_SYMBOL, sid=1),
            OrderMsg(action=op.BUY, oid=10, aid=1, sid=1, price=40, size=5),
            OrderMsg(action=op.BUY, oid=11, aid=2, sid=1, price=40, size=3),
            OrderMsg(action=op.SELL, oid=12, aid=2, sid=1, price=35,
                     size=6),
            OrderMsg(action=op.CANCEL, oid=11, aid=2),
            OrderMsg(action=op.CANCEL, oid=11, aid=2)]
    assert_java_parity(msgs)


def test_java_q2_ghost_trade():
    """Simultaneous taker/maker exhaustion with another crossing maker
    left: the reference emits one zero-size BOUGHT/SOLD pair (Q2)."""
    msgs = [OrderMsg(action=op.CREATE_BALANCE, aid=1),
            OrderMsg(action=op.TRANSFER, aid=1, size=10**6),
            OrderMsg(action=op.CREATE_BALANCE, aid=2),
            OrderMsg(action=op.TRANSFER, aid=2, size=10**6),
            OrderMsg(action=op.ADD_SYMBOL, sid=1),
            # two bids at 50; a sell for exactly the first bid's size
            OrderMsg(action=op.BUY, oid=10, aid=1, sid=1, price=50,
                     size=4),
            OrderMsg(action=op.BUY, oid=11, aid=1, sid=1, price=50,
                     size=3),
            OrderMsg(action=op.SELL, oid=12, aid=2, sid=1, price=45,
                     size=4),
            # and the BUY-side ghost: asks at 55, buy exactly consumes
            OrderMsg(action=op.SELL, oid=13, aid=2, sid=1, price=55,
                     size=2),
            OrderMsg(action=op.SELL, oid=14, aid=2, sid=1, price=55,
                     size=9),
            OrderMsg(action=op.BUY, oid=15, aid=1, sid=1, price=60,
                     size=2)]
    ses, ora = assert_java_parity(msgs)
    # the sell at 45 must have produced a zero-size trade pair
    flat = [l for ls in ses.process_wire([]) for l in ls]  # no-op
    del flat


def test_java_q1_merged_sid0_book():
    """sid=0: -0 == 0, so buys and sells share one book — buys match
    against resting buys (the reference's own behavior)."""
    msgs = [OrderMsg(action=op.CREATE_BALANCE, aid=1),
            OrderMsg(action=op.TRANSFER, aid=1, size=10**6),
            OrderMsg(action=op.CREATE_BALANCE, aid=2),
            OrderMsg(action=op.TRANSFER, aid=2, size=10**6),
            OrderMsg(action=op.ADD_SYMBOL, sid=0),
            OrderMsg(action=op.BUY, oid=10, aid=1, sid=0, price=50,
                     size=5),
            # a second buy at a lower price CROSSES the resting buy
            OrderMsg(action=op.BUY, oid=11, aid=2, sid=0, price=50,
                     size=3),
            OrderMsg(action=op.SELL, oid=12, aid=2, sid=0, price=40,
                     size=4),
            OrderMsg(action=op.CANCEL, oid=10, aid=1)]
    assert_java_parity(msgs)


def test_java_q11_value_as_key():
    """Repeated fills on one (aid, sid): the second fill writes a
    garbage (amount, available) key while the real key stays stale —
    and margin netting reads the stale available (Q11)."""
    msgs = [OrderMsg(action=op.CREATE_BALANCE, aid=1),
            OrderMsg(action=op.TRANSFER, aid=1, size=10**6),
            OrderMsg(action=op.CREATE_BALANCE, aid=2),
            OrderMsg(action=op.TRANSFER, aid=2, size=10**6),
            OrderMsg(action=op.ADD_SYMBOL, sid=1)]
    oid = 100
    for k in range(10):
        msgs.append(OrderMsg(action=op.BUY, oid=oid, aid=1, sid=1,
                             price=50, size=2 + k))
        oid += 1
        msgs.append(OrderMsg(action=op.SELL, oid=oid, aid=2, sid=1,
                             price=45, size=1 + k))
        oid += 1
    ses, ora = assert_java_parity(msgs)
    # the oracle must have accumulated garbage-keyed entries
    garbage = [k for k in ora.positions if k not in
               {(1, 1), (2, 1)}]
    assert garbage, "workload failed to exercise Q11"


@pytest.mark.slow
def test_java_harness_parity():
    """The stock harness distribution (incl. Q5 payouts-as-cancels and
    sid=0 trading) byte-exact vs the java oracle."""
    msgs = harness_stream(1500, seed=3)
    assert_java_parity(msgs, SQ.SeqConfig(
        lanes=8, slots=256, accounts=128, max_fills=64, batch=256,
        pos_cap=1 << 13, fill_cap=1 << 14, probe_max=16, compat="java",
        hbm_books=True))


def test_java_unsupported_ops_raise():
    ses = SeqSession(JCFG)
    with pytest.raises(UnsupportedJavaOp):
        ses.process_wire([OrderMsg(action=op.PAYOUT, sid=1, size=97)])
    with pytest.raises(UnsupportedJavaOp):
        ses.process_wire([OrderMsg(action=op.ADD_SYMBOL, sid=-3)])


def test_java_seq_service(tmp_path):
    """kme-serve's engine='seq' + compat='java': the full service loop
    byte-exact vs the java oracle on the stock harness shape."""
    from kme_tpu.bridge.broker import InProcessBroker
    from kme_tpu.bridge.consume import consume_lines
    from kme_tpu.bridge.provision import provision
    from kme_tpu.bridge.service import MatchService
    from kme_tpu.wire import dumps_order

    msgs = harness_stream(400, seed=5)
    ora = OracleEngine("java")
    want = []
    for m in msgs:
        for r in ora.process(m.copy()):
            want.append(r.wire())
    b = InProcessBroker()
    provision(b)
    for m in msgs:
        b.produce("MatchIn", None, dumps_order(m))
    svc = MatchService(b, engine="seq", compat="java", batch=64,
                       symbols=8, accounts=128, slots=256, max_fills=64)
    assert svc.run(max_messages=len(msgs)) == len(msgs)
    got = list(consume_lines(b, follow=False))
    assert got == want
    # durable java serving works since round 5 (seqjava snapshots,
    # runtime/javasnap.py) — the constructor must ACCEPT a checkpoint
    # dir (kill/resume itself is covered by
    # tests/test_checkpoint.py::test_seqjava_service_kill_resume)
    svc2 = MatchService(b, engine="seq", compat="java", symbols=8,
                        accounts=128, slots=256, max_fills=64,
                        checkpoint_dir=str(tmp_path))
    assert svc2 is not None


def test_java_seq_service_degrades_on_barrier(tmp_path):
    """COMPAT.md closure: a java-mode stream that hits a REAL barrier
    (PAYOUT opcode — outside the device surface, Q3-Q6) mid-stream.
    The service converts the seq session's state to the native engine
    (runtime/javasnap.py) and continues there; the full MatchOut
    stream is byte-exact vs an uninterrupted java-oracle run."""
    from kme_tpu.bridge.broker import InProcessBroker
    from kme_tpu.bridge.consume import consume_lines
    from kme_tpu.bridge.provision import provision
    from kme_tpu.bridge.service import MatchService
    from kme_tpu.wire import OrderMsg, dumps_order
    from kme_tpu import opcodes as op

    msgs = harness_stream(600, seed=21)
    # inject a REAL payout barrier (the harness's own payouts carry the
    # CANCEL opcode, Q5) on an ABSENT book — a payout on a non-empty
    # book is a ReferenceHang (Q4), which no engine may survive; the
    # absent-book payout is the processable barrier shape
    barrier = OrderMsg(action=op.PAYOUT, sid=99, size=3)
    mixed = msgs[:400] + [barrier] + msgs[400:]
    ora = OracleEngine("java")
    want = [r.wire() for m in mixed for r in ora.process(m.copy())]

    b = InProcessBroker()
    provision(b)
    for m in mixed:
        b.produce("MatchIn", None, dumps_order(m))
    svc = MatchService(b, engine="seq", compat="java", batch=64,
                       symbols=8, accounts=128, slots=256, max_fills=64)
    assert svc.run(max_messages=len(mixed)) == len(mixed)
    assert svc._native is not None and svc._session is None, \
        "service should have degraded to the native engine"
    got = list(consume_lines(b, follow=False))
    assert got == want


@pytest.mark.parametrize("bad", [
    OrderMsg(action=op.SELL, oid=901, aid=3, sid=2, price=-1, size=3),
    OrderMsg(action=op.BUY, oid=902, aid=2, sid=1, price=45, size=0),
    OrderMsg(action=op.BUY, oid=903, aid=1, sid=2, price=44, size=-7),
    OrderMsg(action=op.SELL, oid=904, aid=4, sid=1, price=126, size=5),
], ids=["price-1", "size0", "size-7", "price126"])
def test_java_seq_service_degrades_on_out_of_domain_trade(bad):
    """The stock harness draws prices and sizes as floor(N(50, 10)):
    about one trade in a million is zero or negative (seed 48151623 of
    the benchmark's stream: message 85,268 is a SELL at price -1). The
    java device domain excludes it; on the device it was a sticky
    LERR_JAVA_DOMAIN after the state had been touched and the server
    died (rc 1). The router now refuses it before anything is touched,
    and the service continues on the native engine, byte-exact vs an
    uninterrupted java-oracle run. (The cases are ones the reference
    itself survives: a resting order at a negative price can later kill
    it with Q7's NPE, and then every engine dies with it.)"""
    from kme_tpu.bridge.broker import InProcessBroker
    from kme_tpu.bridge.consume import consume_lines
    from kme_tpu.bridge.provision import provision
    from kme_tpu.bridge.service import MatchService
    from kme_tpu.wire import dumps_order

    msgs = harness_stream(300, seed=21)
    mixed = msgs[:200] + [bad] + msgs[200:]
    ora = OracleEngine("java")
    want = [r.wire() for m in mixed for r in ora.process(m.copy())]
    b = InProcessBroker()
    provision(b)
    for m in mixed:
        b.produce("MatchIn", None, dumps_order(m))
    svc = MatchService(b, engine="seq", compat="java", batch=64,
                       symbols=8, accounts=128, slots=256, max_fills=64)
    assert svc.run(max_messages=len(mixed)) == len(mixed)
    assert svc.engine_in_effect() == "native" and svc._session is None
    assert list(consume_lines(b, follow=False)) == want
    # the heartbeat says when: the offset of the batch that left (the
    # trade is message 200, in the fourth batch of 64)
    snap = svc.telemetry.snapshot()
    assert snap["gauges"]["left_device_at_offset"] == 192
