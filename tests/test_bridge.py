"""End-to-end transport bridge tests.

The capability bar (SURVEY.md §7 step 5): the harness roles — provision,
load generator, engine, consumer — run against the MatchIn/MatchOut
topics and the consumer sees the exact `<key> <value>` line stream the
reference's consumer.js:19 prints. Byte parity is judged against the
scalar oracle replica on the same input stream.
"""

import subprocess
import sys
import time

import kme_tpu.opcodes as op
from kme_tpu.bridge.broker import InProcessBroker
from kme_tpu.bridge.consume import consume_lines
from kme_tpu.bridge.provision import provision
from kme_tpu.bridge.service import TOPIC_IN, TOPIC_OUT, MatchService
from kme_tpu.oracle import OracleEngine
from kme_tpu.wire import OrderMsg, dumps_order
from kme_tpu.workload import harness_stream


def _oracle_lines(msgs, compat, **kw):
    ora = OracleEngine(compat, **kw)
    out = []
    for m in msgs:
        out.extend(r.wire() for r in ora.process(m.copy()))
    return out


def _pump(broker, msgs):
    for m in msgs:
        broker.produce(TOPIC_IN, None, dumps_order(m))


def test_bridge_e2e_oracle_java_quirk_exact():
    """Stock harness stream through the oracle-backed service: the
    MatchOut line stream is byte-identical to the reference replica in
    java-compat mode (quirks included)."""
    broker = InProcessBroker()
    assert provision(broker) == {TOPIC_IN: True, TOPIC_OUT: True}
    msgs = harness_stream(400, seed=11)
    _pump(broker, msgs)
    svc = MatchService(broker, engine="oracle", compat="java", batch=64)
    assert svc.run(max_messages=len(msgs)) == len(msgs)
    got = list(consume_lines(broker, follow=False))
    assert got == _oracle_lines(msgs, "java")


def test_bridge_e2e_seq_engine_fixed():
    """Validated workload through the device engine's service; byte
    parity vs the enveloped fixed-mode oracle."""
    broker = InProcessBroker()
    provision(broker)
    msgs = harness_stream(400, seed=5, num_symbols=4, num_accounts=8,
                          payout_opcode_bug=False, validate=True)
    _pump(broker, msgs)
    svc = MatchService(broker, engine="seq", compat="fixed", batch=128,
                       symbols=8, accounts=16, slots=128, max_fills=32)
    assert svc.run(max_messages=len(msgs)) == len(msgs)
    got = list(consume_lines(broker, follow=False))
    assert got == _oracle_lines(msgs, "fixed", book_slots=128, max_fills=32)


def test_bridge_e2e_native_engine_quirk_exact():
    """Stock harness through the native C++ engine service: byte-
    identical MatchOut stream (the fast java-compat serving path)."""
    import pytest

    nat = pytest.importorskip("kme_tpu.native.oracle")
    if not nat.native_available():
        pytest.skip("native library unavailable")
    broker = InProcessBroker()
    provision(broker)
    msgs = harness_stream(600, seed=21)
    _pump(broker, msgs)
    svc = MatchService(broker, engine="native", compat="java", batch=128)
    assert svc.run(max_messages=len(msgs)) == len(msgs)
    got = list(consume_lines(broker, follow=False))
    assert got == _oracle_lines(msgs, "java")


def test_bridge_native_engine_death_forwards_prefix():
    """A reference-death message mid-batch: every record of the earlier
    messages reaches MatchOut BEFORE the service dies (the reference
    forwards per record; its thread dies on the poisoned one)."""
    import pytest

    nat = pytest.importorskip("kme_tpu.native.oracle")
    if not nat.native_available():
        pytest.skip("native library unavailable")
    from kme_tpu.oracle.engine import ReferenceHang

    broker = InProcessBroker()
    provision(broker)
    msgs = [OrderMsg(action=op.CREATE_BALANCE, aid=1),
            OrderMsg(action=op.TRANSFER, aid=1, size=100000),
            OrderMsg(action=op.ADD_SYMBOL, sid=1),
            OrderMsg(action=op.BUY, oid=5, aid=1, sid=1, price=50, size=3),
            OrderMsg(action=op.REMOVE_SYMBOL, sid=1)]  # Q4 hang
    _pump(broker, msgs)
    svc = MatchService(broker, engine="native", compat="java", batch=64)
    with pytest.raises(ReferenceHang):
        svc.run(max_messages=len(msgs))
    got = list(consume_lines(broker, follow=False))
    assert got == _oracle_lines(msgs[:4], "java")


def test_bridge_envelope_overflow_record_policy():
    """A wire-parseable record with price/size outside int32 is outside
    the Jackson envelope (Java int fields — the reference's deserializer
    dies on it): same drop/strict policy as non-JSON, for EVERY engine,
    and the stream continues past it."""
    for engine, compat in (("oracle", "java"), ("native", "java"),
                           ("seq", "fixed")):
        if engine == "native":
            import pytest

            nat = pytest.importorskip("kme_tpu.native.oracle")
            if not nat.native_available():
                continue
        broker = InProcessBroker()
        provision(broker)
        good1 = '{"action":100,"aid":1}'
        poison = '{"action":2,"oid":1,"aid":1,"sid":1,"price":4294967296,"size":1}'
        good2 = '{"action":101,"aid":1,"size":5}'
        for v in (good1, poison, good2):
            broker.produce(TOPIC_IN, None, v)
        svc = MatchService(broker, engine=engine, compat=compat, batch=16,
                           symbols=4, accounts=8)
        assert svc.run(max_messages=3) == 3
        got = list(consume_lines(broker, follow=False))
        from kme_tpu.wire import parse_order

        want = _oracle_lines([parse_order(good1), parse_order(good2)],
                             compat)
        assert got == want, f"engine={engine}"


def test_bridge_malformed_record_policy():
    """Bad JSON is dropped (non-strict) or raises (strict — the
    reference serde kills the stream thread, KProcessor.java:513-517)."""
    import pytest

    broker = InProcessBroker()
    provision(broker)
    broker.produce(TOPIC_IN, None, '{"action":100,"aid":1}')
    broker.produce(TOPIC_IN, None, "not json at all")
    broker.produce(TOPIC_IN, None, '{"action":101,"aid":1,"size":5}')
    svc = MatchService(broker, engine="oracle", compat="java")
    assert svc.run(max_messages=3) == 3
    got = list(consume_lines(broker, follow=False))
    want = _oracle_lines([
        __import__("kme_tpu.wire", fromlist=["parse_order"]).parse_order(
            '{"action":100,"aid":1}'),
        __import__("kme_tpu.wire", fromlist=["parse_order"]).parse_order(
            '{"action":101,"aid":1,"size":5}'),
    ], "java")
    assert got == want

    broker2 = InProcessBroker()
    provision(broker2)
    broker2.produce(TOPIC_IN, None, "not json")
    strict = MatchService(broker2, engine="oracle", compat="java",
                          strict=True)
    with pytest.raises(ValueError):
        strict.step(timeout=0.0)


def test_bridge_tcp_process_boundary(tmp_path):
    """The real four-process topology over TCP: kme-serve hosts the
    broker+engine; provision, loadgen and consume run as separate OS
    processes (the reference README run order). Consumer output is byte-
    identical to the oracle replica."""
    env = None
    serve = subprocess.Popen(
        [sys.executable, "-m", "kme_tpu.cli", "serve",
         "--listen", "127.0.0.1:0", "--engine", "oracle",
         "--compat", "java", "--auto-provision", "--idle-exit", "30"],
        stderr=subprocess.PIPE, text=True, env=env)
    try:
        line = serve.stderr.readline()
        assert "listening on" in line, line
        addr = line.rsplit(" ", 1)[-1].strip()

        prov = subprocess.run(
            [sys.executable, "-m", "kme_tpu.cli", "provision",
             "--broker", addr],
            capture_output=True, text=True, timeout=60)
        assert prov.returncode == 0, prov.stderr
        assert "MatchIn: exists" in prov.stdout  # auto-provisioned already

        load = subprocess.run(
            [sys.executable, "-m", "kme_tpu.cli", "loadgen",
             "--events", "120", "--seed", "3", "--broker", addr],
            capture_output=True, text=True, timeout=60)
        assert load.returncode == 0, load.stderr

        msgs = harness_stream(120, seed=3)
        want = _oracle_lines(msgs, "java")

        deadline = time.monotonic() + 60
        got = []
        while time.monotonic() < deadline and len(got) < len(want):
            cons = subprocess.run(
                [sys.executable, "-m", "kme_tpu.cli", "consume",
                 "--broker", addr, "--no-follow"],
                capture_output=True, text=True, timeout=60)
            assert cons.returncode == 0, cons.stderr
            got = cons.stdout.splitlines()
            if len(got) < len(want):
                time.sleep(0.3)
        assert got == want
    finally:
        serve.terminate()
        serve.wait(timeout=10)
