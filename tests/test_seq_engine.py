"""Sequential mega-kernel engine vs the oracle's fixed-mode semantics.

The seq engine claims bit-exact serial replay by construction
(kme_tpu/engine/seq.py): the kernel processes messages in arrival
order, so its wire stream and store state must equal the scalar
oracle's under the same capacity envelope. On CPU the kernel runs
under pallas interpret mode — the same kernel logic, not a shadow
implementation.
"""

import numpy as np
import pytest

import kme_tpu.opcodes as op
from kme_tpu.engine import seq as SQ
from kme_tpu.oracle import OracleEngine
from kme_tpu.runtime.seqsession import SeqSession
from kme_tpu.wire import OrderMsg
from kme_tpu.workload import (STORM_PROFILES, cancel_heavy_stream,
                              harness_stream, storm_stream,
                              zipf_symbol_stream)

CFG = SQ.SeqConfig(lanes=8, slots=128, accounts=128, max_fills=32,
                   batch=128, pos_cap=1 << 11, fill_cap=1 << 12,
                   probe_max=16)


def assert_seq_parity(msgs, cfg=CFG):
    ses = SeqSession(cfg)
    wire_ses = SeqSession(cfg)
    ora = OracleEngine("fixed", book_slots=cfg.slots,
                      max_fills=cfg.max_fills)
    got = ses.process(msgs)
    got_wire = wire_ses.process_wire([m.copy() for m in msgs])
    for i, m in enumerate(msgs):
        want = [r.wire() for r in ora.process(m.copy())]
        g = [r.wire() for r in got[i]]
        assert g == want, f"stream diverged at message {i}: {m}\n" \
            f"got  {g}\nwant {want}"
        assert got_wire[i] == want, \
            f"wire path diverged at message {i}: {m}"
    exp = ses.export_state()
    assert exp["balances"] == dict(ora.balances)
    assert exp["positions"] == dict(ora.positions)
    oorders = {oid: {"aid": r.aid, "sid": r.sid, "price": r.price,
                     "size": r.size, "is_buy": r.action == op.BUY}
               for oid, r in ora.orders.items()}
    assert exp["orders"] == oorders
    # fixed-mode oracle book keys are 2*sid (buy) / 2*sid+1 (sell)
    assert set(exp["books"]) == {k // 2 for k in ora.books}
    return ses, ora


def test_seq_scenario_end_to_end():
    """Every opcode incl. barriers, double cancel, unknown oid, payout
    YES/NO, remove + re-add."""
    msgs = []
    for a in range(4):
        msgs.append(OrderMsg(action=op.CREATE_BALANCE, aid=a))
        msgs.append(OrderMsg(action=op.TRANSFER, aid=a, size=100000))
    for s in (0, 1, 2):
        msgs.append(OrderMsg(action=op.ADD_SYMBOL, sid=s))
    msgs += [
        OrderMsg(action=op.BUY, oid=10, aid=0, sid=0, price=40, size=5),
        OrderMsg(action=op.BUY, oid=11, aid=1, sid=0, price=40, size=3),
        OrderMsg(action=op.SELL, oid=12, aid=2, sid=0, price=35, size=6),
        OrderMsg(action=op.SELL, oid=13, aid=3, sid=1, price=60, size=4),
        OrderMsg(action=op.BUY, oid=14, aid=0, sid=1, price=65, size=2),
        OrderMsg(action=op.CANCEL, oid=13, aid=3),
        OrderMsg(action=op.CANCEL, oid=13, aid=3),
        OrderMsg(action=op.CANCEL, oid=999, aid=0),
        OrderMsg(action=op.BUY, oid=15, aid=1, sid=2, price=50, size=4),
        OrderMsg(action=op.BUY, oid=16, aid=2, sid=2, price=50, size=2),
        OrderMsg(action=op.SELL, oid=17, aid=3, sid=2, price=45, size=9),
        OrderMsg(action=op.PAYOUT, sid=2, size=97),
        OrderMsg(action=op.PAYOUT, sid=-1, size=97),
        OrderMsg(action=op.REMOVE_SYMBOL, sid=0),
        OrderMsg(action=op.ADD_SYMBOL, sid=0),
        OrderMsg(action=op.BUY, oid=18, aid=0, sid=0, price=30, size=1),
        OrderMsg(action=op.ADD_SYMBOL, sid=-3),
        OrderMsg(action=op.TRANSFER, aid=9, size=5),
        OrderMsg(action=99, oid=0, aid=0),
    ]
    assert_seq_parity(msgs)


def test_seq_same_account_same_symbol_runs():
    """The workload shape a conflict-free scheduler serializes (H1):
    one account hammering one symbol back-to-back — the seq kernel has
    no scheduling constraints, but must still be byte-exact."""
    msgs = [OrderMsg(action=op.CREATE_BALANCE, aid=1),
            OrderMsg(action=op.TRANSFER, aid=1, size=10**6),
            OrderMsg(action=op.CREATE_BALANCE, aid=2),
            OrderMsg(action=op.TRANSFER, aid=2, size=10**6),
            OrderMsg(action=op.ADD_SYMBOL, sid=5)]
    oid = 100
    for k in range(40):
        msgs.append(OrderMsg(action=op.BUY, oid=oid, aid=1, sid=5,
                             price=40 + (k % 7), size=1 + (k % 5)))
        oid += 1
        msgs.append(OrderMsg(action=op.SELL, oid=oid, aid=2, sid=5,
                             price=38 + (k % 9), size=1 + (k % 4)))
        oid += 1
        if k % 3 == 0:
            msgs.append(OrderMsg(action=op.CANCEL, oid=oid - 2, aid=1))
    assert_seq_parity(msgs)


def test_seq_max_fills_envelope_reject():
    cfg = SQ.SeqConfig(lanes=8, slots=128, accounts=128, max_fills=2,
                       batch=128, pos_cap=1 << 11, fill_cap=1 << 12,
                       probe_max=16)
    msgs = [OrderMsg(action=op.CREATE_BALANCE, aid=1),
            OrderMsg(action=op.TRANSFER, aid=1, size=10**6),
            OrderMsg(action=op.CREATE_BALANCE, aid=2),
            OrderMsg(action=op.TRANSFER, aid=2, size=10**6),
            OrderMsg(action=op.ADD_SYMBOL, sid=1)]
    for k in range(3):
        msgs.append(OrderMsg(action=op.SELL, oid=10 + k, aid=1, sid=1,
                             price=50, size=2))
    # sweeps 3 makers -> capacity REJECT; then a 2-maker sweep passes
    msgs.append(OrderMsg(action=op.BUY, oid=20, aid=2, sid=1,
                         price=55, size=6))
    msgs.append(OrderMsg(action=op.BUY, oid=21, aid=2, sid=1,
                         price=55, size=4))
    ses, _ = assert_seq_parity(msgs, cfg)
    m = ses.metrics()
    assert m["rej_capacity"] == 1
    assert m["trades_ok"] == 4  # 3 resting sells + the 2-maker buy


@pytest.mark.parametrize("over", [1, 2])
def test_seq_book_slots_envelope_reject(over):
    """H2 envelope policy: a non-crossing buy into a full book side is
    rejected as a unit (OUT REJECT), that message only; the batch
    continues, no exception, no sticky poison — a crossing sell then
    fills the best buy. Byte-exact vs the enveloped oracle."""
    msgs = [OrderMsg(action=op.CREATE_BALANCE, aid=1),
            OrderMsg(action=op.TRANSFER, aid=1, size=10**8),
            OrderMsg(action=op.CREATE_BALANCE, aid=2),
            OrderMsg(action=op.TRANSFER, aid=2, size=10**8),
            OrderMsg(action=op.ADD_SYMBOL, sid=1)]
    for k in range(CFG.slots + over):   # the last `over` overflow the side
        msgs.append(OrderMsg(action=op.BUY, oid=100 + k, aid=1, sid=1,
                             price=1 + (k % 30), size=1))
    msgs.append(OrderMsg(action=op.SELL, oid=9000, aid=2, sid=1, price=30,
                         size=1))
    ses, _ = assert_seq_parity(msgs)
    assert ses.metrics()["rej_capacity"] == over
    # the stream both engines gave: the overflowing buys were rejected,
    # and only those; the final sell produced fills
    ora = OracleEngine("fixed", book_slots=CFG.slots,
                       max_fills=CFG.max_fills)
    flat = [r.wire() for m in msgs for r in ora.process(m.copy())]
    assert sum(ln.startswith('OUT {"action":7') for ln in flat) == over
    assert any(ln.startswith('OUT {"action":5') for ln in flat)


def test_seq_self_cross_and_zero_residual():
    """An account trading against itself, exact-fill takers, and a taker
    sweeping an entire side."""
    msgs = [OrderMsg(action=op.CREATE_BALANCE, aid=1),
            OrderMsg(action=op.TRANSFER, aid=1, size=100000),
            OrderMsg(action=op.ADD_SYMBOL, sid=0),
            OrderMsg(action=op.BUY, oid=1, aid=1, sid=0, price=50, size=3),
            OrderMsg(action=op.SELL, oid=2, aid=1, sid=0, price=50, size=3),
            OrderMsg(action=op.BUY, oid=3, aid=1, sid=0, price=55, size=4),
            OrderMsg(action=op.BUY, oid=4, aid=1, sid=0, price=54, size=4),
            OrderMsg(action=op.SELL, oid=5, aid=1, sid=0, price=1, size=20)]
    assert_seq_parity(msgs)


def test_seq_fill_credit_wraps_at_int32():
    """Per-fill taker credit is Java int*int — wraps at int32 before the
    long balance add (oracle._fill_order after the round-2 fix); the
    kernel's planar lo/hi arithmetic must wrap identically."""
    msgs = []
    for a in (0, 1):
        msgs.append(OrderMsg(action=op.CREATE_BALANCE, aid=a))
        for _ in range(3):
            msgs.append(OrderMsg(action=op.TRANSFER, aid=a, size=2**31 - 1))
    msgs.append(OrderMsg(action=op.ADD_SYMBOL, sid=0))
    msgs.append(OrderMsg(action=op.SELL, oid=1, aid=0, sid=0, price=0,
                         size=2**25))
    msgs.append(OrderMsg(action=op.BUY, oid=2, aid=1, sid=0, price=125,
                         size=2**25))
    assert_seq_parity(msgs)


def test_seq_transfer_int_min_negation_wraps():
    """`-order.size` negates in int32 (INT_MIN stays INT_MIN): the
    size=INT_MIN withdrawal is ACCEPTED — the kernel must mirror the
    oracle."""
    msgs = [
        OrderMsg(action=op.CREATE_BALANCE, aid=1),
        OrderMsg(action=op.TRANSFER, aid=1, size=-(2**31)),
    ]
    ses, ora = assert_seq_parity(msgs)
    assert ora.balances[1] == -(2**31)


def test_seq_capacity_envelope_zipf_stream_parity():
    """A skewed stream that actually overflows its books stays
    byte-exact vs the enveloped oracle (the BENCH_r02 failure class):
    passive quotes pile onto two symbols' 128-slot sides."""
    msgs = zipf_symbol_stream(1600, num_symbols=2, num_accounts=16, seed=7,
                              zipf_a=1.5)
    ses, _ = assert_seq_parity(msgs, SQ.SeqConfig(
        lanes=8, slots=128, accounts=128, max_fills=16, batch=256,
        pos_cap=1 << 11, fill_cap=1 << 13, probe_max=16))
    # the point of the scenario: overflow actually happened
    assert ses.metrics()["rej_capacity"] > 0


def test_seq_harness_stream_parity():
    """Stock harness distribution (10 accounts, 3 symbols) — the exact
    shape H1 penalizes under a conflict-free scheduler."""
    msgs = harness_stream(600, seed=7)
    assert_seq_parity(msgs, SQ.SeqConfig(
        lanes=8, slots=128, accounts=128, max_fills=64, batch=256,
        pos_cap=1 << 11, fill_cap=1 << 13, probe_max=16))


def test_seq_zipf_stream_parity():
    msgs = zipf_symbol_stream(500, num_symbols=6, num_accounts=24, seed=3)
    assert_seq_parity(msgs, SQ.SeqConfig(
        lanes=8, slots=128, accounts=128, max_fills=64, batch=256,
        pos_cap=1 << 11, fill_cap=1 << 13, probe_max=16))


@pytest.mark.parametrize("stream",
                         ["cancel-heavy"] + sorted(STORM_PROFILES))
def test_seq_workload_parity(stream):
    """The served engine on the streams its steady-state tests never
    send: the cancel/replace mix (about half the events are cancels of
    resting orders) and the five adversarial storms — PAYOUT+re-ADD
    barrier bursts, a flooder clique on one symbol, mass cancels, one
    deep hot book, forced liquidations."""
    if stream == "cancel-heavy":
        msgs = cancel_heavy_stream(500, 6, 24, seed=5)
    else:
        msgs = storm_stream(stream, 500, num_symbols=8,
                            num_accounts=24, seed=5)
    assert_seq_parity(msgs, SQ.SeqConfig(
        lanes=8, slots=128, accounts=128, max_fills=64, batch=256,
        pos_cap=1 << 11, fill_cap=1 << 13, probe_max=16))


def test_seq_canonical_roundtrip_and_resume():
    """Export -> import mid-stream must continue byte-exact (the
    cross-engine snapshot contract)."""
    msgs = zipf_symbol_stream(400, num_symbols=5, num_accounts=16, seed=11)
    cut = 250
    cfg = SQ.SeqConfig(lanes=8, slots=128, accounts=128, max_fills=64,
                       batch=128, pos_cap=1 << 11, fill_cap=1 << 13,
                       probe_max=16)
    full = SeqSession(cfg)
    want = full.process_wire([m.copy() for m in msgs])

    a = SeqSession(cfg)
    got_head = a.process_wire([m.copy() for m in msgs[:cut]])
    canon = SQ.export_canonical(cfg, a.state)
    b = SeqSession(cfg)
    b.state = SQ.import_canonical(cfg, canon)
    b.router = a.router
    got_tail = b.process_wire([m.copy() for m in msgs[cut:]])
    assert got_head + got_tail == want


def _positions_stream():
    """A preamble and eight batches that open >128 distinct (lane,
    account) positions: one batch a symbol, 32 crossing pairs each."""
    pre = [OrderMsg(action=op.CREATE_BALANCE, aid=0),
           OrderMsg(action=op.TRANSFER, aid=0, size=10**9)]
    for a in range(1, 100):
        pre.append(OrderMsg(action=op.CREATE_BALANCE, aid=a))
        pre.append(OrderMsg(action=op.TRANSFER, aid=a, size=10**9))
    for s in range(8):
        pre.append(OrderMsg(action=op.ADD_SYMBOL, sid=s))
    oid = 1000
    batches = []
    for s in range(8):
        batch = []
        for a in range(32):
            batch.append(OrderMsg(action=op.SELL, oid=oid, aid=a % 99,
                                  sid=s, price=50, size=1))
            oid += 1
            batch.append(OrderMsg(action=op.BUY, oid=oid,
                                  aid=(a + 1) % 99, sid=s, price=55,
                                  size=1))
            oid += 1
        batches.append(pre + batch if s == 0 else batch)
    return batches


def test_seq_hash_full_error():
    """LERR_HASH_FULL is java mode's guard (its keys are values, so no
    configuration bounds them): >128 positions at pos_cap=128,
    probe_max=1 trip the sticky error. The fixed store is sized by
    lanes x accounts and takes the same stream whole, whatever
    pos_cap says."""
    from kme_tpu.runtime.seqsession import LaneEngineError
    kw = dict(lanes=8, slots=128, accounts=128, max_fills=8, batch=128,
              pos_cap=128, fill_cap=1 << 12, probe_max=1)
    ses = SeqSession(SQ.SeqConfig(compat="java", **kw))
    with pytest.raises(LaneEngineError) as e:
        for batch in _positions_stream():
            ses.process_wire(batch)
    assert e.value.code == SQ.LERR_HASH_FULL
    ses = SeqSession(SQ.SeqConfig(**kw))
    orc = OracleEngine("fixed", book_slots=128, max_fills=8)
    for batch in _positions_stream():
        got = ses.process_wire(batch)
        assert got == [[r.wire() for r in orc.process(m.copy())]
                       for m in batch]
    touched = {(m.sid, m.aid) for b in _positions_stream() for m in b
               if m.action in (op.BUY, op.SELL)}
    assert len(touched) > 128 > ses.metrics()["positions"] > 0


def test_seq_native_wire_equivalence():
    """The C++ reconstructor (native/kme_wire.cpp) and the pure-Python
    path must produce identical line streams; process_wire_buffer's
    offsets must re-slice to the same lines."""
    cfg = SQ.SeqConfig(lanes=8, slots=128, accounts=128, max_fills=64,
                       batch=256, pos_cap=1 << 11, fill_cap=1 << 13,
                       probe_max=16)
    msgs = harness_stream(700, seed=5)
    a = SeqSession(cfg)
    r = a.process_wire_buffer([m.copy() for m in msgs])
    if r is None:
        pytest.skip("native library unavailable")
    buf, line_off, msg_lines = r
    text = buf.decode("ascii")
    flat = [text[line_off[k]:line_off[k + 1]]
            for k in range(len(line_off) - 1)]
    b = SeqSession(cfg)
    b._use_native_wire = False
    py = b.process_wire([m.copy() for m in msgs])
    pyflat = [l for ls in py for l in ls]
    assert flat == pyflat
    assert int(msg_lines.sum()) == len(pyflat)


def test_seq_hbm_books_parity():
    """hbm_books: book planes in HBM behind the kernel's per-lane VMEM
    scratch cache — same byte parity, exercised at slots=256 (NR=2) so
    multi-row blocks and lane switches are both covered."""
    msgs = zipf_symbol_stream(500, num_symbols=6, num_accounts=24, seed=3)
    assert_seq_parity(msgs, SQ.SeqConfig(
        lanes=8, slots=256, accounts=128, max_fills=64, batch=256,
        pos_cap=1 << 11, fill_cap=1 << 13, probe_max=16, hbm_books=True))


def test_seq_service_and_cross_engine_restore(tmp_path):
    """MatchService with engine='seq': serve a stream byte-exact, crash
    after a checkpoint, resume — and the newest snapshot, restored
    outside the service, holds the oracle's stores exactly."""
    from kme_tpu.bridge.broker import InProcessBroker
    from kme_tpu.bridge.provision import provision
    from kme_tpu.bridge.service import MatchService
    from kme_tpu.runtime import checkpoint as ck
    from kme_tpu.wire import dumps_order

    msgs = harness_stream(300, seed=13, num_symbols=4, num_accounts=8,
                          payout_opcode_bug=False, validate=True)
    ora = OracleEngine("fixed", book_slots=128, max_fills=32)
    per_msg = [[r.wire() for r in ora.process(m.copy())] for m in msgs]

    ck_dir = str(tmp_path / "ck")
    kw = dict(engine="seq", compat="fixed", batch=50, symbols=8,
              accounts=128, slots=128, max_fills=32,
              checkpoint_dir=ck_dir, checkpoint_every=100)
    b = InProcessBroker(persist_dir=str(tmp_path / "log"))
    provision(b)
    for m in msgs:
        b.produce("MatchIn", None, dumps_order(m))
    svc1 = MatchService(b, **kw)
    assert svc1.run(max_messages=150) == 150   # snapshot at >=100
    snap_off = svc1._last_ckpt_offset
    assert snap_off >= 100
    del svc1  # crash

    svc2 = MatchService(b, **kw)               # resume (seq -> seq)
    assert svc2.offset == snap_off
    assert svc2.run(max_messages=len(msgs) - snap_off) \
        == len(msgs) - snap_off
    from kme_tpu.bridge.consume import consume_lines
    got = list(consume_lines(b, follow=False))
    want = [ln for lines in per_msg[:150] for ln in lines]
    want += [ln for lines in per_msg[snap_off:] for ln in lines]
    assert got == want

    # the newest snapshot restores under its own configuration; the
    # restored canonical STATE must equal the oracle's stores exactly,
    # and any remaining stream tail must replay byte-exact
    ses, off = ck.load_seq_session(ck_dir)
    assert ses is not None and off >= snap_off
    if off < len(msgs):
        tail = ses.process_wire([m.copy() for m in msgs[off:]])
        assert [ln for lines in tail for ln in lines] \
            == [ln for lines in per_msg[off:] for ln in lines]
    exp = ses.export_state()
    assert exp["balances"] == dict(ora.balances)
    assert exp["positions"] == dict(ora.positions)


def test_native_router_matches_python():
    """The C++ router must produce identical plans and id maps to the
    Python SeqRouter on a stream exercising every edge (unknown-oid
    cancels, negative-sid addsym, payout route cleanup, re-used oids)."""
    from kme_tpu.runtime.seqsession import (NativeSeqRouter, SeqRouter,
                                            make_seq_router)

    nat = make_seq_router(16, 256)
    if not isinstance(nat, NativeSeqRouter):
        pytest.skip("native library unavailable")
    py = SeqRouter(16, 256)
    msgs = harness_stream(1200, seed=21, num_symbols=6, num_accounts=12,
                          payout_opcode_bug=False, validate=False)
    INT64_MIN = -(1 << 63)
    msgs += [
        # negative-sid trade (allocates a negative map key), then the
        # INT64_MIN payout/remove edge (abs wraps; must host-reject)
        OrderMsg(action=op.BUY, oid=999001, aid=1, sid=-7, price=50,
                 size=1),
        OrderMsg(action=op.PAYOUT, sid=INT64_MIN, size=97),
        OrderMsg(action=op.REMOVE_SYMBOL, sid=INT64_MIN),
        OrderMsg(action=op.PAYOUT, sid=-7, size=97),
    ]
    for chunk in (msgs[:500], msgs[500:]):   # maps persist across calls
        cn, rn = nat.route(chunk)
        cp, rp = py.route(chunk)
        assert rn == rp
        for k in cp:
            assert cn[k].tolist() == cp[k].tolist(), k
    assert nat.aid_idx == py.aid_idx
    assert nat.sid_lane == py.sid_lane
    assert nat.oid_sid == py.oid_sid


def test_submit_collect_pipelined_byte_exact(cpu_devices):
    """The double-buffered serving API (SURVEY.md §7 H5): submit batch
    N+1 before collecting batch N; the concatenated byte stream equals
    the one-shot process_wire_buffer output exactly (incl. barriers)."""
    from kme_tpu.wire import WireBatch
    from kme_tpu.workload import zipf_symbol_stream

    msgs = zipf_symbol_stream(1500, num_symbols=8, num_accounts=32,
                              seed=8, zipf_a=1.1, payout_per_mille=4)
    cfg = SQ.SeqConfig(lanes=8, slots=128, accounts=128, max_fills=16,
                       batch=256, pos_cap=1 << 12, probe_max=8)
    a, b = SeqSession(cfg), SeqSession(cfg)
    ra = a.process_wire_buffer(msgs)
    if ra is None:
        import pytest

        pytest.skip("native toolchain unavailable")
    parts, pend = [], []
    for lo in range(0, len(msgs), 256):
        pend.append(b.submit(WireBatch.from_msgs(msgs[lo:lo + 256])))
        if len(pend) > 1:
            parts.append(b.collect(pend.pop(0)))
    while pend:
        parts.append(b.collect(pend.pop(0)))
    assert b"".join(p[0] for p in parts) == ra[0]
