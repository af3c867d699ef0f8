"""Throughput (lanes) engine vs the oracle's fixed-mode semantics.

The lanes engine + conflict-free scheduler claim bit-exact serial
equivalence (kme_tpu/engine/lanes.py docstring); these tests replay
workloads through LaneSession and the scalar oracle and require
identical wire streams and store state.
"""

import pytest

import kme_tpu.opcodes as op
from kme_tpu.engine.lanes import LaneConfig
from kme_tpu.oracle import OracleEngine
from kme_tpu.runtime.sequencer import CapacityError, EnvelopeError, Scheduler
from kme_tpu.runtime.session import LaneSession
from kme_tpu.wire import OrderMsg
from kme_tpu.workload import cancel_heavy_stream, harness_stream, zipf_symbol_stream

CFG = LaneConfig(lanes=8, slots=128, accounts=64, max_fills=32, steps=32)


def assert_lane_parity(msgs, cfg=CFG, width=16):
    ses = LaneSession(cfg, width=width)
    wire_ses = LaneSession(cfg, width=width)  # fast wire-line path
    ora = OracleEngine("fixed")
    got = ses.process(msgs)
    got_wire = wire_ses.process_wire([m.copy() for m in msgs])
    for i, m in enumerate(msgs):
        want = [r.wire() for r in ora.process(m.copy())]
        g = [r.wire() for r in got[i]]
        assert g == want, f"stream diverged at message {i}: {m}"
        assert got_wire[i] == want, f"wire path diverged at message {i}: {m}"
    exp = ses.export_state()
    assert exp["balances"] == dict(ora.balances)
    assert exp["positions"] == dict(ora.positions)
    oorders = {oid: {"aid": r.aid, "sid": r.sid, "price": r.price,
                     "size": r.size, "is_buy": r.action == op.BUY}
               for oid, r in ora.orders.items()}
    assert exp["orders"] == oorders
    return ses, ora


@pytest.mark.parametrize("width", [0, 1, 16])
def test_lane_scenario_end_to_end(width):
    """width=0 keeps the single-device full-width path covered; width=1
    forces maximal step-bumping through the compaction scheduler."""
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
    assert_lane_parity(msgs, width=width)


def test_lane_self_cross_and_zero_residual():
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
    assert_lane_parity(msgs)


@pytest.mark.slow
def test_lane_parity_harness_workload():
    assert_lane_parity(
        harness_stream(3000, seed=7, payout_opcode_bug=False, validate=True),
        LaneConfig(lanes=4, slots=128, accounts=16, max_fills=32, steps=32))


@pytest.mark.slow
def test_lane_parity_zipf_many_symbols():
    msgs = zipf_symbol_stream(3000, num_symbols=32, num_accounts=48, seed=5)
    assert_lane_parity(
        msgs, LaneConfig(lanes=32, slots=128, accounts=64, max_fills=32,
                         steps=32))


@pytest.mark.slow
def test_lane_parity_cancel_heavy():
    msgs = cancel_heavy_stream(3000, num_symbols=8, num_accounts=24, seed=9)
    assert_lane_parity(
        msgs, LaneConfig(lanes=8, slots=256, accounts=32, max_fills=32,
                         steps=32))


def test_scheduler_invariants():
    """Actor uniqueness per step, per-symbol FIFO, barrier exclusivity."""
    msgs = harness_stream(800, seed=3, payout_opcode_bug=False, validate=True)
    sch = Scheduler(num_lanes=4, num_accounts=16)
    plan = sch.plan(msgs)
    # (segment, step) -> actors and lanes must be unique
    seen = {}
    for p in plan.placements:
        key = (p.segment, p.step)
        actors, lanes = seen.setdefault(key, (set(), set()))
        assert p.lane not in lanes, "two messages on one lane in a step"
        lanes.add(p.lane)
        if p.lane_act != 6:  # ADD_SYMBOL has no actor
            assert p.aid_idx not in actors, "actor collision in a step"
            actors.add(p.aid_idx)
    # per-lane step order must follow arrival order within each segment
    by_lane = {}
    for p in plan.placements:
        by_lane.setdefault((p.segment, p.lane), []).append((p.msg_index, p.step))
    for lst in by_lane.values():
        idx_sorted = sorted(lst)
        steps = [s for _, s in idx_sorted]
        assert steps == sorted(steps), "lane FIFO violated"


def test_capacity_and_envelope_errors():
    sch = Scheduler(num_lanes=2, num_accounts=2)
    msgs = [OrderMsg(action=op.ADD_SYMBOL, sid=s) for s in range(3)]
    with pytest.raises(CapacityError):
        sch.plan(msgs)
    sch2 = Scheduler(num_lanes=8, num_accounts=8)
    with pytest.raises(EnvelopeError):
        sch2.plan([OrderMsg(action=op.BUY, oid=1, aid=1, sid=0, price=2**31,
                            size=1)])


def test_lane_slot_overflow_rejects_per_message():
    """H2 envelope policy: the 5th non-crossing buy into a 4-slot book is
    rejected as a unit (OUT REJECT); the batch continues, no exception.
    Byte-exact vs the enveloped oracle."""
    cfg = LaneConfig(lanes=2, slots=4, accounts=8, max_fills=4, steps=8)
    ses = LaneSession(cfg)
    msgs = [OrderMsg(action=op.CREATE_BALANCE, aid=1),
            OrderMsg(action=op.TRANSFER, aid=1, size=10**6),
            OrderMsg(action=op.ADD_SYMBOL, sid=0)]
    msgs += [OrderMsg(action=op.BUY, oid=10 + i, aid=1, sid=0, price=10 + i,
                      size=1) for i in range(5)]
    ora = OracleEngine("fixed", book_slots=4, max_fills=4)
    want = [[r.wire() for r in ora.process(m.copy())] for m in msgs]
    got = [[r.wire() for r in recs] for recs in ses.process(msgs)]
    assert got == want
    assert got[-1][-1].startswith('OUT {"action":7')  # the overflow reject
    assert sum(1 for recs in got for ln in recs
               if ln.startswith('OUT {"action":7')) == 1


def test_lane_fill_credit_wraps_at_int32():
    """Per-fill taker credit is Java int*int — wraps at int32 before the
    long balance add (oracle._fill_order after the round-2 fix); the
    lanes engine must wrap identically."""
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
    assert_lane_parity(msgs)


def test_lane_transfer_int_min_negation_wraps():
    """`-order.size` negates in int32 (INT_MIN stays INT_MIN): the
    size=INT_MIN withdrawal is ACCEPTED — lanes must mirror the oracle."""
    msgs = [
        OrderMsg(action=op.CREATE_BALANCE, aid=1),
        OrderMsg(action=op.TRANSFER, aid=1, size=-(2**31)),
    ]
    ses, ora = assert_lane_parity(msgs)
    assert ora.balances[1] == -(2**31)


def test_capacity_envelope_book_full_rejects_per_message(cpu_devices):
    """H2 policy: overflowing a book side rejects THAT message only —
    the batch continues and stays oracle-exact (no sticky poison)."""
    slots = 4
    cfg = LaneConfig(lanes=2, slots=slots, accounts=8, max_fills=8, steps=8)
    msgs = [OrderMsg(action=op.CREATE_BALANCE, aid=1),
            OrderMsg(action=op.TRANSFER, aid=1, size=10_000_000),
            OrderMsg(action=op.CREATE_BALANCE, aid=2),
            OrderMsg(action=op.TRANSFER, aid=2, size=10_000_000),
            OrderMsg(action=op.ADD_SYMBOL, sid=0)]
    # 6 non-crossing buys on one side: slots 5 and 6 must reject
    for i in range(slots + 2):
        msgs.append(OrderMsg(action=op.BUY, oid=100 + i, aid=1, sid=0,
                             price=10 + i, size=5))
    # the book still works afterwards: a crossing sell fills the best buy
    msgs.append(OrderMsg(action=op.SELL, oid=200, aid=2, sid=0,
                         price=10, size=5))

    ora = OracleEngine("fixed", book_slots=slots, max_fills=8)
    want = [[r.wire() for r in ora.process(m.copy())] for m in msgs]
    ses = LaneSession(cfg)
    got = [[r.wire() for r in recs] for recs in ses.process(msgs)]
    assert got == want
    # the overflowing buys were rejected, and only those
    flat = [ln for recs in got for ln in recs]
    rejects = [ln for ln in flat if ln.startswith('OUT {"action":7')]
    assert len(rejects) == 2
    # the final sell produced fills (stream survived the overflow)
    assert any(ln.startswith('OUT {"action":5') for ln in flat)


def test_capacity_envelope_max_fills_rejects_per_message(cpu_devices):
    """H3 policy: a taker that would sweep more than max_fills makers is
    rejected as a unit; makers stay untouched."""
    E = 2
    cfg = LaneConfig(lanes=2, slots=16, accounts=8, max_fills=E, steps=8)
    msgs = [OrderMsg(action=op.CREATE_BALANCE, aid=1),
            OrderMsg(action=op.TRANSFER, aid=1, size=10_000_000),
            OrderMsg(action=op.CREATE_BALANCE, aid=2),
            OrderMsg(action=op.TRANSFER, aid=2, size=10_000_000),
            OrderMsg(action=op.ADD_SYMBOL, sid=0)]
    for i in range(E + 1):  # 3 resting sells at one level
        msgs.append(OrderMsg(action=op.SELL, oid=100 + i, aid=1, sid=0,
                             price=50, size=1))
    # sweeping all 3 exceeds max_fills=2 -> reject
    msgs.append(OrderMsg(action=op.BUY, oid=200, aid=2, sid=0,
                         price=50, size=3))
    # sweeping 2 is inside the envelope -> fills
    msgs.append(OrderMsg(action=op.BUY, oid=201, aid=2, sid=0,
                         price=50, size=2))

    ora = OracleEngine("fixed", book_slots=16, max_fills=E)
    want = [[r.wire() for r in ora.process(m.copy())] for m in msgs]
    ses = LaneSession(cfg)
    got = [[r.wire() for r in recs] for recs in ses.process(msgs)]
    assert got == want
    flat = [ln for recs in got for ln in recs]
    assert sum(1 for ln in flat if ln.startswith('OUT {"action":7')) == 1
    assert sum(1 for ln in flat if ln.startswith('OUT {"action":6')) == 2


def test_capacity_envelope_zipf_stream_parity(cpu_devices):
    """A skewed stream that actually overflows small books stays
    byte-exact vs the enveloped oracle (the BENCH_r02 failure class)."""
    slots = 8
    msgs = zipf_symbol_stream(800, num_symbols=4, num_accounts=16, seed=7,
                              zipf_a=1.5)
    cfg = LaneConfig(lanes=4, slots=slots, accounts=32, max_fills=16,
                     steps=16)
    ora = OracleEngine("fixed", book_slots=slots, max_fills=16)
    want = [[r.wire() for r in ora.process(m.copy())] for m in msgs]
    ses = LaneSession(cfg)
    got = [[r.wire() for r in recs] for recs in ses.process(msgs)]
    assert got == want
    flat = [ln for recs in got for ln in recs]
    # the point of the scenario: overflow actually happened
    assert any(ln.startswith('OUT {"action":7') for ln in flat)
