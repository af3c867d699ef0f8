"""kme-front (bridge/front.py): the multi-leader front door.

Pins the three contracts the symbol-sharded scale-out stands on:
- assignment parity: the C++ columnar pass (kme_group_assign), the
  numpy fallback and the scalar reference produce bit-identical
  group ids (the split is part of the durable stream — drift between
  the twins would silently re-partition every topic);
- deterministic merge: the global feed is a pure function of the
  per-group streams, whatever interleaving the racing consumers saw;
- transfer dedup: injected reserve→settle legs are replay-regenerated
  with identical (epoch, out_seq) stamps, and the broker/consumer
  dedup layers suppress duplicate delivery — zero double-settles.
"""

import random

import pytest

import kme_tpu.opcodes as op
from kme_tpu.bridge import front
from kme_tpu.oracle import OracleEngine
from kme_tpu.wire import dumps_order, order_json, parse_order
from kme_tpu.workload import cross_account_stream, zipf_symbol_stream


def _lines(events=600, symbols=24, accounts=12, seed=3):
    msgs = zipf_symbol_stream(events, num_symbols=symbols,
                              num_accounts=accounts, seed=seed)
    return [dumps_order(m) for m in msgs]


# -- assignment parity -------------------------------------------------


def test_scalar_vs_numpy_assignment(monkeypatch):
    import kme_tpu.native

    monkeypatch.setattr(kme_tpu.native, "load_library", lambda: None)
    keys = [0, 1, 2, -1, -7, 12345, 2 ** 53, -(2 ** 62), (1 << 63) - 1]
    for n in (1, 2, 3, 4, 7):
        for salt in (front.SALT_SYMBOL, front.SALT_ACCOUNT):
            got = front.assign_groups(keys, n, salt).tolist()
            want = [front.group_of(k, n, salt) for k in keys]
            assert got == want, (n, salt)


def test_native_vs_python_assignment():
    from kme_tpu.native import load_library

    if load_library() is None:
        pytest.skip("native library unavailable")
    rng = random.Random(11)
    keys = [rng.randrange(-(2 ** 63), 2 ** 63) for _ in range(4096)]
    keys += [0, -1, (1 << 63) - 1, -(1 << 63)]
    for n in (2, 3, 4, 8):
        got = front.assign_groups(keys, n, front.SALT_SYMBOL).tolist()
        want = [front.group_of(k, n, front.SALT_SYMBOL) for k in keys]
        assert got == want, f"native/python drift at ngroups={n}"


def test_symbol_group_ignores_payout_sign():
    # a payout (negative sid) must land on its book's group
    for sid in (1, 7, 123456789, 2 ** 40):
        for n in (2, 3, 4):
            assert (front.symbol_group(sid, n)
                    == front.symbol_group(-sid, n))


def test_assignment_balances():
    # rendezvous over a wide universe: no group starves (the bound is
    # loose on purpose — placement quality, not an exact split)
    n = 4
    counts = [0] * n
    for sid in range(1, 2049):
        counts[front.symbol_group(sid, n)] += 1
    assert min(counts) > 2048 / n / 2, counts


# -- deterministic merge -----------------------------------------------


def test_merge_records_interleaving_invariant():
    per, _ = front.split_lines(_lines(), 3)
    engines = [OracleEngine("fixed") for _ in range(3)]
    records = []
    for g in range(3):
        seq = 0
        for ln in per[g]:
            for rec in engines[g].process(parse_order(ln)):
                records.append((g, seq, rec.wire()))
                seq += 1
    want = front.merge_records(records)
    rng = random.Random(5)
    for _ in range(5):
        shuffled = records[:]
        rng.shuffle(shuffled)
        assert front.merge_records(shuffled) == want
    # merge_streams over the in-order per-group streams is the same
    # convention
    per_out = [[], [], []]
    for g, _seq, ln in sorted(records, key=lambda r: (r[0], r[1])):
        per_out[g].append(ln)
    assert front.merge_streams(per_out) == want


def test_merge_filters_internal_echoes():
    internal = front.make_internal_transfer(7, -100, 0)
    assert front.is_internal_line(internal)
    assert front.is_internal_line(f"OUT {internal}")  # engine echo too
    out = front.merge_streams([[internal, 'OUT {"action":2,"oid":1}'],
                               [front.make_internal_create(7, 1)]])
    assert out == ['OUT {"action":2,"oid":1}']


def test_organic_stream_never_carries_the_marker():
    assert not any(front.is_internal_line(ln) for ln in _lines())


# -- split semantics ---------------------------------------------------


def test_split_is_replay_deterministic():
    lines = _lines()
    a, ra = front.split_lines(lines, 4)
    b, rb = front.split_lines(lines, 4)
    assert a == b
    assert ra.counters == rb.counters


def test_original_line_lands_on_exactly_one_group():
    lines = _lines()
    router = front.GroupRouter(4)
    for ln in lines:
        routed = router.route_line(ln)
        organic = [g for g, out in routed
                   if not front.is_internal_line(out)]
        assert len(organic) == 1
        assert any(out == ln for _g, out in routed)


def test_create_balance_broadcasts_to_every_group():
    router = front.GroupRouter(3)
    routed = router.route_line(order_json(op.CREATE_BALANCE, 0, 42,
                                          0, 0, 0))
    assert sorted(g for g, _ in routed) == [0, 1, 2]
    internal = [ln for _g, ln in routed if front.is_internal_line(ln)]
    assert len(internal) == 2
    assert router.counters["balance_broadcasts_total"] == 2


def _cross_pair(n=2):
    """(aid, sid) such that the account's home differs from the
    symbol's group under n groups."""
    for aid in range(1, 200):
        for sid in range(1, 200):
            if (front.account_group(aid, n)
                    != front.symbol_group(sid, n)):
                return aid, sid
    raise AssertionError("no cross pair found")


def test_prefund_chunks_transfer_legs():
    aid, sid = _cross_pair()
    deposit = order_json(op.TRANSFER, 0, aid, 0, 0, 10 ** 9)
    create = order_json(op.CREATE_BALANCE, 0, aid, 0, 0, 0)
    adds = [order_json(op.ADD_SYMBOL, 0, 0, sid, 0, 0)]
    orders = [order_json(op.BUY, 100 + i, aid, sid, 10, 5)
              for i in range(16)]
    lines = [create, deposit] + adds + orders

    per1, r1 = front.split_lines(lines, 2, prefund=1)
    assert r1.counters["cross_shard_transfers_total"] == 16
    per8, r8 = front.split_lines(lines, 2, prefund=8)
    # 16 identical orders at prefund=8 need exactly two grants
    assert r8.counters["cross_shard_transfers_total"] == 2
    assert r8.counters["transfer_shortfall_total"] == 0
    # the chunking changes WHICH legs ride the stream, never the
    # oracle-visible outcome
    for prefund, per in ((1, per1), (8, per8)):
        engines = [OracleEngine("fixed") for _ in range(2)]
        outs = [[rec.wire() for ln in per[g]
                 for rec in engines[g].process(parse_order(ln))]
                for g in range(2)]
        rep = front.verify_groups(lines, outs, prefund=prefund)
        assert rep["ok"], rep["mismatches"]


def test_underfunded_cross_order_counts_a_shortfall():
    aid, sid = _cross_pair()
    lines = [order_json(op.CREATE_BALANCE, 0, aid, 0, 0, 0),
             order_json(op.ADD_SYMBOL, 0, 0, sid, 0, 0),
             order_json(op.BUY, 100, aid, sid, 10, 5)]  # no deposit
    _per, router = front.split_lines(lines, 2)
    assert router.counters["transfer_shortfall_total"] == 1
    assert router.counters["cross_shard_transfers_total"] == 0


# -- end-to-end parity -------------------------------------------------


@pytest.mark.parametrize("ngroups", [1, 2, 4])
def test_front_to_engines_to_merge_parity(ngroups):
    lines = _lines(events=500, symbols=16, accounts=10, seed=9)
    per, _router = front.split_lines(lines, ngroups)
    engines = [OracleEngine("fixed") for _ in range(ngroups)]
    outs = [[rec.wire() for ln in per[g]
             for rec in engines[g].process(parse_order(ln))]
            for g in range(ngroups)]
    rep = front.verify_groups(lines, outs)
    assert rep["ok"], rep["mismatches"][:1]


def test_cross_account_workload_parity():
    msgs = cross_account_stream(400, 32, 16, 2, seed=4, cross_frac=1.0)
    lines = [dumps_order(m) for m in msgs]
    per, router = front.split_lines(lines, 2)
    assert router.counters["cross_shard_transfers_total"] > 0
    engines = [OracleEngine("fixed") for _ in range(2)]
    outs = [[rec.wire() for ln in per[g]
             for rec in engines[g].process(parse_order(ln))]
            for g in range(2)]
    rep = front.verify_groups(lines, outs)
    assert rep["ok"], rep["mismatches"][:1]


# -- what cross-group routing costs ------------------------------------


@pytest.mark.parametrize("ngroups,transfers", [(2, 1250), (4, 2087)])
def test_cross_shard_transfer_frac(ngroups, transfers):
    """Transfers the front door injects into the zipf stream (20,000
    events, 1,024 symbols x 256 accounts, seed 0, prefund 8), from the
    split alone: router arithmetic with no clock and no RNG, so the
    counts are exact. 2,087 over 17,999 orders is the 0.116
    transfers/order a four-group deployment pays; a change to
    the rendezvous hash, the home-account rule or the chunked
    reserve->settle policy moves it."""
    msgs = zipf_symbol_stream(20_000, num_symbols=1024,
                              num_accounts=256, seed=0)
    orders = sum(1 for m in msgs if m.action in (op.BUY, op.SELL))
    assert orders == 17_999
    per, router = front.split_lines([dumps_order(m) for m in msgs],
                                    ngroups, prefund=8)
    assert router.counters["cross_shard_transfers_total"] == transfers
    assert router.counters["transfer_shortfall_total"] == 0
    # every counted transfer is a debit leg and a credit leg in the
    # substreams
    legs = sum(1 for sub in per for ln in sub
               if front.is_internal_line(ln)
               and parse_order(ln).action == op.TRANSFER)
    assert legs == 2 * transfers


# -- transfer dedup under duplicate delivery ---------------------------


def test_duplicate_transfer_stamps_are_suppressed_by_the_broker():
    from kme_tpu.bridge.broker import InProcessBroker

    b = InProcessBroker()
    topic = "Xfer.g0"
    b.create_topic(topic)
    leg = front.make_internal_transfer(7, -500, 0)
    assert b.produce(topic, "OUT", leg, epoch=2, out_seq=10) == 0
    # the crash-replay regenerates the identical leg with the identical
    # stamp: the watermark must swallow it, not append a double-settle
    assert b.produce(topic, "OUT", leg, epoch=2, out_seq=10) == -1
    assert b.dup_suppressed == 1
    assert b.produce(topic, "OUT", leg, epoch=2, out_seq=11) == 1
    recs = b.fetch(topic, 0, 100, timeout=0.0)
    assert len(recs) == 2
    assert [r.out_seq for r in recs] == [10, 11]


def test_duplicate_transfer_delivery_deduped_at_the_consumer():
    from kme_tpu.bridge.consume import DedupRing

    ring = DedupRing()
    assert not ring.is_dup(2, 10)
    assert ring.is_dup(2, 10)          # redelivery of the same leg
    assert not ring.is_dup(3, 10)      # new epoch, new identity
    assert ring.suppressed == 1
