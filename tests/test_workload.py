"""Workload generator: determinism, preamble shape, distribution sanity,
and an oracle smoke-run over the harness distribution (the reference's own
"test" is exactly this: fire random events, assert no crash —
exchange_test.js:33-36, SURVEY.md §4)."""

import collections

import pytest

from kme_tpu import opcodes as op
from kme_tpu.oracle import OracleEngine
from kme_tpu.workload import WorkloadGen, cancel_heavy_stream, harness_stream, \
    payout_storm_stream, quote_churn_stream, zipf_hot_stream, \
    zipf_symbol_stream


def test_deterministic_under_seed():
    a = harness_stream(500, seed=7)
    b = harness_stream(500, seed=7)
    assert a == b
    c = harness_stream(500, seed=8)
    assert a != c


def test_preamble_shape_matches_reference():
    # exchange_test.js:23-32 with defaults: 10 accounts (create+transfer
    # pairs), then the float loop bound `i < 3/2+1` -> 3 symbols
    pre = WorkloadGen().preamble()
    assert len(pre) == 23
    assert [m.action for m in pre[:4]] == [100, 101, 100, 101]
    assert [m.sid for m in pre[20:]] == [0, 1, 2]
    # numSymbols=4 also creates only symbols 0..2 (the reference quirk)
    pre4 = WorkloadGen(num_symbols=4).preamble()
    assert [m.sid for m in pre4 if m.action == op.ADD_SYMBOL] == [0, 1, 2]


def test_event_mix_roughly_matches_per_mille():
    gen = WorkloadGen(seed=3)
    counts = collections.Counter(gen.gen_event().action for _ in range(50_000))
    assert 0.30 < counts[op.BUY] / 50_000 < 0.37
    assert 0.30 < counts[op.SELL] / 50_000 < 0.37
    # cancels include the opcode-bugged payouts (both action=4)
    assert 0.30 < counts[op.CANCEL] / 50_000 < 0.37
    assert counts[op.PAYOUT] == 0  # Q5: payout opcode bug


def test_payout_opcode_fix_flag():
    gen = WorkloadGen(seed=3, payout_opcode_bug=False)
    actions = [gen.gen_event().action for _ in range(50_000)]
    assert op.PAYOUT in actions


def test_validate_mode_bounds_domain():
    for m in harness_stream(5_000, seed=1, validate=True):
        if m.action in (op.BUY, op.SELL):
            assert 0 <= m.price <= 125 and m.size >= 1


def test_validate_clamps_the_one_trade_outside_the_java_device_domain():
    """Seed 2147483736 draws one trade that `--engine seq --compat
    java` does not keep on the device (0 <= price < 126, size > 0):
    message 14,330 of the whole stream, preamble included, SELL 58 x
    -1. With `validate` that one message reads SELL 58 x 1 and every
    other is the stock stream's — the stream the benchmark's
    java-harness-sat cell serves (PERF.md section 4)."""
    def outside(m):
        return m.action in (op.BUY, op.SELL) and not (
            0 <= m.price < 126 and m.size > 0)

    seed, events = 2147483736, 20000
    mine = harness_stream(events, seed=seed, validate=True)
    stock = harness_stream(events, seed=seed)
    assert len(mine) == len(stock) == events + 23
    assert not any(outside(m) for m in mine)
    assert [k for k, m in enumerate(stock) if outside(m)] == [14330]
    assert [k for k, (a, b) in enumerate(zip(mine, stock))
            if a != b] == [14330]
    assert (stock[14330].action, stock[14330].price,
            stock[14330].size) == (op.SELL, 58, -1)
    assert (mine[14330].action, mine[14330].price,
            mine[14330].size) == (op.SELL, 58, 1)


def test_oracle_survives_harness_distribution_java():
    e = OracleEngine("java")
    n = 0
    for m in harness_stream(5_000, seed=11):
        recs = e.process(m)
        assert recs[0].key == "IN" and recs[-1].key == "OUT"
        n += len(recs)
    assert n >= 10_000


def test_oracle_survives_harness_distribution_fixed():
    e = OracleEngine("fixed")
    for m in harness_stream(5_000, seed=11, payout_opcode_bug=False,
                            validate=True):
        e.process(m)
    # fixed-mode solvency: no balance ever ends negative
    assert all(b >= 0 for b in e.balances.values())


def test_scale_streams_shape():
    z = zipf_symbol_stream(2_000, num_symbols=64, num_accounts=128, seed=5)
    assert sum(1 for m in z if m.action == op.ADD_SYMBOL) == 64
    ch = cancel_heavy_stream(2_000, num_symbols=8, num_accounts=32, seed=5)
    cancels = sum(1 for m in ch if m.action == op.CANCEL)
    # every cancel consumes one prior submit: steady state caps near 50%
    assert cancels > 0.45 * 2_000


def test_quote_churn_is_lazy_and_deterministic_under_seed():
    kw = dict(num_symbols=16, num_accounts=64, standing=256)
    it = quote_churn_stream(4_000, seed=9, **kw)
    assert iter(it) is it           # a generator: the harness draws it
    a = list(it)                    # beside the server's start-up
    assert a == list(quote_churn_stream(4_000, seed=9, **kw))
    assert a != list(quote_churn_stream(4_000, seed=10, **kw))
    pre = a[:2 * 64 + 16]           # zipf_symbol_stream's preamble
    assert [m.action for m in pre[:2]] == [op.CREATE_BALANCE, op.TRANSFER]
    assert pre[1].size == 10_000_000
    assert [m.sid for m in pre[128:]] == list(range(16))
    assert len(a) == len(pre) + 4_000


@pytest.mark.parametrize("seed", [7, 2147540107])
def test_quote_churn_holds_its_mix(seed):
    """BASELINE.json config 4 as quote_churn_stream reads it: once the
    pool stands at `standing`, every second event a cancel; one submit
    in twenty a taker on the far side of the mid; quotes never cross
    the mid; through the reference, about four cancels in five are
    accepted, a taker lifts about four quotes, no trade is refused and
    a message makes about 2.2 records."""
    from kme_tpu.native.oracle import NativeOracleEngine

    standing, n = 512, 12_000
    msgs = list(quote_churn_stream(n, 16, 64, seed=seed,
                                   standing=standing))
    body = msgs[2 * 64 + 16:]
    assert all(m.action in (op.BUY, op.SELL) for m in body[:standing])
    steady = body[2 * standing:]
    cancels = [m for m in steady if m.action == op.CANCEL]
    trades = [m for m in steady if m.action in (op.BUY, op.SELL)]
    assert len(cancels) + len(trades) == len(steady)
    assert 0.48 < len(cancels) / len(steady) < 0.52
    far = [m for m in trades if (m.price >= 51) == (m.action == op.BUY)]
    assert 0.035 < len(far) / len(trades) < 0.065
    assert all(m.price != 50 and 0 <= m.price <= 125 and m.size >= 1
               for m in trades)
    sent_all = [m.oid for m in body if m.action != op.CANCEL]
    assert len(set(sent_all)) == len(sent_all)      # no oid twice
    # a cancel names an order submitted before it, once
    sent, pulled = set(), set()
    for m in body:
        if m.action == op.CANCEL:
            assert m.oid in sent and m.oid not in pulled
            pulled.add(m.oid)
        else:
            sent.add(m.oid)
    assert standing - 1 <= len(sent - pulled) <= standing + 1
    # zipf over the ranks: the first symbol leads
    by_sid = collections.Counter(m.sid for m in trades)
    assert by_sid[0] > 2 * by_sid[3] > 0
    try:
        eng = NativeOracleEngine("fixed", book_slots=128, max_fills=16)
    except RuntimeError:
        return                      # no native library: the mix stands
    lines = eng.process_wire([m.copy() for m in msgs])[-len(steady):]
    rejected = ['"action":7,' in g[-1] for g in lines]
    took = [not r for m, r in zip(steady, rejected)
            if m.action == op.CANCEL]
    assert 0.70 < sum(took) / len(took) < 0.86
    assert not any(r for m, r in zip(steady, rejected)
                   if m.action != op.CANCEL)
    lifted = [(len(g) - 2) // 2 for m, g in zip(steady, lines)
              if m in far]
    assert 3.0 < sum(lifted) / len(lifted) < 5.0
    assert 2.0 < sum(map(len, lines)) / len(lines) < 2.5
    assert max(collections.Counter(
        (o["sid"], o["action"]) for o in eng.export_state()["orders"]
        .values()).values()) <= 96      # 3/4 of the 128 slots a side


def test_zipf_hot_deterministic_and_skewed():
    a = zipf_hot_stream(3_000, num_symbols=8, num_accounts=32, seed=9)
    b = zipf_hot_stream(3_000, num_symbols=8, num_accounts=32, seed=9)
    assert a == b
    assert a != zipf_hot_stream(3_000, num_symbols=8, num_accounts=32,
                                seed=10)
    # symbol 0 dominates (hot_frac=0.7 of events), but the cold set is
    # ZIPF, not uniform: the second-ranked book must be distinctly warm
    # (that co-location is what defeats static `lane % shards` placement)
    sub = collections.Counter(
        m.sid for m in a if m.action in (op.BUY, op.SELL))
    total = sum(sub.values())
    assert sub[0] / total > 0.6
    assert sub[1] > 1.5 * sub[4]
    # valid domain end to end (the mesh parity tests feed this raw)
    for m in a:
        if m.action in (op.BUY, op.SELL):
            assert 0 <= m.price <= 125 and m.size >= 1


def test_payout_storm_deterministic_with_bursts():
    a = payout_storm_stream(2_000, num_symbols=8, num_accounts=32,
                            seed=4, storms=3)
    assert a == payout_storm_stream(2_000, num_symbols=8,
                                    num_accounts=32, seed=4, storms=3)
    payouts = [i for i, m in enumerate(a) if m.action == op.PAYOUT]
    # every storm settles EVERY symbol (real PAYOUT opcode, Q5 fixed)
    assert len(payouts) == 3 * 8
    # bursts are contiguous: each storm's 8 payouts interleave only
    # with their re-ADDs (payout positions step by 2 within a burst)
    for s in range(3):
        burst = payouts[s * 8:(s + 1) * 8]
        assert burst[-1] - burst[0] == 2 * 7
    # each payout is immediately followed by the symbol's re-ADD
    for i in payouts:
        assert a[i + 1].action == op.ADD_SYMBOL
        assert a[i + 1].sid == abs(a[i].sid)


def test_storm_profiles_deterministic_under_seed():
    # same seed -> identical stream, for every named profile; a seed
    # bump must move the stream (the chaos scenarios and the CI shed
    # gate both depend on this)
    from kme_tpu.workload import STORM_PROFILES, storm_stream

    for name in STORM_PROFILES:
        a = storm_stream(name, 800, num_symbols=8, num_accounts=16,
                         seed=3)
        b = storm_stream(name, 800, num_symbols=8, num_accounts=16,
                         seed=3)
        assert a == b, name
        assert a != storm_stream(name, 800, num_symbols=8,
                                 num_accounts=16, seed=4), name


def test_storm_windows_cover_stream_and_scale():
    from kme_tpu.workload import (STORM_PROFILES, storm_stream,
                                  storm_windows)

    for name in STORM_PROFILES:
        msgs = storm_stream(name, 800, num_symbols=8, num_accounts=16,
                            seed=0)
        wins = storm_windows(name, 800, num_symbols=8, num_accounts=16)
        assert wins, name
        for lo, hi, mult in wins:
            assert 0 <= lo < hi <= len(msgs), (name, lo, hi, len(msgs))
            assert mult > 1, name


def test_storm_profile_character():
    from kme_tpu import opcodes as op
    from kme_tpu.workload import storm_stream, storm_windows

    # payout-storm-wide: one contiguous burst settling EVERY symbol
    a = storm_stream("payout-storm-wide", 600, num_symbols=16,
                     num_accounts=16, seed=1)
    payouts = [i for i, m in enumerate(a) if m.action == op.PAYOUT]
    assert len(payouts) == 16
    assert payouts[-1] - payouts[0] == 2 * 15        # contiguous burst
    (lo, hi, mult), = storm_windows("payout-storm-wide", 600,
                                    num_symbols=16, num_accounts=16)
    assert lo <= payouts[0] and payouts[-1] < hi

    # cancel-storm: cancels dominate, mostly for bogus oids
    c = storm_stream("cancel-storm", 2_000, num_symbols=8,
                     num_accounts=16, seed=1)
    cancels = [m for m in c if m.action == op.CANCEL]
    assert len(cancels) > 0.6 * 2_000

    # hot-book: one symbol carries nearly all the order flow
    h = storm_stream("hot-book", 2_000, num_symbols=8,
                     num_accounts=16, seed=1)
    sub = collections.Counter(m.sid for m in h
                              if m.action in (op.BUY, op.SELL))
    assert sub[0] / sum(sub.values()) > 0.9

    # liquidation-cascade: multiple full-universe settlement waves
    lq = storm_stream("liquidation-cascade", 1_000, num_symbols=8,
                      num_accounts=16, seed=1)
    assert sum(1 for m in lq if m.action == op.PAYOUT) == 2 * 8


def test_storm_profiles_survive_oracle():
    # oracle-survival at small scale: every profile's full stream must
    # process without crash, and fixed-mode solvency must hold
    from kme_tpu.workload import STORM_PROFILES, storm_stream

    for name in STORM_PROFILES:
        e = OracleEngine("fixed")
        for m in storm_stream(name, 600, num_symbols=8,
                              num_accounts=16, seed=2):
            e.process(m)
        assert all(b >= 0 for b in e.balances.values()), name


def test_adversarial_streams_survive_oracle():
    e = OracleEngine("fixed")
    for m in zipf_hot_stream(1_500, num_symbols=8, num_accounts=24,
                             seed=2):
        e.process(m)
    e2 = OracleEngine("fixed")
    for m in payout_storm_stream(1_500, num_symbols=8,
                                 num_accounts=24, seed=2):
        e2.process(m)
    assert all(b >= 0 for b in e2.balances.values())


# -- market_lifecycle_stream (PR 36): a market that lists, trades,
# settles and is never relisted

_LIFE = dict(num_symbols=1024, num_accounts=2048)


def _lifecycle(events, seed):
    from kme_tpu.workload import market_lifecycle_stream

    return list(market_lifecycle_stream(events, seed=seed, **_LIFE))


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_lifecycle_stream_is_seed_deterministic(seed):
    a, b = _lifecycle(3000, seed), _lifecycle(3000, seed)
    assert a == b and a != _lifecycle(3000, seed + 1)
    # the preamble is zipf_symbol_stream's
    assert a[:5120] == zipf_symbol_stream(0, 1024, 2048, seed=seed)


def test_lifecycle_stream_never_repeats_an_id_and_holds_1024_listed():
    msgs = _lifecycle(200_000, seed=7)
    listed, ever, settled = set(), [], []
    for m in msgs:
        if m.action == op.ADD_SYMBOL:
            assert m.sid not in ever
            ever.append(m.sid)
            listed.add(m.sid)
        elif m.action == op.PAYOUT:
            listed.remove(abs(m.sid))   # paid out once, while listed
            settled.append(abs(m.sid))
        elif m.action in (op.BUY, op.SELL) and m.sid not in listed:
            # only the late order names an id that is not listed: the
            # one paid out last
            assert m.sid == settled[-1]
        assert len(listed) in (1023, 1024) or len(ever) < 1024
    assert ever == list(range(len(ever))) and len(ever) > 1024 + 150
    assert len(listed) == 1024
    # a settlement is followed at once by the next listing
    for i, m in enumerate(msgs):
        if m.action == op.PAYOUT:
            assert msgs[i + 1].action == op.ADD_SYMBOL
            assert m.size == 97


def test_lifecycle_stream_reads_the_upstreams_mix_per_mille():
    """exchange_test.js:106-117 with one slot added: per mille of EVENTS
    1 settlement (a PAYOUT and its ADD_SYMBOL), 1 late order, 2
    transfers, 332 buys, 332 sells, 332 cancels."""
    n = 200_000
    msgs = _lifecycle(n, seed=11)[5120:]
    c = collections.Counter(m.action for m in msgs)
    assert c[op.PAYOUT] == c[op.ADD_SYMBOL] and len(msgs) == n + c[op.PAYOUT]
    per_mille = {k: 1000 * v / n for k, v in c.items()}
    assert 0.7 < per_mille[op.PAYOUT] < 1.3
    assert 1.6 < per_mille[op.TRANSFER] < 2.4
    assert 329 < per_mille[op.CANCEL] < 335
    # the late order is a buy or a sell by a coin
    assert 329.5 < per_mille[op.BUY] < 335.5
    assert 329.5 < per_mille[op.SELL] < 335.5
    yes = sum(1 for m in msgs if m.action == op.PAYOUT and m.sid >= 0)
    assert 0.35 < yes / c[op.PAYOUT] < 0.65
    # trades stay inside the device domain, as `validate` clamps them
    assert all(0 <= m.price <= 125 and m.size >= 1 for m in msgs
               if m.action in (op.BUY, op.SELL))
