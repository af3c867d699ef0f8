"""A brokerage's stream (PR 48): `kme_tpu.workload.brokerage_stream` —
TPC-E's populations and order mix, YCSB's scrambled zipfian senders —
at 40 symbols x 384 accounts.

What must hold:

- the stream is a generator function, seed-deterministic, its preamble
  `_storm_preamble`'s;
- six submits in ten are marketable, cancels are at most a tenth of the
  messages and at least half of them find their quote resting, and a
  message makes half a trade or more (against `NativeOracleEngine`);
- the hot accounts are no neighbours: the 16 that send most lie in more
  than one tile of a lane's position store;
- served on the normal path (`--engine seq --compat fixed --pipeline 2`,
  snapshots on, stopped and resumed from its newest snapshot in the
  middle) `MatchOut` is the reference's byte for byte, and it ends in
  the reference's state with one route for each resting order."""

import collections
import inspect
import itertools

import pytest

from kme_tpu import opcodes as op
from kme_tpu.bridge.broker import InProcessBroker
from kme_tpu.bridge.consume import consume_lines
from kme_tpu.bridge.provision import provision
from kme_tpu.bridge.service import TOPIC_IN, MatchService
from kme_tpu.native import load_library
from kme_tpu.native.oracle import NativeOracleEngine
from kme_tpu.wire import dumps_order
from kme_tpu.workload import _storm_preamble, WorkloadGen, brokerage_stream

SYMBOLS, ACCOUNTS, EVENTS, SLOTS, FILLS = 40, 384, 20_000, 128, 16
# the pool of cancellable quotes at this size: 64 (the cell's 512 is
# for 3,425 books; in 40 a quote is filled sooner)
PARAMS = dict(standing=64)
PREAMBLE = 2 * ACCOUNTS + SYMBOLS

needs_native = pytest.mark.skipif(
    load_library() is None,
    reason="native host runtime unavailable (KME_NATIVE=0 or no "
           "toolchain)")


def stream(events=EVENTS, seed=7, **kw):
    return brokerage_stream(events, SYMBOLS, ACCOUNTS, seed=seed,
                            **{**PARAMS, **kw})


@pytest.fixture(scope="module")
def served():
    """(messages, the reference's lines per message, its stores)."""
    msgs = list(stream())
    eng = NativeOracleEngine("fixed", book_slots=SLOTS, max_fills=FILLS)
    lines = eng.process_wire([m.copy() for m in msgs])
    return msgs, lines, eng.export_state()


def test_it_yields_and_the_seed_decides_every_message():
    assert inspect.isgeneratorfunction(brokerage_stream)
    it = stream(seed=2 ** 31 + 5)
    head = list(itertools.islice(it, PREAMBLE + 500))
    again = list(itertools.islice(stream(seed=2 ** 31 + 5), PREAMBLE + 500))
    other = list(itertools.islice(stream(seed=6), PREAMBLE + 500))
    assert head == again and head[PREAMBLE:] != other[PREAMBLE:]
    # the preamble is the storms': every account created and funded,
    # ids 0..symbols-1 listed
    gen = WorkloadGen(ACCOUNTS, SYMBOLS, seed=0, validate=True,
                      payout_opcode_bug=False)
    assert head[:PREAMBLE] == _storm_preamble(gen, ACCOUNTS, SYMBOLS,
                                              1_000_000_000)
    assert len(list(it)) == EVENTS - 500


@needs_native
@pytest.mark.parametrize("invariant", [
    "six-in-ten-marketable", "cancels-a-tenth-half-accepted",
    "half-a-trade-a-message", "no-order-refused"])
def test_the_stream_keeps_its_invariants(invariant, served):
    msgs, lines, _stores = served
    c = collections.Counter()
    for m, out in zip(msgs, lines):
        rejected = '"action":7,' in out[-1][:20]
        if m.action in (op.BUY, op.SELL):
            c["submits"] += 1
            c["marketable"] += (m.price >= 51 if m.action == op.BUY
                                else m.price <= 49)
            c["trades"] += (len(out) - 2) // 2
            c["refused"] += rejected
        elif m.action == op.CANCEL:
            c["cancels"] += 1
            c["accepted"] += not rejected
    if invariant == "six-in-ten-marketable":
        assert 0.59 <= c["marketable"] / c["submits"] <= 0.61
    elif invariant == "cancels-a-tenth-half-accepted":
        assert 0.08 < c["cancels"] / len(msgs) <= 0.10
        assert c["accepted"] >= c["cancels"] / 2
    elif invariant == "half-a-trade-a-message":
        assert c["trades"] >= len(msgs) / 2
    else:
        assert c["refused"] <= c["submits"] / 1000


def test_the_hot_accounts_are_no_neighbours():
    sent = collections.Counter(
        m.aid for m in stream() if m.action in (op.BUY, op.SELL))
    hot = [aid for aid, _n in sent.most_common(16)]
    assert len({aid >> 8 for aid in hot}) > 1       # tiles of 256
    assert sent[hot[0]] > 3 * sent[hot[15]]         # and skewed
    # another seed, another permutation
    other = collections.Counter(
        m.aid for m in stream(seed=8) if m.action in (op.BUY, op.SELL))
    assert [a for a, _ in other.most_common(16)] != hot


@needs_native
def test_served_with_snapshots_and_a_restore_it_is_the_references_bytes(
        served, tmp_path):
    msgs, lines, stores = served
    broker = InProcessBroker()
    provision(broker)
    for m in msgs:
        broker.produce(TOPIC_IN, None, dumps_order(m))
    serve = dict(engine="seq", compat="fixed", batch=128, symbols=SYMBOLS,
                 accounts=ACCOUNTS, slots=SLOTS, max_fills=FILLS,
                 pipeline=2, exactly_once=True,
                 checkpoint_dir=str(tmp_path), checkpoint_every=2048)
    cut = 86 * 128       # 11,008: whole batches
    svc = MatchService(broker, **serve)
    assert svc.run(max_messages=cut) == cut
    assert svc._session.snapshot_gauges["snapshot_pos_calls"] == 1
    svc.close()
    # a new leader on the same directory: the newest snapshot (message
    # 10,240) and the input log from there
    svc = MatchService(broker, **serve)
    resumed = svc.offset
    assert resumed == 10_240 and svc.epoch == 2
    assert svc.run(max_messages=len(msgs) - resumed) == len(msgs) - resumed
    svc.checkpoint()
    ses, final = svc._session, svc.metrics()
    svc.close()
    assert broker.dup_suppressed > 0      # the replayed tail's records
    assert list(consume_lines(broker, follow=False)) \
        == [ln for g in lines for ln in g]
    got = ses.export_state()
    assert got["balances"] == stores["balances"]
    assert got["positions"] == stores["positions"]
    assert set(got["orders"]) == set(stores["orders"])
    assert dict(ses.router.oid_sid) \
        == {oid: o["sid"] for oid, o in stores["orders"].items()}
    assert final["stale_routes"] == 0 == final["rej_capacity"]
    assert ses.snapshot_gauges["snapshot_live_positions"] \
        == len(stores["positions"]) > 2000
