"""The symbol lifecycle on the seq engine (PR 36): a lane is bound to a
symbol id by its ADD_SYMBOL and goes back to the router's pool when an
accepted PAYOUT has emptied it, so a leader serves any number of ids
over its life on `--symbols` lanes.

What must hold:

- a market that lists, trades, settles and never relists — 40 distinct
  ids on 4 lanes — is served byte-exact against `NativeOracleEngine`
  AND ends in the reference's balances, positions and books (the
  credits `MatchOut` cannot show), serial and `--pipeline 2`, Python and
  native router, native and pure-Python reconstruction;
- the two routers stay column-exact, the map state a mid-batch
  `CapacityError` leaves included; five ids listed at once on four
  lanes still raises it;
- a message naming an id that holds no lane is rejected and takes none;
  `REMOVE_SYMBOL` alone keeps the lane (its positions stay);
- a snapshot taken between a settlement and the next listing restores
  to the same continuation and the same lane choice; the parent's
  files restore (both versions); a snapshot after payouts carries only
  the routes that survived them."""

import os
import random
import shutil

import numpy as np
import pytest

from kme_tpu import opcodes as op
from kme_tpu.bridge.broker import InProcessBroker
from kme_tpu.bridge.consume import consume_lines
from kme_tpu.bridge.provision import provision
from kme_tpu.bridge.service import TOPIC_IN, MatchService
from kme_tpu.engine import seq as SQ
from kme_tpu.native import load_library
from kme_tpu.native.oracle import NativeOracleEngine
from kme_tpu.runtime import checkpoint as ck
from kme_tpu.runtime import seqsession
from kme_tpu.runtime.seqsession import (ROUTER_STATS, CapacityError,
                                        NativeSeqRouter, SeqRouter,
                                        SeqSession)
from kme_tpu.wire import OrderMsg, WireBatch, dumps_order
from kme_tpu.workload import WorkloadGen, zipf_symbol_stream

HERE = os.path.dirname(os.path.abspath(__file__))
LANES, ACCOUNTS, SLOTS, FILLS = 4, 8, 128, 16
SERVE = dict(engine="seq", compat="fixed", batch=128, symbols=LANES,
             accounts=ACCOUNTS, slots=SLOTS, max_fills=FILLS)
CFG = SQ.SeqConfig(lanes=LANES, slots=SLOTS, accounts=128,
                   max_fills=FILLS, batch=128, fill_cap=1 << 12)

needs_native = pytest.mark.skipif(
    load_library() is None,
    reason="native host runtime unavailable (KME_NATIVE=0 or no "
           "toolchain)")


def msg(action, **kw):
    return OrderMsg(action=action, **kw)


def dense_lifecycle_stream(events=640, seed=1, ids=40):
    """market_lifecycle_stream's shape with settlements every ~16th
    event instead of every 1,000th, on 4 ranks: `ids` distinct symbol
    ids in all, each listed once. Beside the late order it sends a
    cancel of an order the wipe took, a second PAYOUT of a settled id,
    and a REMOVE_SYMBOL followed by the same id's re-listing (the one
    way a delisted id gets its book back before its payout)."""
    gen = WorkloadGen(ACCOUNTS, LANES, seed=seed, validate=True,
                      payout_opcode_bug=False)
    rng = random.Random(seed)
    out = []
    for aid in range(ACCOUNTS):
        out += [gen.create_account(aid), gen.create_transfer(aid, 1_000_000)]
    sid_of = list(range(LANES))
    out += [gen.create_symbol(s) for s in sid_of]
    next_id, settled, resting = LANES, None, {s: [] for s in sid_of}

    def trade(sid):
        make = gen.create_buy if rng.random() < 0.5 else gen.create_sell
        m = make(rng.randrange(ACCOUNTS), sid, gen._normal_param(50, 10),
                 gen._normal_param(20, 8))
        resting.setdefault(sid, []).append((m.oid, m.aid))
        return m

    for _ in range(events):
        e = rng.randrange(100)
        if e < 6 and next_id < ids:
            rank = rng.randrange(LANES)
            settled = sid_of[rank]
            out += [gen.create_payout(settled, rng.random() < 0.5),
                    gen.create_symbol(next_id)]
            sid_of[rank], next_id = next_id, next_id + 1
            resting[sid_of[rank]] = []
        elif e < 9 and settled is not None:
            out.append(trade(settled))                  # a late order
        elif e < 11 and settled is not None and resting[settled]:
            oid, aid = rng.choice(resting[settled])     # wiped with it
            out.append(msg(op.CANCEL, oid=oid, aid=aid))
        elif e < 12 and settled is not None:
            out.append(gen.create_payout(settled, True))    # paid twice
        elif e < 14:
            sid = rng.choice(sid_of)
            out += [msg(op.REMOVE_SYMBOL, sid=sid), trade(sid),
                    gen.create_symbol(sid)]
        elif e < 17:
            out.append(gen.create_transfer(rng.randrange(ACCOUNTS),
                                           gen._normal_param(0, 12500)))
        elif e < 75:
            out.append(trade(rng.choice(sid_of)))
        else:
            out.append(gen.create_cancel())
    assert next_id == ids, "the stream is too short to list every id"
    return out


def reference(msgs):
    """-> (MatchOut lines per message, the reference's stores in
    SeqSession.export_state's terms)."""
    eng = NativeOracleEngine("fixed", book_slots=SLOTS, max_fills=FILLS)
    lines = eng.process_wire([m.copy() for m in msgs])
    st = eng.export_state()
    orders = {oid: {"aid": o["aid"], "sid": o["sid"], "price": o["price"],
                    "size": o["size"], "is_buy": o["action"] == op.BUY}
              for oid, o in st["orders"].items()}
    return lines, {"balances": st["balances"], "positions": st["positions"],
                   "orders": orders,
                   # fixed-mode book keys are 2*sid (buy) / 2*sid+1
                   "books": {k // 2: True for k in st["books"]}}


def assert_state(ses, want):
    got = ses.export_state()
    for store in ("balances", "positions", "orders", "books"):
        assert got[store] == want[store], store


@pytest.fixture(scope="module")
def stream():
    msgs = dense_lifecycle_stream()
    lines, stores = reference(msgs)
    return msgs, lines, stores


def test_the_stream_lists_forty_ids_and_settles_thirty_six(stream):
    msgs, lines, stores = stream
    listed = [m.sid for m in msgs if m.action == op.ADD_SYMBOL]
    assert len(set(listed)) == 40
    flat = [ln for g in lines for ln in g]
    paid = [m for m, g in zip(msgs, lines) if m.action == op.PAYOUT
            and '"action":200' in g[-1]]
    assert len(paid) == 36
    assert sum('OUT {"action":7,' in ln for ln in flat) > 40   # rejects too
    assert len(stores["books"]) == LANES and stores["positions"]


@needs_native
@pytest.mark.parametrize("pipeline", [0, 2])
@pytest.mark.parametrize("router", ["native", "python"])
def test_served_stream_equals_the_reference_in_bytes_and_in_state(
        stream, pipeline, router, monkeypatch):
    msgs, lines, stores = stream
    if router == "python":
        monkeypatch.setattr(
            seqsession, "make_seq_router",
            lambda lanes, accounts, compat="fixed":
            SeqRouter(lanes, accounts, compat))
    broker = InProcessBroker()
    provision(broker)
    for m in msgs:
        broker.produce(TOPIC_IN, None, dumps_order(m))
    svc = MatchService(broker, pipeline=pipeline, **SERVE)
    want_router = SeqRouter if router == "python" else NativeSeqRouter
    assert type(svc._session.router) is want_router
    assert svc.run(max_messages=len(msgs)) == len(msgs)
    snap = svc.telemetry.snapshot()
    ses = svc._session
    svc.close()
    assert list(consume_lines(broker, follow=False)) \
        == [ln for g in lines for ln in g]
    assert_state(ses, stores)
    c, g = snap["counters"], snap["gauges"]
    assert c["symbols_settled"] == c["lanes_released"] == 36
    assert c["lanes_reused"] == 36 and c["symbols_listed"] >= 40
    assert c["unlisted_rejects"] > 0 and c["barrier_wiped_orders"] > 0
    assert c["barrier_credited_positions"] > 0
    assert (g["lanes_bound"], g["lanes_free"]) == (LANES, 0)
    assert g["route_purge_n"] >= 36 and g["route_purge_s"] > 0
    assert snap["counters"]["rej_capacity"] == 0


@pytest.mark.parametrize("path", ["process", "process_wire"])
def test_python_router_and_python_reconstruction(stream, path):
    """The KME_NATIVE=0 shape: SeqRouter, the numpy pack and the
    pure-Python record builders, in batches of 100 so that a lane names
    two ids inside one batch."""
    msgs, lines, stores = stream
    ses = SeqSession(CFG)
    ses.router = SeqRouter(LANES, 128)
    ses._use_native_wire = False
    got = []
    for lo in range(0, len(msgs), 100):
        part = [m.copy() for m in msgs[lo:lo + 100]]
        if path == "process":
            got += [[r.wire() for r in g] for g in ses.process(part)]
        else:
            got += ses.process_wire(part)
    assert got == lines
    assert_state(ses, stores)
    assert ses.router_stats["symbols_settled"] == 36


def routers():
    lib = load_library()
    if lib is None:
        pytest.skip("native library unavailable")
    return SeqRouter(LANES, 128), NativeSeqRouter(LANES, 128, lib)


def maps(r):
    return (dict(r.sid_lane), dict(r.aid_idx), dict(r.oid_sid),
            set(r.delisted),
            dict(zip(ROUTER_STATS[:5] + ROUTER_STATS[6:],
                     r.stats()[:5] + r.stats()[6:])))


def test_routers_column_exact_over_the_lifecycle(stream):
    msgs = stream[0]
    py, nat = routers()
    for lo in range(0, len(msgs), 97):
        part = msgs[lo:lo + 97]
        cp, rp = py.route(part)
        cn, rn = nat.route(WireBatch.from_msgs(part))
        assert rp == rn and set(cp) == set(cn)
        for f in cp:
            assert np.array_equal(cp[f], cn[f]), f
        assert maps(py) == maps(nat)
    assert nat.stats()[ROUTER_STATS.index("route_purge_ns")] > 0


def test_five_ids_listed_at_once_on_four_lanes_is_a_capacity_error():
    """...and both routers leave the same maps behind: the messages
    before the fifth listing are routed, it and those after are not."""
    batch = ([msg(op.CREATE_BALANCE, aid=1)]
             + [msg(op.ADD_SYMBOL, sid=s) for s in (10, 11, 12)]
             + [msg(op.BUY, oid=1, aid=1, sid=11, price=5, size=1),
                msg(op.PAYOUT, sid=11, size=97),
                msg(op.ADD_SYMBOL, sid=13), msg(op.ADD_SYMBOL, sid=14),
                msg(op.BUY, oid=2, aid=2, sid=14, price=5, size=1),
                msg(op.ADD_SYMBOL, sid=15),        # the fifth at once
                msg(op.BUY, oid=3, aid=3, sid=10, price=5, size=1)])
    left = []
    for r in routers():
        with pytest.raises(CapacityError, match="symbol capacity"):
            r.route(batch if isinstance(r, SeqRouter)
                    else WireBatch.from_msgs(batch))
        assert r.sid_lane == {10: 0, 12: 2, 13: 1, 14: 3}
        assert r.oid_sid == {2: 14} and r.aid_idx == {1: 0, 2: 1}
        left.append(maps(r))
        # a settlement makes room again, and the pool is whole
        cols, rej = r.route([msg(op.PAYOUT, sid=-12, size=97),
                             msg(op.ADD_SYMBOL, sid=15)])
        assert cols["lane"].tolist() == [2, 2] and not rej
    assert left[0] == left[1]


def test_a_message_naming_an_unlisted_id_is_rejected_and_takes_no_lane():
    pre = [msg(op.CREATE_BALANCE, aid=1),
           msg(op.TRANSFER, aid=1, size=10_000),
           msg(op.ADD_SYMBOL, sid=7), msg(op.ADD_SYMBOL, sid=8),
           msg(op.BUY, oid=1, aid=1, sid=7, price=40, size=2),
           msg(op.PAYOUT, sid=7, size=97)]
    late = [msg(op.SELL, oid=2, aid=1, sid=7, price=40, size=1),  # late
            msg(op.CANCEL, oid=1, aid=1),       # an order the wipe took
            msg(op.PAYOUT, sid=7, size=97),     # paid twice
            msg(op.REMOVE_SYMBOL, sid=7),
            msg(op.BUY, oid=3, aid=1, sid=99, price=40, size=1),
            msg(op.PAYOUT, sid=-99, size=97)]   # never listed
    lines, stores = reference(pre + late)
    ses = SeqSession(CFG)
    assert ses.process_wire([m.copy() for m in pre]) == lines[:len(pre)]
    bound = ses.router.sid_lane
    assert bound == {8: 1} and ses.router_stats["lanes_bound"] == 1
    got = ses.process_wire([m.copy() for m in late])
    assert got == lines[len(pre):]
    assert all('OUT {"action":7,' in g[-1] for g in got)
    assert ses.router.sid_lane == bound
    assert ses.router_stats["lanes_bound"] == 1
    # the unknown-oid cancel is the old kind of host reject, not this
    assert ses.router_stats["unlisted_rejects"] == len(late) - 1
    assert_state(ses, stores)


def test_remove_symbol_without_payout_keeps_the_lane():
    """Its positions stay in the lane, so the id stays bound: delisted.
    A PAYOUT of a delisted id is the device's to reject, the id's
    re-listing gives it its book back, and its payout then frees the
    lane."""
    msgs = ([msg(op.CREATE_BALANCE, aid=a) for a in (1, 2)]
            + [msg(op.TRANSFER, aid=a, size=10_000) for a in (1, 2)]
            + [msg(op.ADD_SYMBOL, sid=5),
               msg(op.BUY, oid=1, aid=1, sid=5, price=40, size=3),
               msg(op.SELL, oid=2, aid=2, sid=5, price=40, size=3),
               msg(op.BUY, oid=3, aid=1, sid=5, price=30, size=1),
               msg(op.REMOVE_SYMBOL, sid=5)])
    ses = SeqSession(CFG)
    ses.process_wire([m.copy() for m in msgs])
    r = ses.router
    assert r.sid_lane == {5: 0} and r.delisted == {5}
    assert ses.export_state()["positions"] == {(1, 5): (3, 3),
                                               (2, 5): (-3, -3)}
    more = [msg(op.PAYOUT, sid=5, size=97),         # no book: rejected
            msg(op.BUY, oid=4, aid=1, sid=5, price=30, size=1),
            msg(op.ADD_SYMBOL, sid=6)]              # takes lane 1, not 0
    ses.process_wire([m.copy() for m in more])
    assert r.sid_lane == {5: 0, 6: 1} and r.delisted == {5}
    assert ses.router_stats["symbols_settled"] == 0
    last = [msg(op.ADD_SYMBOL, sid=5), msg(op.PAYOUT, sid=5, size=97),
            msg(op.ADD_SYMBOL, sid=9)]
    lines, stores = reference(msgs + more + last)
    assert ses.process_wire([m.copy() for m in last]) == lines[-3:]
    assert r.sid_lane == {6: 1, 9: 0} and r.delisted == set()
    assert ses.router_stats["lanes_reused"] == 1
    assert_state(ses, stores)


def test_snapshot_between_a_settlement_and_the_next_listing(stream,
                                                            tmp_path):
    """The restored router rebuilds its pool from `sid_lane`: the same
    continuation, byte for byte, and the same lanes chosen — with a
    delisted id on board, which the restored books give back."""
    msgs, lines, stores = stream
    payouts = [i for i, (m, g) in enumerate(zip(msgs, lines))
               if m.action == op.PAYOUT and '"action":200' in g[-1]]
    cut = payouts[20] + 1
    assert msgs[cut].action == op.ADD_SYMBOL
    # a REMOVE_SYMBOL whose re-listing comes after the cut
    head = msgs[:cut] + [msg(op.REMOVE_SYMBOL,
                             sid=sid_listed(msgs[:cut]))]
    tail = msgs[cut:cut + 1] + [msg(op.ADD_SYMBOL, sid=head[-1].sid)] \
        + msgs[cut + 1:]
    want, stores = reference(head + tail)
    ses = SeqSession(CFG)
    assert ses.process_wire([m.copy() for m in head]) == want[:len(head)]
    assert len(ses.router.sid_lane) == LANES - 1 and ses.router.delisted
    path = ck.save_seq_session(str(tmp_path), ses, len(head))
    _, meta = ck._load_file(path)
    assert set(meta) <= {"version", "kind", "offset", "cfg", "metrics",
                         "hist", "aid_idx", "sid_lane", "oid_sid",
                         "rr_lane", "width", "shards", "layout"}
    back, off = ck.load_seq_session(str(tmp_path), CFG)
    assert off == len(head)
    for r in (ses.router, back.router):
        assert r.sid_lane == ses.router.sid_lane
    assert back.router.delisted == ses.router.delisted
    for s in (ses, back):
        assert s.process_wire([m.copy() for m in tail]) == want[len(head):]
        assert_state(s, stores)
    assert back.router.sid_lane == ses.router.sid_lane


@pytest.mark.parametrize("router", ["native", "python"])
def test_a_snapshot_after_payouts_holds_only_the_surviving_routes(
        stream, router, tmp_path):
    """A settlement purges its symbol's routes from the router, an
    order that leaves the book takes its own with it (PR 41), and the
    snapshot carries what the router holds, nothing thinned and nothing
    kept back: the file's two arrays are the orders that rest, a subset
    of a never-forgetting router's map over the same messages, and none
    of them names a settled id."""
    msgs, lines, _ = stream
    payouts = [i for i, (m, g) in enumerate(zip(msgs, lines))
               if m.action == op.PAYOUT and '"action":200' in g[-1]]
    head = msgs[:payouts[20] + 1]
    ses = SeqSession(CFG)
    if router == "python":
        ses.router = SeqRouter(LANES, 128)
    elif load_library() is None:
        pytest.skip("native host runtime unavailable")
    ses.process_wire([m.copy() for m in head])
    ref = SeqRouter(LANES, 128)
    ref.route([m.copy() for m in head])
    with np.load(ck.save_seq_session(str(tmp_path), ses, len(head))) as z:
        got = dict(zip(z["route_oid"].tolist(), z["route_sid"].tolist()))
        assert z["route_oid"].tolist() == sorted(got)
    resting = ses.export_state()["orders"]
    assert got == {oid: o["sid"] for oid, o in resting.items()} and got
    assert got.items() <= ref.oid_sid.items()
    traded = {m.oid for m in head if m.action in (op.BUY, op.SELL)}
    assert len(got) < len(traded) / 2           # most went with a wipe
    assert set(got.values()) <= set(ses.router.sid_lane)
    assert ses.snapshot_gauges["snapshot_routes"] == len(got)
    assert ses.snapshot_gauges["stale_routes"] == 0


def sid_listed(msgs):
    """An id that holds a book after `msgs` and has resting orders or
    positions worth keeping: the most traded of the listed ones."""
    _, stores = reference(msgs)
    count = {sid: 0 for sid in stores["books"]}
    for m in msgs:
        if m.action in (op.BUY, op.SELL) and m.sid in count:
            count[m.sid] += 1
    return max(count, key=count.get)


@pytest.mark.parametrize("name,offset,seed,version", [
    ("seq_pre_pr29.npz", 700, 11, 1),
    ("seq_dense_pr34.npz", 600, 12, 1),
    ("seq_sparse_pr35.npz", 600, 12, 2),    # written by the parent
])
def test_snapshots_of_older_writers_restore_and_their_lanes_turn_over(
        name, offset, seed, version, tmp_path):
    """The format did not change: a file the parent wrote (all eight
    lanes bound, by its bind-for-ever router) restores; the pool is
    rebuilt from its `sid_lane`, so the next settlement frees a lane
    and the next new id takes it."""
    msgs = list(zipf_symbol_stream(900, 8, 64, seed=seed, zipf_a=0.0))
    msgs += [msg(op.PAYOUT, sid=3, size=97), msg(op.ADD_SYMBOL, sid=100),
             msg(op.BUY, oid=5, aid=1, sid=100, price=50, size=2),
             msg(op.SELL, oid=6, aid=2, sid=100, price=50, size=1),
             msg(op.BUY, oid=7, aid=1, sid=3, price=50, size=2)]
    shutil.copy(os.path.join(HERE, "data", name),
                ck.snapshot_path(str(tmp_path), offset))
    _, meta = ck._load_file(ck.snapshot_path(str(tmp_path), offset))
    assert meta["version"] == version
    eng = NativeOracleEngine("fixed", book_slots=128, max_fills=16)
    want = eng.process_wire([m.copy() for m in msgs])
    ses, off = ck.load_seq_session(str(tmp_path))
    assert off == offset and len(ses.router.sid_lane) == 8
    lane = ses.router.sid_lane[3]
    assert ses.process_wire([m.copy() for m in msgs[offset:]]) \
        == want[offset:]
    assert ses.router.sid_lane[100] == lane and 3 not in ses.router.sid_lane
    exp = ses.export_state()
    ref = eng.export_state()
    assert exp["balances"] == ref["balances"]
    assert exp["positions"] == ref["positions"]
