"""An order's route lives as long as the order (PR 41): the seq routers
write a route when a trade is routed and drop it when the order is
known to have left the book — refused, filled at once, filled later as
a maker, cancelled — not only when its symbol is wiped, so a leader's
route map is as large as its books and not as long as its life.

What must hold:

- Python and native router agree on every drop, stamps included (a
  drop learned from plan k leaves alone what a later plan wrote);
- a quoting market (`quote_churn_stream`) is served byte-exact against
  `NativeOracleEngine` and ends in its state, serial and `--pipeline 2`,
  both routers; every snapshot's routes are exactly its resting orders;
- a partly filled last maker keeps its route and can be cancelled, an
  exactly emptied one loses it (the kernel's bit in the flags plane);
- an oid reused while the router is ahead of the collect, or inside one
  batch, is still cancellable;
- snapshot -> restore -> continue equals the uninterrupted run, and a
  file an older tree wrote restores with its stale routes and serves;
- java mode keeps every route."""

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
from kme_tpu.runtime.seqsession import (ROUTER_STATS, NativeSeqRouter,
                                        SeqRouter, SeqSession, route_events)
from kme_tpu.wire import OrderMsg, WireBatch, dumps_order
from kme_tpu.workload import (harness_stream, quote_churn_stream,
                              zipf_symbol_stream)

HERE = os.path.dirname(os.path.abspath(__file__))
LANES, ACCOUNTS, SLOTS, FILLS = 16, 64, 128, 16
CFG = SQ.SeqConfig(lanes=LANES, slots=SLOTS, accounts=128,
                   max_fills=FILLS, batch=128, fill_cap=1 << 12)
SERVE = dict(engine="seq", compat="fixed", batch=128, symbols=LANES,
             accounts=128, slots=SLOTS, max_fills=FILLS)

needs_native = pytest.mark.skipif(
    load_library() is None,
    reason="native host runtime unavailable (KME_NATIVE=0 or no "
           "toolchain)")


def msg(action, **kw):
    return OrderMsg(action=action, **kw)


def reference(msgs, slots=SLOTS):
    """-> (MatchOut lines per message, the reference's stores in
    SeqSession.export_state's terms)."""
    eng = NativeOracleEngine("fixed", book_slots=slots, max_fills=FILLS)
    lines = eng.process_wire([m.copy() for m in msgs])
    st = eng.export_state()
    orders = {oid: {"aid": o["aid"], "sid": o["sid"], "price": o["price"],
                    "size": o["size"], "is_buy": o["action"] == op.BUY}
              for oid, o in st["orders"].items()}
    return lines, {"balances": st["balances"], "positions": st["positions"],
                   "orders": orders,
                   "books": {k // 2: True for k in st["books"]}}


def assert_state(ses, want):
    got = ses.export_state()
    for store in ("balances", "positions", "orders", "books"):
        assert got[store] == want[store], store


def stale_routes(ses):
    return ses.stale_routes(ses.metrics()["open_orders"])


def assert_routes_are_the_resting_orders(ses):
    resting = {oid: o["sid"] for oid, o in ses.export_state()["orders"]
               .items()}
    assert dict(ses.router.oid_sid) == resting
    assert stale_routes(ses) == 0


@pytest.fixture(scope="module")
def churn():
    """A quoting market at a small size: 16 symbols, 64 accounts, about
    200 orders resting at any time, every second message a cancel."""
    msgs = list(quote_churn_stream(8_000, LANES, ACCOUNTS, seed=3,
                                   standing=256))
    lines, stores = reference(msgs)
    return msgs, lines, stores


def preamble(accounts=(1, 2, 3), symbols=(5, 6)):
    return ([msg(op.CREATE_BALANCE, aid=a) for a in accounts]
            + [msg(op.TRANSFER, aid=a, size=1_000_000) for a in accounts]
            + [msg(op.ADD_SYMBOL, sid=s) for s in symbols])


# -- the routers -----------------------------------------------------


def fetched(rows):
    """(cols, host, fills) as a fetch would give them, from rows of
    (act, oid, ok, residual, [(maker oid, emptied), ...])."""
    nfill = [len(r[4]) for r in rows]
    makers = [m for r in rows for m in r[4]]
    cols = {"act": np.array([r[0] for r in rows], np.int32),
            "oid": np.array([r[1] for r in rows], np.int64)}
    host = {"ok": np.array([r[2] for r in rows], bool),
            "residual": np.array([r[3] for r in rows], np.int32),
            "nfill": np.array(nfill, np.int32),
            "last_emptied": np.array([bool(r[4]) and r[4][-1][1]
                                      for r in rows], bool)}
    fills = np.zeros((4, len(makers)), np.int64)
    fills[0] = [m[0] for m in makers]
    return cols, host, fills


@needs_native
def test_python_and_native_router_agree_on_every_drop(churn):
    """Both routers over the same batches, told the same made-up fetch
    results with the plan the collect of a pipelined service would name
    (up to two plans behind the router): the same routes are left, the
    same counts returned, at every step."""
    msgs = churn[0]
    py = SeqRouter(LANES, 128)
    nat = NativeSeqRouter(LANES, 128, load_library())
    rng = random.Random(11)
    seen, pending = [], []
    for lo in range(0, len(msgs), 97):
        part = msgs[lo:lo + 97]
        cp, rp = py.route(part)
        cn, rn = nat.route(WireBatch.from_msgs(part))
        assert rp == rn
        for f in cp:
            assert np.array_equal(cp[f], cn[f]), f
        plan = py.stats()[ROUTER_STATS.index("plans")]
        assert plan == nat.stats()[ROUTER_STATS.index("plans")]
        rows = []
        for act, oid in zip(cp["act"].tolist(), cp["oid"].tolist()):
            ok = rng.random() < 0.8
            swept = []
            if act in (SQ.L_BUY, SQ.L_SELL):
                if ok and seen and rng.random() < 0.3:
                    # a maker is swept once: the last one, partly or not
                    swept = [(o, True) for o in rng.sample(
                        seen, min(len(seen), rng.randrange(1, 4)))]
                    swept[-1] = (swept[-1][0], rng.random() < 0.5)
                seen.append(oid)
            elif act == SQ.L_CANCEL and rng.random() < 0.2:
                act, oid = SQ.L_BUY, rng.choice(seen)   # an oid again
            rows.append((act, oid, ok, rng.choice((0, 0, 3)), swept))
        pending.append((plan, fetched(rows)))
        while len(pending) > rng.choice((0, 1, 2)):
            k, got = pending.pop(0)
            assert py.drop_batch(*got, k) == nat.drop_batch(*got, k)
            assert dict(py.oid_sid) == dict(nat.oid_sid)
    assert py.stats()[:5] + py.stats()[7:] \
        == nat.stats()[:5] + nat.stats()[7:]
    assert 0 < py.n_routes() == nat.n_routes() < len(seen) / 2


@pytest.mark.parametrize("kind", ["python", "native"])
def test_a_drop_leaves_alone_what_a_later_plan_wrote(kind):
    if kind == "native" and load_library() is None:
        pytest.skip("native library unavailable")
    r = (SeqRouter(4, 16) if kind == "python"
         else NativeSeqRouter(4, 16, load_library()))
    route = (lambda ms: r.route(ms) if kind == "python"
             else r.route(WireBatch.from_msgs(ms)))
    dead = lambda oid: (SQ.L_BUY, oid, False, 0, [])        # noqa: E731
    rests = lambda oid: (SQ.L_BUY, oid, True, 1, [])        # noqa: E731
    route(preamble())                                           # plan 1
    route([msg(op.BUY, oid=7, aid=1, sid=5, price=40, size=1),
           msg(op.BUY, oid=8, aid=1, sid=5, price=40, size=1)])   # plan 2
    route([msg(op.SELL, oid=7, aid=2, sid=6, price=60, size=1)])  # plan 3
    assert r.oid_sid == {7: 6, 8: 5}
    # plan 2's collect: both its orders died, but 7 is plan 3's by now
    assert r.drop_batch(*fetched([dead(7), dead(8)]), 2) == (1, 1)
    assert r.oid_sid == {7: 6}
    # the last event of an oid decides: dead, then rested again
    assert r.drop_batch(*fetched([dead(7), rests(7)]), 3) == (1, 1)
    # ... rested, then swept whole by a later taker of the batch
    assert r.drop_batch(*fetched([
        rests(7), (SQ.L_SELL, 9, True, 0, [(7, True)])]), 3) == (2, 0)
    assert r.oid_sid == {}
    # an imported route carries no stamp: any collect may drop it
    r.import_routes(np.array([9]), np.array([5]))
    assert r.drop_batch(*fetched([
        (SQ.L_CANCEL, 9, True, 0, [])]), 0) == (3, 0)
    # a fetch whose fill counts run past its fills is refused whole
    cols, host, fills = fetched([(SQ.L_SELL, 9, True, 0, [(7, True)])])
    with pytest.raises((RuntimeError, IndexError, ValueError)):
        r.drop_batch(cols, host, fills[:, :0], 5)


def test_route_events_lists_a_batch_in_message_order():
    """Rows: a buy that rests, a sell that sweeps two makers (the last
    one partly), a refused buy, an accepted and a refused cancel, a
    create."""
    cols = {"act": np.array([SQ.L_BUY, SQ.L_SELL, SQ.L_BUY, SQ.L_CANCEL,
                             SQ.L_CANCEL, SQ.L_CREATE], np.int32),
            "oid": np.array([10, 11, 12, 13, 14, 0], np.int64)}
    host = {"ok": np.array([1, 1, 0, 1, 0, 1], bool),
            "nfill": np.array([0, 2, 0, 0, 0, 0], np.int32),
            "residual": np.array([5, 0, 7, 0, 0, 0], np.int32),
            "last_emptied": np.zeros(6, bool)}
    fills = np.array([[20, 21], [0, 0], [50, 50], [3, 1]], np.int64)
    oids, alive = route_events(cols, host, fills)
    assert oids.tolist() == [10, 20, 11, 12, 13]
    assert alive.tolist() == [True, False, False, False, False]
    host["last_emptied"][1] = True
    oids, alive = route_events(cols, host, fills)
    assert oids.tolist() == [10, 20, 21, 11, 12, 13]
    assert not alive[1:].any()


# -- the session -----------------------------------------------------


@pytest.mark.parametrize("emptied", [False, True])
def test_the_last_maker_of_a_sweep(emptied):
    """Two asks of 5 at 50; a buy of 8 (or of 10) takes the first whole
    and the second partly (or whole). The partly filled one keeps its
    route and a cancel reaches it; the emptied one's route is gone and
    the cancel is rejected on the host — the same bytes either way."""
    msgs = preamble() + [
        msg(op.SELL, oid=1, aid=1, sid=5, price=50, size=5),
        msg(op.SELL, oid=2, aid=2, sid=5, price=50, size=5),
        msg(op.BUY, oid=3, aid=3, sid=5, price=50,
            size=10 if emptied else 8)]
    after = [msg(op.CANCEL, oid=2, aid=2), msg(op.CANCEL, oid=1, aid=1),
             msg(op.CANCEL, oid=3, aid=3)]
    lines, stores = reference(msgs + after)
    ses = SeqSession(CFG)
    assert ses.process_wire([m.copy() for m in msgs]) == lines[:len(msgs)]
    assert ses.router.oid_sid == ({} if emptied else {2: 5})
    assert_routes_are_the_resting_orders(ses)
    made = ses.router_stats["routes_made"]
    assert (made, ses.routes_dropped, ses.routes_held) \
        == (3, 3 if emptied else 2, 0 if emptied else 1)
    got = ses.process_wire([m.copy() for m in after])
    assert got == lines[len(msgs):]
    assert ('"action":7,' in got[0][-1]) == emptied
    assert ses.router_stats["cancels_routed"] == (0 if emptied else 1)
    assert ses.router_stats["cancels_host_rejected"] \
        == (3 if emptied else 2)
    assert ses.router.oid_sid == {}
    assert_state(ses, stores)


@needs_native
def test_an_oid_reused_inside_the_pipeline_window_is_cancellable():
    """Batch one's order 7 is filled at once; batch two, planned before
    batch one is collected, rests another order 7 on another symbol.
    The collect of batch one must not take batch two's route."""
    pre = preamble() + [msg(op.SELL, oid=1, aid=1, sid=5, price=50,
                            size=5)]
    one = [msg(op.BUY, oid=7, aid=2, sid=5, price=50, size=5)]
    two = [msg(op.SELL, oid=7, aid=3, sid=6, price=60, size=4)]
    three = [msg(op.CANCEL, oid=7, aid=3), msg(op.CANCEL, oid=7, aid=3)]
    lines, stores = reference(pre + one + two + three)
    flat = [ln for g in lines for ln in g]
    ses = SeqSession(CFG)
    ses.process_wire([m.copy() for m in pre])
    h1 = ses.submit(one)
    h2 = ses.submit(two)
    got = []
    for h in (h1, h2, ses.submit(three)):
        buf, off, _ = ses.collect(h)
        text = buf.decode()
        got += [text[off[i]:off[i + 1]] for i in range(len(off) - 1)]
        if h is h1:
            assert ses.router.oid_sid == {7: 6}     # batch two's
    assert got == flat[-len(got):]
    assert '"action":4,' in got[-3] and '"action":7,' in got[-1]
    assert ses.router.oid_sid == {}
    assert_state(ses, stores)


def test_an_oid_reused_inside_one_batch_is_cancellable():
    """Cancelled and sent again under the same oid in one batch: the
    last event decides, so the route stays with the order that rests."""
    batch = preamble() + [
        msg(op.BUY, oid=7, aid=1, sid=5, price=40, size=2),
        msg(op.CANCEL, oid=7, aid=1),
        msg(op.SELL, oid=7, aid=2, sid=6, price=60, size=3)]
    after = [msg(op.CANCEL, oid=7, aid=2)]
    lines, stores = reference(batch + after)
    ses = SeqSession(CFG)
    assert ses.process_wire([m.copy() for m in batch]) == lines[:len(batch)]
    assert ses.router.oid_sid == {7: 6}
    assert ses.process_wire([m.copy() for m in after]) == lines[-1:]
    assert '"action":4,' in lines[-1][-1]
    assert_state(ses, stores)


@needs_native
@pytest.mark.parametrize("pipeline", [0, 2])
@pytest.mark.parametrize("router", ["native", "python"])
def test_a_quoting_market_is_served_byte_exact_and_forgets_as_it_learns(
        churn, pipeline, router, monkeypatch, tmp_path):
    msgs, lines, stores = churn
    if router == "python":
        monkeypatch.setattr(
            seqsession, "make_seq_router",
            lambda lanes, accounts, compat="fixed":
            SeqRouter(lanes, accounts, compat))
    broker = InProcessBroker()
    provision(broker)
    for m in msgs:
        broker.produce(TOPIC_IN, None, dumps_order(m))
    svc = MatchService(broker, pipeline=pipeline,
                       checkpoint_dir=str(tmp_path), checkpoint_every=1024,
                       checkpoint_keep=100, **SERVE)
    assert svc.run(max_messages=len(msgs)) == len(msgs)
    svc.checkpoint()
    svc._publish_spans()
    snap = svc.telemetry.snapshot()
    ses = svc._session
    final = svc.metrics()
    svc.close()
    assert list(consume_lines(broker, follow=False)) \
        == [ln for g in lines for ln in g]
    assert_state(ses, stores)
    assert_routes_are_the_resting_orders(ses)
    assert final["stale_routes"] == 0 == final["rej_capacity"]
    # every snapshot's routes are its own resting orders
    files = sorted(f for f in os.listdir(tmp_path) if f.endswith(".npz"))
    assert len(files) >= 6
    for f in files:
        data, _ = ck._load_file(os.path.join(tmp_path, f))
        resting = data["slot_oid"][data["slot_used"].astype(bool)]
        assert data["route_oid"].tolist() == sorted(resting.tolist()), f
    c, g = snap["counters"], snap["gauges"]
    trades = sum(m.action in (op.BUY, op.SELL) for m in msgs)
    cancels = sum(m.action == op.CANCEL for m in msgs)
    assert c["routes_made"] == trades
    assert c["cancels_routed"] + c["cancels_host_rejected"] == cancels
    assert c["routes_made"] - c["routes_dropped"] == g["routes_held"] \
        == len(stores["orders"]) == g["snapshot_routes"]
    assert c["cancels_host_rejected"] > cancels / 10   # the filled ones
    assert g["stale_routes"] == 0
    assert g["route_drop_n"] >= len(msgs) // 128 and g["route_drop_s"] > 0
    assert g["route_purge_n"] == 0


@needs_native
def test_snapshot_restore_continue_equals_the_uninterrupted_run(
        churn, tmp_path):
    msgs, lines, stores = churn
    cut = 5_000
    ses = SeqSession(CFG)
    got = []
    for lo in range(0, cut, 500):
        got += ses.process_wire([m.copy() for m in msgs[lo:lo + 500]])
    ck.save_seq_session(str(tmp_path), ses, cut)
    assert ses.snapshot_gauges["stale_routes"] == 0
    assert ses.snapshot_gauges["snapshot_routes"] \
        == ses.snapshot_gauges["snapshot_live_slots"] > 100
    back, off = ck.load_seq_session(str(tmp_path), CFG)
    assert off == cut and back.router.oid_sid == ses.router.oid_sid
    for lo in range(cut, len(msgs), 500):
        got += back.process_wire([m.copy() for m in msgs[lo:lo + 500]])
    assert got == lines
    assert_state(back, stores)
    assert_routes_are_the_resting_orders(back)


def test_a_snapshot_of_an_older_tree_restores_with_its_stale_routes(
        tmp_path):
    """PR 35's file holds every route its writer had ever made (408 for
    109 resting orders). It restores, the stale ones are counted, a
    cancel naming one goes to the device and is rejected there as
    before, the stream is served on byte-exact, and a stale route goes
    when its symbol is wiped."""
    msgs = list(zipf_symbol_stream(900, 8, 64, seed=12, zipf_a=0.0))
    shutil.copy(os.path.join(HERE, "data", "seq_sparse_pr35.npz"),
                ck.snapshot_path(str(tmp_path), 600))
    cfg = SQ.SeqConfig(lanes=8, slots=128, accounts=128, max_fills=16,
                       batch=128, fill_cap=1 << 12)
    ses, off = ck.load_seq_session(str(tmp_path), cfg)
    assert off == 600
    resting = ses.export_state()["orders"]
    routes = dict(ses.router.oid_sid)
    assert set(resting) < set(routes)
    stale = stale_routes(ses)
    assert stale == len(routes) - len(resting) > 100
    gone = next(o for o in sorted(routes) if o not in resting)
    tail = msgs[600:] + [msg(op.CANCEL, oid=gone, aid=1),
                         msg(op.PAYOUT, sid=routes[gone], size=97)]
    lines, stores = reference(msgs[:600] + tail)
    assert ses.process_wire([m.copy() for m in tail]) == lines[600:]
    assert ses.router_stats["cancels_routed"] >= 1
    assert_state(ses, stores)
    left = stale_routes(ses)
    assert 0 < left < stale and gone not in ses.router.oid_sid
    assert routes[gone] not in set(ses.router.oid_sid.values())


def test_java_mode_keeps_every_route():
    msgs = harness_stream(600, seed=4, validate=True)
    cfg = SQ.SeqConfig(lanes=8, slots=512, accounts=128, max_fills=128,
                       batch=256, fill_cap=1 << 14, compat="java")
    ses = SeqSession(cfg)
    ses.process_wire([m.copy() for m in msgs])
    traded = {m.oid for m in msgs if m.action in (op.BUY, op.SELL)}
    assert set(ses.router.oid_sid) == traded
    assert ses.routes_dropped == 0 and ses.routes_held == len(traded)
    assert stale_routes(ses) is None and "stale_routes" not in ses.metrics()
    assert ses.router.n_routes() == len(traded)
