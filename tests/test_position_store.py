"""The fixed-mode position store (engine/seq.py): dense, sized by the
deployment (lanes x accounts), in HBM with one tile cached in VMEM.
Byte-exact against the native oracle through MatchService's serial
path, with every pair the configuration has live at once, across a
snapshot + restore, through payouts (the per-lane tile scan) and with
several tiles a lane.
"""

import dataclasses
import os

import numpy as np
import pytest

import kme_tpu.opcodes as op
from kme_tpu.bridge.broker import InProcessBroker
from kme_tpu.bridge.consume import consume_lines
from kme_tpu.bridge.provision import TOPIC_IN, provision
from kme_tpu.bridge.service import MatchService
from kme_tpu.engine import seq as SQ
from kme_tpu.native.oracle import NativeOracleEngine
from kme_tpu.wire import OrderMsg, dumps_order
from kme_tpu.workload import zipf_symbol_stream

HERE = os.path.dirname(os.path.abspath(__file__))


def _preamble(accounts, symbols):
    msgs = []
    for a in range(accounts):
        msgs += [OrderMsg(action=op.CREATE_BALANCE, aid=a),
                 OrderMsg(action=op.TRANSFER, aid=a, size=10**7)]
    return msgs + [OrderMsg(action=op.ADD_SYMBOL, sid=s)
                   for s in range(symbols)]


def _every_pair_stream(accounts=128, symbols=8):
    """Every (account, symbol) pair of the configuration holds a
    position at once (accounts 2k / 2k+1 trade one contract on every
    symbol), then the store is worked on while full: partial reversals,
    resting orders with margin, cancels, a payout either way and a
    removed symbol, each followed by trades on the lane again."""
    msgs = _preamble(accounts, symbols)
    oid = 1000

    def trade(seller, buyer, sid, size=1, price=50):
        nonlocal oid
        msgs.append(OrderMsg(action=op.SELL, oid=oid, aid=seller, sid=sid,
                             price=price, size=size))
        msgs.append(OrderMsg(action=op.BUY, oid=oid + 1, aid=buyer, sid=sid,
                             price=price, size=size))
        oid += 2

    for s in range(symbols):
        for k in range(0, accounts, 2):
            trade(k, k + 1, s, size=1 + (k + s) % 5)
    full_at = len(msgs)
    for s in range(symbols):
        for k in range(0, accounts, 8):
            trade(k + 1, k, s, size=1 + (k + s) % 3)    # toward zero
        for k in range(3, accounts, 16):                # rest, then cancel
            msgs.append(OrderMsg(action=op.BUY, oid=oid, aid=k, sid=s,
                                 price=40, size=7))
            msgs.append(OrderMsg(action=op.CANCEL, oid=oid, aid=k))
            oid += 1
    msgs.append(OrderMsg(action=op.PAYOUT, sid=2, size=97))
    msgs.append(OrderMsg(action=op.PAYOUT, sid=-3, size=97))
    msgs.append(OrderMsg(action=op.REMOVE_SYMBOL, sid=4))
    for s in (2, 3, 4):
        msgs.append(OrderMsg(action=op.ADD_SYMBOL, sid=s))
        for k in range(0, accounts, 32):
            trade(k, k + 5, s, size=2)
    return msgs, full_at


def _reference(msgs, slots=128, max_fills=16):
    ref = NativeOracleEngine("fixed", book_slots=slots, max_fills=max_fills)
    return [ln for g in ref.process_wire([m.copy() for m in msgs])
            for ln in g]


def _serve(tmp_path, msgs, stop_at=None, **kw):
    """MatchService, serial path, over a durable broker log; with
    `stop_at` the first incarnation is dropped there and a second
    resumes from its newest snapshot. -> (MatchOut lines, services,
    messages the first had processed: whole batches)."""
    log_dir = str(tmp_path / "broker-log")
    kw = dict(engine="seq", compat="fixed", slots=128, max_fills=16,
              pipeline=0, checkpoint_dir=str(tmp_path / "ck"), **kw)
    broker = InProcessBroker(persist_dir=log_dir)
    provision(broker)
    for m in msgs:
        broker.produce(TOPIC_IN, None, dumps_order(m))
    svcs = [MatchService(broker, **kw)]
    stopped = None
    if stop_at is not None:
        stopped = svcs[0].run(max_messages=stop_at)
        resumed_at = svcs[0]._last_ckpt_offset
        assert 0 < resumed_at <= stopped < len(msgs)
        broker = InProcessBroker(persist_dir=log_dir)   # the process died
        svcs.append(MatchService(broker, **kw))
        assert svcs[1].offset == resumed_at
    rest = len(msgs) - svcs[-1].offset
    assert svcs[-1].run(max_messages=rest) == rest
    return list(consume_lines(broker, follow=False)), svcs, stopped


def test_every_pair_live_at_once_through_the_service(tmp_path):
    msgs, full_at = _every_pair_stream()
    got, (svc,), _ = _serve(tmp_path, msgs, symbols=8, accounts=128,
                            batch=256, checkpoint_every=10**9)
    assert got == _reference(msgs)
    cfg = svc._session.cfg
    assert cfg.pos_capacity == 8 * 128
    # the store was full, to the last pair, when message `full_at` ran
    probe = NativeOracleEngine("fixed", book_slots=128, max_fills=16)
    probe.process_wire([m.copy() for m in msgs[:full_at]])
    assert len(probe.export_state()["positions"]) == cfg.pos_capacity


def _per_message(msgs):
    return NativeOracleEngine("fixed", book_slots=128,
                              max_fills=16).process_wire(
        [m.copy() for m in msgs])


def _with_replay(per, stop, at):
    """What MatchOut holds when the first incarnation stopped after
    `stop` messages and the second resumed from the snapshot at `at`:
    the tail after the snapshot is there twice (at-least-once,
    exactly-once off)."""
    return ([ln for g in per[:stop] for ln in g]
            + [ln for g in per[at:] for ln in g])


def test_full_store_through_snapshot_and_restore(tmp_path):
    """The snapshot is taken while every pair is live; what follows
    (reversals, cancels, payouts) runs on the restored store."""
    msgs, full_at = _every_pair_stream()
    got, svcs, stop = _serve(tmp_path, msgs, stop_at=full_at + 200,
                             symbols=8, accounts=128, batch=256,
                             checkpoint_every=full_at)
    at = svcs[0]._last_ckpt_offset
    assert at >= full_at
    assert got == _with_replay(_per_message(msgs), stop, at)
    assert svcs[1]._session.metrics()["positions"] > 0


def test_store_in_hbm_tile_cache_byte_exact(tmp_path):
    """A uniform stream over 1280 accounts (five tiles a lane) switches
    tiles at nearly every fill; payouts scan a lane's tiles; a snapshot
    + restore in between."""
    symbols, accounts = 128, 1280
    msgs = list(zipf_symbol_stream(1500, symbols, accounts, seed=5,
                                   zipf_a=0.0))
    msgs += [OrderMsg(action=op.PAYOUT, sid=1, size=97),
             OrderMsg(action=op.PAYOUT, sid=-2, size=97),
             OrderMsg(action=op.ADD_SYMBOL, sid=1),
             OrderMsg(action=op.ADD_SYMBOL, sid=2)]
    msgs += list(zipf_symbol_stream(600, symbols, accounts, seed=6,
                                    zipf_a=0.0))[2 * accounts + symbols:]
    got, svcs, stop = _serve(
        tmp_path, msgs, stop_at=2 * accounts + symbols + 1200,
        symbols=symbols, accounts=accounts, batch=512,
        checkpoint_every=1024)
    cfg = svcs[1]._session.cfg
    assert cfg.pos_tiles_per_lane == 5
    assert got == _with_replay(_per_message(msgs), stop,
                               svcs[0]._last_ckpt_offset)
    ses = svcs[1]._session
    assert ses.pos_probe_tiles > 0
    gauges = svcs[1].telemetry.snapshot()["gauges"]
    live = ses.metrics()["positions"]
    assert live > 256
    assert gauges["books_in_hbm"] == 0 and "pos_in_hbm" not in gauges
    svcs[1]._engine_refresh()
    svcs[1]._publish_spans()
    snap = svcs[1].telemetry.snapshot()
    assert snap["gauges"]["pos_live"] == live
    assert snap["gauges"]["pos_capacity"] == symbols * accounts
    assert snap["gauges"]["pos_load_pct"] == pytest.approx(
        100.0 * live / (symbols * accounts), abs=1e-3)
    assert snap["counters"]["pos_probe_tiles"] == ses.pos_probe_tiles


@pytest.mark.parametrize("dispatch", ["lockstep", "async"])
def test_mesh_shards_count_the_tiles_they_bring(cpu_devices, dispatch):
    """A shard's store is in HBM like any other: both collect loops of
    the mesh session add each call's tile count."""
    from kme_tpu.parallel.seqmesh import SeqMeshSession

    msgs = list(zipf_symbol_stream(300, 8, 64, seed=3, zipf_a=0.0))
    ses = SeqMeshSession(SQ.SeqConfig(lanes=8, slots=128, accounts=128,
                                      max_fills=16), 2, dispatch=dispatch)
    assert ses.dispatch == dispatch
    assert ses.process_wire([m.copy() for m in msgs]) == _per_message(msgs)
    assert ses.pos_probe_tiles > 0


def _construction_sites(tmp_path):
    """A SeqConfig from every place the program builds one for a
    deployment of 256 symbols x 1024 accounts x 128 slots."""
    from kme_tpu.parallel.seqmesh import SeqMeshSession
    from kme_tpu.runtime import checkpoint as ck
    from kme_tpu.runtime.seqsession import SeqSession

    svc = MatchService.__new__(MatchService)
    svc._req_symbols, svc._req_accounts = 256, 1000   # rounds up to 1024
    svc._req_slots, svc._req_max_fills, svc._compat = 128, 16, "fixed"
    served = svc._seq_cfg()
    yield "service._seq_cfg", served
    small = dataclasses.replace(served, lanes=8, accounts=128)
    ck.save_seq_session(str(tmp_path), SeqSession(small), 3)
    ses, _ = ck.load_seq_session(str(tmp_path), None)   # cfg from the file
    yield "checkpoint (snapshot's cfg)", ses.cfg
    ses, _ = ck.load_seq_session(str(tmp_path), small)
    yield "checkpoint (service's cfg)", ses.cfg
    yield "seqmesh shard", SeqMeshSession(
        dataclasses.replace(served, lanes=8, accounts=128), 2,
        dispatch="lockstep").local_cfg


def test_capacity_is_one_function_of_the_configuration(tmp_path):
    """Wherever a fixed-mode SeqConfig is built the store holds lanes x
    accounts pairs, whatever pos_cap says; java mode keeps its hash's
    own capacity."""
    for where, cfg in _construction_sites(tmp_path):
        assert cfg.pos_capacity == cfg.lanes * cfg.accounts, where
        assert cfg.pos_rows == (cfg.lanes * -(-cfg.accounts // 256) * 8), \
            where
        assert SQ.make_seq_state(cfg)["pos"].shape == (cfg.pos_rows, 128)
    a = SQ.SeqConfig(lanes=8, accounts=128, pos_cap=128, probe_max=1)
    b = SQ.SeqConfig(lanes=8, accounts=128)
    assert a.pos_capacity == b.pos_capacity == 1024
    # kme-serve's flagless shape
    assert SQ.SeqConfig(lanes=1024, accounts=4096).pos_capacity == 1 << 22
    j = SQ.SeqConfig(lanes=8, accounts=128, compat="java", pos_cap=1 << 12)
    assert j.pos_capacity == 1 << 12
    assert "pos" not in SQ.make_seq_state(j)


def test_snapshot_from_before_the_dense_store_restores(tmp_path):
    """tests/data/seq_pre_pr29.npz was written by the tree before this
    store existed (hash planes on the device, SeqConfig(pos_cap=2048,
    probe_max=16) in its meta) at offset 700 of the stream below. It
    restores into today's session, which finishes the stream
    byte-exact."""
    import shutil

    from kme_tpu.runtime import checkpoint as ck

    msgs = list(zipf_symbol_stream(900, 8, 64, seed=11, zipf_a=0.0))
    shutil.copy(os.path.join(HERE, "data", "seq_pre_pr29.npz"),
                str(tmp_path / "ckpt-700.npz"))
    for cfg in (None, SQ.SeqConfig(lanes=8, slots=128, accounts=128,
                                   max_fills=16)):
        ses, off = ck.load_seq_session(str(tmp_path), cfg)
        assert off == 700 and ses.cfg.pos_capacity == 8 * 128
        assert np.asarray(ses.state["pos"]).any()
        assert (ses.process_wire([m.copy() for m in msgs[700:]])
                == _per_message(msgs)[700:])
