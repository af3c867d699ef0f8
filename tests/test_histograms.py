"""On-device distribution histograms vs full host-side recomputation.

The kernel accumulates three power-of-two-bucket histograms alongside
the metrics vector (engine/seq.py kernel epilogue) — fetched with the
same transfers, never an extra device round-trip:

- fills_per_order: one observation per ACCEPTED trade, value = number
  of maker fills (a resting 0-fill trade lands in bucket 0);
- book_depth: one observation per book-mutating message (accepted
  trade or cancel), value = the touched lane's occupied slot count
  (both sides) AFTER the message;
- batch_occupancy: one observation per non-empty kernel call, value =
  the messages in it.

The host recomputations here share NO code with the kernels: fills and
depth replay the stream through the quirk-exact oracle, occupancy
replays the host router."""

import pytest

from kme_tpu import opcodes as op
from kme_tpu.engine import seq as SQ
from kme_tpu.oracle import OracleEngine
from kme_tpu.runtime.seqsession import SeqSession, make_seq_router
from kme_tpu.telemetry import N_BUCKETS, bucket_index
from kme_tpu.workload import zipf_symbol_stream


def host_fills_and_depth(msgs, book_slots, max_fills):
    """Expected fills_per_order / book_depth via oracle replay."""
    ora = OracleEngine("fixed", book_slots=book_slots, max_fills=max_fills)
    fills = [0] * N_BUCKETS
    depth = [0] * N_BUCKETS
    for m in msgs:
        is_trade = m.action in (op.BUY, op.SELL)
        is_cancel = m.action == op.CANCEL
        # a cancel's sid comes from the resting order it targets
        sid = m.sid
        if is_cancel:
            rest = ora.orders.get(m.oid)
            sid = rest.sid if rest is not None else None
        recs = ora.process(m.copy())
        accepted = recs[-1].value.action != op.REJECT
        if not accepted:
            continue
        if is_trade:
            fills[bucket_index((len(recs) - 2) // 2)] += 1
        if is_trade or is_cancel:
            d = sum(1 for o in ora.orders.values() if o.sid == sid)
            depth[bucket_index(d)] += 1
    return fills, depth


def host_occupancy_seq(msgs, cfg):
    """Routed messages per kernel call: the dispatch chunks the routed
    stream into cfg.batch-sized calls (runtime/seqsession.py _plan)."""
    r = make_seq_router(cfg.lanes, cfg.accounts, compat=cfg.compat)
    cols, _ = r.route([m.copy() for m in msgs])
    n = len(cols["act"])
    occ = [0] * N_BUCKETS
    for ci in range(max(-(-n // cfg.batch), 1)):
        c = max(min(cfg.batch, n - ci * cfg.batch), 0)
        if c > 0:
            occ[bucket_index(c)] += 1
    return occ


def _stream(n, symbols=8, accounts=24, seed=5, payout_per_mille=3):
    return zipf_symbol_stream(n, num_symbols=symbols,
                              num_accounts=accounts, seed=seed,
                              zipf_a=1.0,
                              payout_per_mille=payout_per_mille)


def _check_seq(msgs, cfg):
    ses = SeqSession(cfg)
    ses.process_wire([m.copy() for m in msgs])
    h = ses.histograms()
    fills, depth = host_fills_and_depth(msgs, cfg.slots, cfg.max_fills)
    assert h["fills_per_order"] == fills
    assert h["book_depth"] == depth
    assert h["batch_occupancy"] == host_occupancy_seq(msgs, cfg)
    assert sum(fills) > 0 and sum(depth) > 0


def test_seq_histograms_match_host():
    _check_seq(_stream(600),
               SQ.SeqConfig(lanes=8, slots=128, accounts=128,
                            max_fills=16))


def test_seq_java_fills_histogram():
    """Java mode has no book-depth plane (the merged-book layout has no
    per-lane occupancy), but fills and occupancy still accumulate."""
    msgs = _stream(400, payout_per_mille=0)  # no barriers in java mode
    cfg = SQ.SeqConfig(lanes=8, slots=128, accounts=128, max_fills=16,
                       compat="java")
    ses = SeqSession(cfg)
    ses.process_wire([m.copy() for m in msgs])
    h = ses.histograms()
    met = ses.metrics()
    assert sum(h["fills_per_order"]) == met["trades_ok"]
    assert h["book_depth"] == [0] * N_BUCKETS
    assert sum(h["batch_occupancy"]) > 0


def test_hist_observation_counts_match_metrics():
    """Structural invariants tying the histograms to the counters:
    one fills observation per accepted trade, one depth observation per
    accepted trade or cancel."""
    msgs = _stream(600)
    ses = SeqSession(SQ.SeqConfig(lanes=8, slots=128, accounts=128,
                                  max_fills=16))
    ses.process_wire([m.copy() for m in msgs])
    h = ses.histograms()
    met = ses.metrics()
    assert sum(h["fills_per_order"]) == met["trades_ok"] > 0
    assert sum(h["book_depth"]) == met["trades_ok"] + met["cancels_ok"]


@pytest.mark.slow
def test_seq_histograms_match_host_10k():
    """The acceptance-criterion conformance stream: 10k orders."""
    _check_seq(_stream(10_000, symbols=16, accounts=64, seed=7),
               SQ.SeqConfig(lanes=16, slots=128, accounts=128,
                            max_fills=16))
