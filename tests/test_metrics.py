"""On-device metrics: counters accumulated by the kernel + gauges.

The counters must agree exactly with the oracle-checked wire stream
(they are derived from the same per-message outcomes)."""

from kme_tpu.engine import seq as SQ
from kme_tpu.runtime.seqsession import SeqSession
from kme_tpu.workload import zipf_symbol_stream

CFG = SQ.SeqConfig(lanes=8, slots=128, accounts=128, max_fills=16)


def _stream():
    return zipf_symbol_stream(800, num_symbols=8, num_accounts=24, seed=3,
                              zipf_a=1.0, payout_per_mille=4)


def test_metrics_agree_with_wire_stream():
    msgs = _stream()
    ses = SeqSession(CFG)
    lines = [ln for lines in ses.process_wire(msgs) for ln in lines]
    met = ses.metrics()

    fills = sum(1 for ln in lines if ln.startswith('OUT {"action":5'))
    assert met["fills"] * 2 == fills + sum(
        1 for ln in lines if ln.startswith('OUT {"action":6'))
    # every trade emits maker+taker events: fills counter == maker events
    assert met["trades_ok"] + met["rej_capacity"] + met["rej_risk"] == sum(
        1 for m in msgs if m.action in (2, 3))
    # every payout in this stream executes (zipf_symbol_stream re-ADDs
    # the symbol right after each payout, so the book always exists at
    # settle time — the counter counts EXECUTED settles)
    assert met["barriers"] == sum(1 for m in msgs if m.action in (1, 200))
    assert met["barriers"] > 0
    assert met["open_orders"] >= 0 and met["books"] <= CFG.lanes
    assert met["accounts"] == 24

    # cumulative across batches: a second batch only adds
    met2_before = met["msgs"]
    ses.process_wire(_stream()[:100])
    assert ses.metrics()["msgs"] > met2_before
