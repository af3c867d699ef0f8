"""Continuous invariant auditor (kme_tpu/telemetry/audit.py): the
shadow ledger stays clean on real streams, trips on injected
corruption, cross-checks the live engine at checkpoint cadence, and
its repro dumps reproduce offline."""

import json

import numpy as np
import pytest

from kme_tpu.bridge.broker import InProcessBroker, line_offsets
from kme_tpu.bridge.provision import provision
from kme_tpu.bridge.service import TOPIC_IN, MatchService
from kme_tpu.telemetry import Registry
from kme_tpu.telemetry.audit import (InvariantAuditor, load_repro,
                                     replay_repro)
from kme_tpu.native import load_library
from kme_tpu.telemetry.journal import (EventBatch, Journal, _decode, _encode,
                                       batch_events, oracle_events,
                                       rec_dtype)
from kme_tpu.oracle import OracleEngine
from kme_tpu.wire import dumps_order, parse_order
from kme_tpu.workload import harness_stream


def _event_batches(n=600, seed=21, chunk=60, book_slots=None,
                   max_fills=None):
    """Message-aligned event batches from an oracle replay — what the
    journal's observer fan-out delivers per committed batch."""
    msgs = harness_stream(n, seed=seed, num_accounts=8, num_symbols=3,
                          payout_opcode_bug=False, validate=True)
    lines = [dumps_order(m) for m in msgs]
    evs = oracle_events(lines, book_slots=book_slots,
                        max_fills=max_fills)
    # chunk message-aligned (by input offset): the auditor finalizes
    # its pending taker at batch end, so a message must not straddle
    # two observe() calls — exactly the guarantee record_batch gives
    out = []
    for lo in range(0, len(lines), chunk):
        out.append([dict(ev, b=lo // chunk) for ev in evs
                    if lo <= ev.get("off", -1) < lo + chunk])
    return lines, out


def test_clean_stream_no_violations():
    reg = Registry()
    aud = InvariantAuditor(registry=reg)
    _, batches = _event_batches()
    for evs in batches:
        aud.observe(evs)
    assert aud.violations == []
    assert reg.counter("audit_violations").value == 0
    assert reg.counter("audit_batches").value == len(batches)
    # the shadow actually accumulated state (not vacuously clean)
    assert aud.balances and aud.batches == len(batches)


def test_payout_stream_stays_clean():
    # settlement wipes books + mints external credit; the escrow
    # invariant must survive it (payouts count as inflow)
    from kme_tpu.workload import zipf_symbol_stream

    msgs = zipf_symbol_stream(900, num_symbols=4, num_accounts=8,
                              seed=4, payout_per_mille=30)
    evs = oracle_events([dumps_order(m) for m in msgs])
    assert any(e["e"] in ("payout", "remove_symbol") for e in evs)
    aud = InvariantAuditor()
    aud.observe(evs)
    assert aud.violations == []


def test_tampered_fill_qty_detected(tmp_path):
    reg = Registry()
    hits = []
    aud = InvariantAuditor(registry=reg, repro_dir=str(tmp_path),
                           on_violation=lambda v, d: hits.append((v, d)))
    _, batches = _event_batches()
    # bump the first fill's quantity in the first batch that has one
    done = False
    for evs in batches:
        if not done:
            for ev in evs:
                if ev["e"] == "fill":
                    ev["qty"] += 1
                    done = True
                    break
        aud.observe(evs)
    assert done and aud.violations
    kinds = {v["kind"] for v in aud.violations}
    assert kinds & {"fill_overfill", "rest_mismatch",
                    "unfilled_residual", "state_mismatch",
                    "position_sum", "escrow_negative",
                    "fill_no_taker"}
    assert reg.counter("audit_violations").value == len(aud.violations)
    assert hits and hits[0][1] is not None       # repro dump written


def test_tampered_balance_conjuring_detected():
    # a transfer event whose qty was inflated after the fact breaks
    # the escrow bound: balances exceed external inflow
    _, batches = _event_batches(300)
    aud = InvariantAuditor()
    tampered = False
    for evs in batches:
        for ev in evs:
            if not tampered and ev["e"] == "fill":
                ev["px"] += 1            # maker paid a different price
                tampered = True
        aud.observe(evs)
    assert tampered
    assert aud.violations


def test_repro_dump_replays_offline(tmp_path):
    aud = InvariantAuditor(repro_dir=str(tmp_path))
    _, batches = _event_batches(500)
    done = False
    for evs in batches:
        if not done:
            for ev in evs:
                if ev["e"] == "fill":
                    ev["qty"] += 2
                    done = True
                    break
        aud.observe(evs)
    assert aud.dumps, "violation must write a repro dump"
    doc = load_repro(aud.dumps[0])
    assert doc["violations"] and doc["events"] and "pre_state" in doc
    # the dump is self-contained: a fresh auditor seeded from its
    # pre-batch state re-finds the violation
    found = replay_repro(aud.dumps[0])
    assert found
    assert ({v["kind"] for v in doc["violations"]}
            <= {v["kind"] for v in found} | {v["kind"] for v in found})


def test_check_engine_against_seq_session():
    from kme_tpu.engine import seq as SQ
    from kme_tpu.runtime.seqsession import SeqSession

    msgs = harness_stream(300, seed=9, num_accounts=8, num_symbols=3,
                          payout_opcode_bug=False, validate=True)
    ses = SeqSession(SQ.SeqConfig(lanes=8, slots=128, accounts=128,
                                  max_fills=16))
    aud = InvariantAuditor()
    for lo in range(0, len(msgs), 100):
        part = [m.copy() for m in msgs[lo:lo + 100]]
        records = ses.process_wire(part)
        evs = batch_events(records, reasons=ses.last_reasons,
                           offsets=list(range(lo, lo + len(part))))
        aud.observe(evs)
    assert aud.violations == []
    # deep cross-check vs the engine's exported stores + histograms
    assert aud.check_engine(ses.export_state(), ses.histograms()) == []
    # corrupt one shadow balance: check_engine must notice
    aid = next(iter(aud.balances))
    aud.balances[aid] += 1
    found = aud.check_engine(ses.export_state())
    assert found and found[0]["kind"] == "state_mismatch"


def test_service_audit_end_to_end_tamper(tmp_path, monkeypatch):
    """The ISSUE's acceptance path: a serving MatchService with --audit
    detects an injected conservation violation (KME_AUDIT_TAMPER test
    hook), increments audit_violations, marks the heartbeat degraded,
    and writes a repro dump that reproduces offline."""
    monkeypatch.setenv("KME_AUDIT_TAMPER", "fill_qty")
    msgs = harness_stream(400, seed=13, num_accounts=8, num_symbols=3,
                          payout_opcode_bug=False, validate=True)
    broker = InProcessBroker()
    provision(broker)
    for m in msgs:
        broker.produce(TOPIC_IN, None, dumps_order(m))
    jp = str(tmp_path / "journal.jsonl")
    rd = str(tmp_path / "repro")
    svc = MatchService(broker, engine="oracle", compat="fixed",
                       batch=80, journal=jp, audit=True,
                       audit_repro_dir=rd)
    assert svc.run(max_messages=len(msgs)) == len(msgs)
    svc.close()
    assert svc.auditor is not None and svc.auditor.violations
    assert svc.degraded is not None
    assert svc.telemetry.counter("audit_violations").value > 0
    hb = tmp_path / "hb.json"
    svc._write_heartbeat(str(hb), seen=len(msgs), tick=1)
    doc = json.loads(hb.read_text())
    assert doc["degraded"] == svc.degraded
    assert doc["metrics"]["counters"]["audit_violations"] > 0
    assert svc.auditor.dumps
    assert replay_repro(svc.auditor.dumps[0])


def test_service_audit_clean_run_and_annotations(tmp_path):
    """No tamper: a full service run over the harness stream audits
    clean, and --annotate-rejects adds ADDITIVE REJ records without
    touching the reference IN/OUT byte stream."""
    from kme_tpu.bridge.consume import consume_lines

    msgs = harness_stream(400, seed=2, num_accounts=8, num_symbols=3,
                          payout_opcode_bug=False, validate=True)
    per_msg = []
    ora = OracleEngine("fixed")
    for m in msgs:
        per_msg.append([r.wire() for r in ora.process(m.copy())])
    broker = InProcessBroker()
    provision(broker)
    for m in msgs:
        broker.produce(TOPIC_IN, None, dumps_order(m))
    jp = str(tmp_path / "journal.bin")
    svc = MatchService(broker, engine="oracle", compat="fixed",
                       batch=100, journal=jp, audit=True,
                       annotate_rejects=True)
    assert svc.run(max_messages=len(msgs)) == len(msgs)
    svc.close()
    assert svc.auditor.violations == []
    assert svc.degraded is None
    got = list(consume_lines(broker, follow=False))
    rej = [ln for ln in got if ln.startswith("REJ ")]
    rest = [ln for ln in got if not ln.startswith("REJ ")]
    assert rest == [ln for lines in per_msg for ln in lines]
    n_rejects = sum(1 for lines in per_msg
                    if '"action":7,' in lines[-1])
    assert len(rej) == n_rejects > 0
    for ln in rej:
        rec = json.loads(ln.partition(" ")[2])
        assert set(rec) == {"oid", "aid", "reason", "rej"}
        assert rec["rej"].startswith("rej_")


def test_audit_requires_journal():
    broker = InProcessBroker()
    provision(broker)
    with pytest.raises(ValueError, match="journal"):
        MatchService(broker, engine="oracle", compat="fixed",
                     audit=True)


# ---------------------------------------------------------------------------
# one arithmetic, two feeders (PR 51): a record array replayed as it is
# against the same records as event dicts


def _rows(evs):
    """Event dicts as the journal's record array holds them."""
    return EventBatch(np.frombuffer(
        b"".join(_encode(ev) for ev in evs), rec_dtype()).copy())


def _shadow(aud):
    return (aud.balances, aud.positions, aud.orders, aud.books,
            aud.inflow, aud._fills_hist, aud._depth_obs, aud._sid_sum,
            aud._unbalanced, aud._bal_total, aud._unbounded_credit,
            aud.batches)


# (event type, field, what is added to it) of the first such event
PLANTED = {
    "clean": None,
    "fill_qty": ("fill", "qty", 1),         # test_tampered_fill_qty_...
    "fill_qty2": ("fill", "qty", 2),        # test_repro_dump_replays_...
    "fill_px": ("fill", "px", 1),           # ..._balance_conjuring_...
    "fill_maker": ("fill", "moid", 10 ** 6),
    "fill_taker": ("fill", "oid", 10 ** 6),
    "rest_qty": ("rest", "qty", 3),
    "accept_aid": ("accept", "aid", 10 ** 6),
    "accept_book": ("accept", "sid", 10 ** 4),
    "cancel_aid": ("cancel", "aid", 1),
    "transfer": ("transfer", "qty", -10 ** 12),
    # these two on a stream with settlements
    "payout_clean": None,
    "payout_book": ("payout", "sid", 10 ** 4),
}


@pytest.mark.parametrize("fault", sorted(PLANTED))
def test_row_feeder_is_the_dict_feeder(fault, tmp_path):
    """The shadow and the violations — kind, detail, batch, seq — after
    every batch, fed as dicts and fed as an EventBatch's rows."""
    if fault.startswith("payout"):
        from kme_tpu.workload import zipf_symbol_stream

        evs = oracle_events([dumps_order(m) for m in zipf_symbol_stream(
            900, num_symbols=4, num_accounts=8, seed=4,
            payout_per_mille=30)])
        batches = [[dict(ev, b=lo // 90) for ev in evs
                    if lo <= ev["off"] < lo + 90]
                   for lo in range(0, 1000, 90)]
    else:
        _, batches = _event_batches(700, seed=23)
    seq = 0
    for evs in batches:
        for ev in evs:
            ev.update(seq=seq, ts=5, sh=0)
            seq += 1
    planted = PLANTED[fault]
    if planted is not None:
        e, field, by = planted
        ev = next(ev for evs in batches for ev in evs if ev["e"] == e)
        ev[field] += by
    by_dicts = InvariantAuditor(repro_dir=str(tmp_path / "d"))
    by_rows = InvariantAuditor(repro_dir=str(tmp_path / "r"))
    for evs in batches:
        by_dicts.observe(evs)
        by_rows.observe(_rows(evs))
        assert _shadow(by_rows) == _shadow(by_dicts)
    assert by_rows.violations == by_dicts.violations
    assert bool(by_dicts.violations) == (planted is not None)
    assert len(by_rows.dumps) == len(by_dicts.dumps)
    for a, b in zip(by_rows.dumps, by_dicts.dumps):
        # a dump's events are the dicts the records decode to
        doc = load_repro(b)
        doc["events"] = [_decode(_encode(ev)) for ev in doc["events"]]
        assert load_repro(a) == doc
        assert replay_repro(a) == replay_repro(b) != []


@pytest.mark.skipif(load_library() is None,
                    reason="native host runtime unavailable")
def test_repro_dump_from_a_buffer_is_the_one_from_lines(tmp_path):
    """A journal fed buffers hands its auditor EventBatches, one fed
    the same batches as lines hands dicts and the lines: a lying line
    (one taker fill a contract too large) gives the same violations and
    the same repro dump, inputs included, and both reproduce."""
    msgs = harness_stream(500, seed=21, num_accounts=8, num_symbols=3,
                          payout_opcode_bug=False, validate=True)
    eng = OracleEngine("fixed")
    groups = [[r.wire() for r in eng.process(m)] for m in msgs]
    g = next(g for g in groups[120:] if len(g) >= 4)
    key, _, val = g[2].partition(" ")
    tk = parse_order(val)
    tk.size += 1
    g[2] = f"{key} {dumps_order(tk)}"
    auds = []
    for name in ("buffers", "lines"):
        j = Journal(str(tmp_path / f"{name}.kmej"), clock=lambda: 9)
        aud = InvariantAuditor(repro_dir=str(tmp_path / name))
        j.observers.append(aud.observe)
        for lo in range(0, len(groups), 60):
            part = groups[lo:lo + 60]
            offs = list(range(lo, lo + len(part)))
            if name == "lines":
                j.record_batch(part, offsets=offs)
                continue
            flat = [ln for grp in part for ln in grp]
            j.record_buffer("".join(flat).encode(), line_offsets(flat),
                            np.array([len(grp) for grp in part], np.int32),
                            None, offs)
        j.close()
        assert j.native_batches == (name == "buffers") * -(-len(groups) // 60)
        auds.append(aud)
    rows, dicts = auds
    assert rows.violations == dicts.violations != []
    assert _shadow(rows) == _shadow(dicts)
    assert len(rows.dumps) == len(dicts.dumps) >= 1
    for a, b in zip(rows.dumps, dicts.dumps):
        assert load_repro(a) == load_repro(b)
        assert load_repro(a)["inputs"] and replay_repro(a)
