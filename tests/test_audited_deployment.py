"""The exchange as a regulated venue runs it (PR 50): `--engine seq
--compat fixed` with the flight recorder, the shadow-ledger auditor and
snapshots on, serial and `--pipeline 2`, small and on the CPU.

What must hold:

- (a) served through `MatchService` with a journal (fsynced per batch)
  and the auditor on, `MatchOut` is `NativeOracleEngine`'s byte for
  byte, the journal's canonical events are `oracle_events`' of the same
  input, the auditor saw every batch, found nothing, and the heartbeat
  is not degraded;
- (b) the auditor's snapshot-cadence compare reads the live entries the
  snapshot fetched (`SeqSession.export_live`): at every snapshot that is
  the dict `export_state()` gives, books and positions sparse
  (pipelined run, 1,024 slots) or dense (serial run, 128 slots), and
  after a fault planted in the device state — a flipped position
  amount, a resized resting order, a dropped order, a changed balance —
  both forms give the same violation kinds and details; the served path
  of a fixed-mode `SeqSession` never calls `_canon_to_export`;
- (c) `KME_AUDIT_TAMPER=fill_qty` trips on the seq engine and its repro
  replays;
- (d) the running conservation sums give the full pass's verdicts on
  random streams with payouts, removals and planted faults;
- (e) a leader resumed from a snapshot taken with the auditor on
  re-seeds the shadow and audits clean to the end (the pipelined run is
  stopped and resumed in the middle)."""

import numpy as np
import pytest

from kme_tpu.bridge.broker import InProcessBroker
from kme_tpu.bridge.consume import consume_lines
from kme_tpu.bridge.provision import provision
from kme_tpu.bridge.service import TOPIC_IN, MatchService
from kme_tpu.native import load_library
from kme_tpu.native.oracle import NativeOracleEngine
from kme_tpu.telemetry.audit import InvariantAuditor, replay_repro
from kme_tpu.telemetry.journal import (canonical_lines, oracle_events,
                                       read_events)
from kme_tpu.wire import dumps_order
from kme_tpu.workload import harness_stream, zipf_symbol_stream

SYMBOLS, ACCOUNTS, EVENTS, FILLS, BATCH = 24, 96, 3000, 16, 128
# slots of the two served runs: at 1,024 the books' live rows are under
# a quarter of a plane's and cross by the row program, at 128 they cross
# whole; the positions follow their own occupancy
RUNS = {"pipelined": dict(pipeline=2, slots=1024),
        "serial": dict(pipeline=0, slots=128)}

pytestmark = pytest.mark.skipif(
    load_library() is None,
    reason="native host runtime unavailable (KME_NATIVE=0 or no "
           "toolchain)")


def stream(events=EVENTS, seed=11):
    return zipf_symbol_stream(events, SYMBOLS, ACCOUNTS, seed=seed,
                              payout_per_mille=4)


def service(broker, tmp, pipeline, slots, **kw):
    return MatchService(
        broker, engine="seq", compat="fixed", batch=BATCH,
        symbols=SYMBOLS, accounts=ACCOUNTS, slots=slots, max_fills=FILLS,
        pipeline=pipeline, exactly_once=True,
        checkpoint_dir=str(tmp / "state"), checkpoint_every=512,
        # a directory that is not there yet: Journal makes it
        journal=str(tmp / "state" / "planes" / "journal.kmej"),
        journal_fsync="batch", audit=True,
        audit_repro_dir=str(tmp / "state" / "repro"), **kw)


def as_live(state):
    """export_state()'s dict with an order as export_live gives it."""
    return dict(state, orders={
        o: (v["aid"], v["sid"], v["is_buy"], v["price"], v["size"])
        for o, v in state["orders"].items()})


def watched(svc, seen):
    """Every snapshot-cadence compare of `svc` also builds the old form
    and notes whether the two dicts are one."""
    check = svc._audit_check_engine

    def both(fetched):
        assert len(fetched) == 2, "the snapshot handed nothing over"
        canon, layout = fetched
        seen.append((svc._session.export_live(canon, layout)
                     == as_live(svc._session.export_state()),
                     tuple(layout["sparse"])))
        check(fetched)
    svc._audit_check_engine = both


@pytest.fixture(scope="module", params=sorted(RUNS))
def served(request, tmp_path_factory):
    """One stream served to its end with every plane on; the pipelined
    run is stopped after 12 batches and resumed by a second leader."""
    from kme_tpu.runtime.seqsession import SeqSession

    how, tmp = RUNS[request.param], tmp_path_factory.mktemp(request.param)
    msgs = stream()
    eng = NativeOracleEngine("fixed", book_slots=how["slots"],
                             max_fills=FILLS)
    want = [ln for g in eng.process_wire([m.copy() for m in msgs])
            for ln in g]
    broker = InProcessBroker()
    provision(broker)
    for m in msgs:
        broker.produce(TOPIC_IN, None, dumps_order(m))
    seen, dense_walks, batches = [], [], 0
    canon_to_export = SeqSession._canon_to_export
    mp = pytest.MonkeyPatch()
    svc = service(broker, tmp, **how)
    if request.param == "pipelined":
        watched(svc, seen)
        cut = 12 * BATCH
        assert svc.run(max_messages=cut) == cut
        svc.close()
        assert svc.auditor.violations == [] and svc.degraded is None
        batches = svc.telemetry.counter("service_batches").value
        assert svc.telemetry.counter("audit_batches").value == batches
        svc = service(broker, tmp, **how)      # seeds its shadow
        assert 0 < svc.offset <= cut and svc.epoch == 2
        assert svc.auditor.positions and svc.auditor.orders
        watched(svc, seen)
    else:
        # no resume here: whatever walks the dense planes is the
        # served path's doing
        mp.setattr(SeqSession, "_canon_to_export",
                   lambda self, canon: dense_walks.append(1)
                   or canon_to_export(self, canon))
    try:
        left = len(msgs) - svc.offset
        assert svc.run(max_messages=left) == left
        svc.checkpoint()
    finally:
        mp.undo()
    svc.close()
    return dict(svc=svc, broker=broker, msgs=msgs, want=want, seen=seen,
                dense_walks=dense_walks, how=how, tmp=tmp,
                journal=str(tmp / "state" / "planes" / "journal.kmej"))


def test_matchout_is_the_references_bytes(served):
    assert list(consume_lines(served["broker"], follow=False)) \
        == served["want"]


def test_journal_is_the_oracles_events(served):
    lines = [dumps_order(m) for m in served["msgs"]]
    want = canonical_lines(oracle_events(
        lines, book_slots=served["how"]["slots"], max_fills=FILLS))
    got = canonical_lines(read_events(served["journal"]))
    assert len(got) > 2 * len(lines) and got == want


def test_auditor_saw_every_batch_and_found_nothing(served):
    svc = served["svc"]
    t = svc.telemetry
    assert svc.auditor.violations == [] and svc.degraded is None
    assert svc.auditor.dumps == []
    batches = t.counter("service_batches").value
    assert batches >= (len(served["msgs"]) - 12 * BATCH) // BATCH
    assert t.counter("audit_batches").value == batches
    assert t.counter("audit_violations").value == 0
    gauges = t.snapshot()["gauges"]
    checks = gauges["audit_check_engine_n"]
    assert checks >= 2
    # balances, books, live positions, resting orders: all of them, at
    # every snapshot
    assert t.counter("audit_entries_compared").value \
        > checks * (ACCOUNTS + SYMBOLS)
    assert gauges["audit_shadow_positions"] \
        == len(svc.auditor.positions) > 100
    assert gauges["journal_record_n"] == gauges["audit_observe_n"] \
        == batches
    # a batch's lifecycle events and its latency stamps: two commits
    assert gauges["journal_write_n"] == 2 * batches
    assert t.counter("journal_bytes").value \
        == 96 * t.counter("journal_events").value > 0
    if served["how"]["pipeline"]:
        assert gauges["journal_lines_n"] == batches
    # a pipelined batch's records are made once, by the native walk
    # over its buffer; the serial path hands the journal lines
    assert t.counter("journal_native_batches").value \
        == (batches if served["how"]["pipeline"] else 0)


def test_rows_and_dicts_replay_the_served_journal_alike(served):
    """The journal as served, batch by batch, into a fresh auditor as
    the file's own records (the row feeder: what the pipelined leader's
    auditor was fed) and as the event dicts they decode to (the dict
    feeder: the serial leader's): one shadow, the served auditor's."""
    from kme_tpu.telemetry.journal import (ETYPES, MAGIC, EventBatch,
                                           rec_dtype)

    rows = np.fromfile(served["journal"], rec_dtype(), offset=len(MAGIC))
    evs = read_events(served["journal"])
    assert len(rows) == len(evs) > 0
    life = rows["etype"] < ETYPES.index("win")
    cuts = np.flatnonzero(np.diff(rows["b"]) | np.diff(life)) + 1
    by_rows, by_dicts = InvariantAuditor(), InvariantAuditor()
    for lo, hi in zip([0, *cuts], [*cuts, len(rows)]):
        by_rows.observe(EventBatch(rows[lo:hi]))
        by_dicts.observe(evs[lo:hi])
    assert by_rows.batches == by_dicts.batches >= len(served["msgs"]) // BATCH
    assert by_rows.violations == by_dicts.violations == []
    for name in ("balances", "positions", "orders", "books", "inflow",
                 "_fills_hist", "_depth_obs", "_sid_sum", "_bal_total"):
        assert getattr(by_rows, name) == getattr(by_dicts, name), name
    live = served["svc"].auditor
    assert (by_rows.balances, by_rows.positions, by_rows.orders) \
        == (live.balances, live.positions, live.orders)


def test_compare_reads_what_the_snapshot_fetched(served):
    if served["how"]["pipeline"]:
        # every snapshot of both leaders: the two forms are one dict,
        # and the books crossed by their live rows
        assert len(served["seen"]) >= 5
        assert all(same for same, _ in served["seen"])
        assert all("books" in sparse for _, sparse in served["seen"])
    else:
        # the served path never walked the dense planes
        assert served["dense_walks"] == []
        ses = served["svc"]._session
        assert ses.snapshot_gauges["snapshot_fetch_calls"] == 0


def plant(ses, fault):
    """One fault in the device state; -> the state to put back."""
    import jax.numpy as jnp

    from kme_tpu.engine import seq as SQ

    clean = ses.state
    if fault == "balance":
        k, at = "bal_lo", (0, 5)
    elif fault == "position":
        # amount, low word, of the first live position (value plane 0)
        words = np.asarray(clean["pos"]).reshape(-1, 4, SQ.LN)
        t, c = np.argwhere(words[:, 0, :] != 0)[0]
        k, at = "pos", (4 * t, c)
    else:
        k = "bs"
        at = tuple(np.argwhere(np.asarray(clean[k]) > 0)[0])
    plane = np.array(clean[k])
    plane[at] = 0 if fault == "dropped" else plane[at] + 1
    ses.state = {**clean, k: jnp.asarray(plane)}
    return clean


@pytest.mark.parametrize("fault", ["position", "resized", "dropped",
                                   "balance"])
def test_planted_fault_reads_alike_in_both_forms(fault, served):
    from kme_tpu.engine import seq as SQ

    ses = served["svc"]._session
    aud = InvariantAuditor()
    aud.seed(ses.export_state(), ses.histograms())
    assert aud.check_engine(ses.export_state(), ses.histograms()) == []
    clean = plant(ses, fault)
    try:
        canon, layout, _fetch = SQ.export_snapshot(ses.cfg, ses.state)
        live = aud.check_engine(ses.export_live(canon, layout),
                                ses.histograms())
        old = aud.check_engine(ses.export_state(), ses.histograms())
    finally:
        ses.state = clean
    assert [v["kind"] for v in live] == ["state_mismatch"]
    section = {"position": "positions", "balance": "balances"}.get(
        fault, "orders")
    assert live[0]["detail"].startswith(f"{section} differ: ")
    assert [(v["kind"], v["detail"]) for v in live] \
        == [(v["kind"], v["detail"]) for v in old]


@pytest.mark.parametrize("drill", ["fill_qty", "journal_fill_qty"])
def test_tampered_fill_trips_on_the_seq_engine(drill, tmp_path, monkeypatch):
    """The auditor's feed tampered (`fill_qty`) or the journal's lines
    (`journal_fill_qty`: the one thing that takes a pipelined batch off
    the native walk)."""
    monkeypatch.setenv("KME_AUDIT_TAMPER", drill)
    msgs = stream(events=600)
    broker = InProcessBroker()
    provision(broker)
    for m in msgs:
        broker.produce(TOPIC_IN, None, dumps_order(m))
    svc = service(broker, tmp_path, **RUNS["pipelined"])
    assert svc.run(max_messages=len(msgs)) == len(msgs)
    svc.close()
    assert svc.auditor.violations and svc.degraded is not None
    assert svc.telemetry.counter("audit_violations").value > 0
    assert svc.auditor.dumps
    assert svc.auditor.dumps[0].startswith(str(tmp_path / "state" / "repro"))
    assert replay_repro(svc.auditor.dumps[0])
    t = svc.telemetry
    assert t.counter("journal_native_batches").value == (
        t.counter("service_batches").value if drill == "fill_qty" else 0)
    # MatchOut is untouched: the tamper is in the auditor's feed
    eng = NativeOracleEngine("fixed", book_slots=1024, max_fills=FILLS)
    assert list(consume_lines(broker, follow=False)) == [
        ln for g in eng.process_wire(msgs) for ln in g]


def test_a_watch_leaves_the_journal_its_buffer(tmp_path):
    """`--watch` reads lines it makes itself: the journal of a watched
    pipelined leader still takes every batch as its buffer, and the
    watch fires what it fires on the oracle's leader."""
    exprs = ["depth[1]>=2", "position[2,1]>0"]
    msgs = stream(events=600)
    hits = []
    for seq in (True, False):
        broker = InProcessBroker()
        provision(broker)
        for m in msgs:
            broker.produce(TOPIC_IN, None, dumps_order(m))
        svc = (service(broker, tmp_path, watch=exprs, **RUNS["pipelined"])
               if seq else MatchService(broker, engine="oracle",
                                        compat="fixed", batch=BATCH,
                                        watch=exprs))
        assert svc.run(max_messages=len(msgs)) == len(msgs)
        svc.close()
        hits.append(list(svc.watch.hits))
        if seq:
            t = svc.telemetry
            assert t.counter("journal_native_batches").value \
                == t.counter("service_batches").value > 0
    assert hits[0] == hits[1] != []


class BothPasses(InvariantAuditor):
    """Every batch's conservation verdict by the running sums and by
    the full pass."""

    def _batch_checks(self, out, batch):
        full = []
        self._batch_checks_full(full, batch)
        mark = len(out)
        super()._batch_checks(out, batch)
        self.compared = getattr(self, "compared", 0) + 1
        assert out[mark:] == full, (batch, out[mark:], full)


@pytest.mark.parametrize("seed", [3, 6, 5, 2 ** 31 + 9])
@pytest.mark.parametrize("kind", ["zipf-payouts", "harness-removals"])
def test_running_sums_are_the_full_pass(kind, seed):
    if kind == "zipf-payouts":
        msgs = zipf_symbol_stream(1200, 5, 12, seed=seed,
                                  payout_per_mille=25)
    else:
        msgs = harness_stream(1200, seed=seed, num_accounts=8,
                              num_symbols=3, payout_opcode_bug=False,
                              validate=True)
    evs = oracle_events([dumps_order(m) for m in msgs])
    assert any(e["e"] in ("payout", "remove_symbol") for e in evs)
    # planted faults, so that the verdicts are not all empty (odd
    # seeds): the shadow starts with a position nobody holds the other
    # side of - its symbol's amounts do not sum to zero until it is
    # settled - and one fill pays a price 40 off the maker's
    aud = BothPasses()
    if seed % 2:
        aud.seed({"balances": {}, "positions": {(10 ** 6, 1): (5, 5)},
                  "orders": {}, "books": {}})
        fills = [e for e in evs if e["e"] == "fill"]
        fills[len(fills) // 3]["px"] += 40
    rng = np.random.default_rng(seed)
    lo = 0
    while lo < len(msgs):
        n = int(rng.integers(1, 90))
        aud.observe([e for e in evs if lo <= e.get("off", -1) < lo + n])
        lo += n
    assert aud.compared > 10
    kinds = {v["kind"] for v in aud.violations}
    assert ("position_conservation" in kinds) == bool(seed % 2)
    # a seeded copy starts from the same sums
    twin = BothPasses()
    twin.seed({"balances": aud.balances, "positions": aud.positions,
               "orders": {}, "books": {}})
    assert {s: t for s, t in twin._sid_sum.items() if t} \
        == {s: t for s, t in aud._sid_sum.items() if t}
    assert twin._unbalanced == aud._unbalanced
    assert twin._bal_total == aud._bal_total
