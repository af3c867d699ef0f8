"""Live N→M resharding (bridge/reshard.py + GroupRouter.reshard).

Pins the contracts the reshard-under-storm drill stands on, all
in-process so they run in tier-1 time:

- the plan is deterministic and rendezvous-minimal (growing 2→4 only
  moves keys onto NEW group ids — moved_key_frac);
- `partition_engines` + settlement legs + a resharded router reproduce
  the single-leader oracle byte-for-byte across the barrier
  (verify_groups_reshard);
- the coordinator journal makes every phase idempotent: a re-run after
  a mid-settle crash regenerates identical stamps and the broker
  watermark suppresses every leg that already landed;
- the old generation stays durably fenced (probe_fenced).
"""

import json
import os

import pytest

from kme_tpu.bridge import front, lease
from kme_tpu.bridge import reshard as rs
from kme_tpu.bridge.broker import InProcessBroker
from kme_tpu.oracle import OracleEngine
from kme_tpu.runtime import checkpoint as ck
from kme_tpu.wire import dumps_order, parse_order
from kme_tpu.workload import cross_account_stream

SLOTS, FILLS, PREFUND = 128, 16, 8


def _lines(events=600, symbols=128, accounts=32, n=2, seed=7,
           cross_frac=0.5):
    msgs = cross_account_stream(events, symbols, accounts, n, seed=seed,
                                cross_frac=cross_frac)
    return [dumps_order(m) for m in msgs]


def _run_group_engines(substreams):
    """Feed each substream through its own fixed-mode oracle; return
    (engines, per-group raw echo lines — internal echoes included)."""
    engines = [OracleEngine("fixed", SLOTS, FILLS) for _ in substreams]
    outs = []
    for eng, sub in zip(engines, substreams):
        out = []
        for ln in sub:
            out.extend(r.wire() for r in eng.process(parse_order(ln)))
        outs.append(out)
    return engines, outs


# -- plan --------------------------------------------------------------


def test_rendezvous_minimal_frac_values():
    assert rs.rendezvous_minimal_frac(2, 4) == pytest.approx(0.5)
    assert rs.rendezvous_minimal_frac(4, 2) == pytest.approx(0.5)
    assert rs.rendezvous_minimal_frac(1, 4) == pytest.approx(0.75)
    assert rs.rendezvous_minimal_frac(3, 3) == 0.0


def test_plan_reshard_deterministic_and_minimal():
    syms, accts = range(512), range(128)
    a = rs.plan_reshard(2, 4, syms, accts)
    b = rs.plan_reshard(2, 4, syms, accts)
    assert a == b
    # rendezvous superset property: growing 2→4, a key only ever moves
    # TO a new group id (2 or 3) — modulo hashing would scatter moves
    # across all four and inflate moved_key_frac toward 1
    for s in a["moved_symbols"]:
        assert front.symbol_group(s, 4) >= 2, s
    for acct in a["moved_accounts"]:
        assert front.account_group(acct, 4) >= 2, acct
    want = rs.rendezvous_minimal_frac(2, 4)
    assert abs(a["moved_key_frac"] - want) < 0.15
    assert a["rendezvous_minimal_frac"] == pytest.approx(want)


# -- state surgery parity ----------------------------------------------


def test_partition_engines_rejects_java_mode():
    with pytest.raises(ValueError):
        rs.partition_engines([OracleEngine("java", SLOTS, FILLS)], 4)


def test_settlement_legs_deterministic_and_dense():
    consolidation = {5: 100, 9: 0, 2: 7, 11: -3, 40: 250}
    legs = rs.settlement_legs(consolidation, 4)
    assert legs == rs.settlement_legs(consolidation, 4)
    # non-positive balances carry no leg
    assert {leg[3] for leg in legs} == {2, 5, 40}
    # out_seq is dense per group (replay-stable broker stamps)
    per = {}
    for g, seq, xid, _aid, amt, line in legs:
        assert seq == per.get(g, 0)
        per[g] = seq + 1
        assert xid >= rs.XID_BASE and amt > 0
        assert front.is_internal_line(line)


def test_reshard_parity_in_process():
    """The drill's surgery chain, no processes: N engines drain, state
    is partitioned to M engines, settlement legs land first, the SAME
    router re-routes the suffix — byte parity with the single oracle."""
    n, m = 2, 4
    lines = _lines(events=600, n=n)
    split_at = len(lines) // 2
    pre_sub, router = front.split_lines(lines[:split_at], n,
                                        prefund=PREFUND)
    old_engines, actual_pre = _run_group_engines(pre_sub)

    new_engines, consolidation = rs.partition_engines(old_engines, m)
    legs = rs.settlement_legs(consolidation, m)
    actual_post = [[] for _ in range(m)]
    for g, _seq, _xid, _aid, _amt, line in legs:
        actual_post[g].extend(
            r.wire()
            for r in new_engines[g].process(parse_order(line)))

    info = router.reshard(m)
    assert info["old_groups"] == n and info["new_groups"] == m
    for ln in lines[split_at:]:
        for g, routed in router.route_line(ln):
            actual_post[g].extend(
                r.wire()
                for r in new_engines[g].process(parse_order(routed)))

    rep = front.verify_groups_reshard(
        lines, split_at, actual_pre, actual_post, compat="fixed",
        book_slots=SLOTS, max_fills=FILLS, prefund=PREFUND)
    assert rep["ok"], rep["mismatches"][:2]
    # conservation: consolidated cash equals the sum of the drained
    # engines' balances (transfer legs cancel in the sum)
    assert sum(consolidation.values()) == sum(
        sum(e.balances.values()) for e in old_engines)


def test_router_reshard_is_deterministic():
    lines = _lines(events=400, n=2)
    split_at = 250

    def run():
        _, router = front.split_lines(lines[:split_at], 2,
                                      prefund=PREFUND)
        router.reshard(4)
        return [router.route_line(ln) for ln in lines[split_at:]]

    assert run() == run()


# -- coordinator journal -----------------------------------------------


def _seed_old_generation(root, n, lines):
    """Drained old generation on disk: per-group snapshot + broker log
    (what `--idle-exit` leaves behind, minus the serve)."""
    subs, _router = front.split_lines(lines, n, prefund=PREFUND)
    engines, outs = _run_group_engines(subs)
    for k, (eng, sub) in enumerate(zip(engines, subs)):
        gdir = os.path.join(root, f"group{k}")
        lease.acquire(gdir)     # the old leader's grant
        ck.save_oracle(gdir, eng, len(sub))
        b = InProcessBroker(
            persist_dir=os.path.join(gdir, "broker-log"))
        b.create_topic(f"MatchIn.g{k}")
        for i, ln in enumerate(sub):
            b.produce(f"MatchIn.g{k}", None, ln, out_seq=i)
        b.sync()
    return subs


def test_coordinator_idempotent_resume(tmp_path):
    n, m = 2, 4
    lines = _lines(events=300, n=n)
    old = str(tmp_path / "r0")
    new = str(tmp_path / "r1")
    _seed_old_generation(old, n, lines)

    coord = rs.ReshardCoordinator(old, new, n, m)
    j1 = coord.run()
    assert j1["done"] and j1["settle"]["legs"] > 0
    assert j1["settle"]["dup_suppressed"] == 0

    # crash-after-settle resume: wipe the settle phase from the journal
    # (as if the coordinator died before the fsync) — the re-run must
    # regenerate identical stamps and the broker must suppress ALL of
    # them, leaving the MatchIn logs byte-identical
    sizes = {k: InProcessBroker(persist_dir=os.path.join(
        new, f"group{k}", "broker-log")).end_offset(f"MatchIn.g{k}")
        for k in range(m)}
    with open(coord.journal_path, encoding="utf-8") as f:
        j = json.load(f)
    del j["settle"]
    del j["done"]
    with open(coord.journal_path, "w", encoding="utf-8") as f:
        json.dump(j, f)

    j2 = rs.ReshardCoordinator(old, new, n, m).run()
    assert j2["settle"]["legs"] == j1["settle"]["legs"]
    assert j2["settle"]["dup_suppressed"] == j1["settle"]["legs"]
    for k in range(m):
        b = InProcessBroker(persist_dir=os.path.join(
            new, f"group{k}", "broker-log"))
        assert b.end_offset(f"MatchIn.g{k}") == sizes[k]

    # every journaled leg line appears exactly once in its group's log
    for g, _seq, _xid, _aid, _amt, line in j2["migrate"]["legs"]:
        b = InProcessBroker(persist_dir=os.path.join(
            new, f"group{g}", "broker-log"))
        recs = b.fetch(f"MatchIn.g{g}", 0, 10_000)
        assert sum(1 for r in recs if r.value == line) == 1


def test_coordinator_refuses_topology_mismatch(tmp_path):
    n = 2
    lines = _lines(events=200, n=n)
    old = str(tmp_path / "r0")
    new = str(tmp_path / "r1")
    _seed_old_generation(old, n, lines)
    rs.ReshardCoordinator(old, new, n, 4).run()
    with pytest.raises(ValueError, match="different reshard"):
        rs.ReshardCoordinator(old, new, n, 8).run()


def test_old_generation_stays_fenced(tmp_path):
    n = 2
    lines = _lines(events=200, n=n)
    old = str(tmp_path / "r0")
    new = str(tmp_path / "r1")
    _seed_old_generation(old, n, lines)
    g0 = os.path.join(old, "group0")
    # before the reshard: no tombstone, probe reports unfenced
    assert rs.probe_fenced(g0) is False
    rs.ReshardCoordinator(old, new, n, 4).run()
    for k in range(n):
        gdir = os.path.join(old, f"group{k}")
        stolen = lease.current_epoch(gdir)
        assert rs.probe_fenced(gdir, epoch=stolen - 1) is True
    # the new generation's first leader acquires strictly above the
    # coordinator's settle epoch
    for k in range(4):
        gdir = os.path.join(new, f"group{k}")
        assert lease.current_epoch(gdir) >= 1
        assert lease.acquire(gdir) >= 2


def test_coordinator_needs_drained_snapshots(tmp_path):
    old = str(tmp_path / "r0")
    os.makedirs(os.path.join(old, "group0"))
    os.makedirs(os.path.join(old, "group1"))
    coord = rs.ReshardCoordinator(old, str(tmp_path / "r1"), 2, 4)
    with pytest.raises(ValueError, match="drained"):
        coord.run()
