"""Order-lifecycle flight recorder (kme_tpu/telemetry/journal.py):
framing round-trips, oracle-replay agreement, rotation, torn-tail
resume, at-least-once rewind, pipeline-window math and lifecycle
reconstruction."""

import json
import os

from kme_tpu.oracle import OracleEngine
from kme_tpu.telemetry.journal import (MAGIC, REC_SIZE, Journal,
                                       account_history, batch_events,
                                       canonical_lines, iter_events,
                                       lifecycle_summary,
                                       measured_overlap_s,
                                       oracle_events, order_lifecycle,
                                       read_events)
from kme_tpu.wire import REJ_MALFORMED, dumps_order, parse_order
from kme_tpu.workload import harness_stream


def _wire_groups(n=300, seed=11):
    """Input lines + the oracle's per-message wire line groups — the
    same shape the sessions hand the journal."""
    msgs = harness_stream(n, seed=seed, num_accounts=6, num_symbols=2,
                          payout_opcode_bug=False, validate=True)
    lines = [dumps_order(m) for m in msgs]
    eng = OracleEngine("fixed")
    groups = [[r.wire() for r in eng.process(parse_order(ln))]
              for ln in lines]
    return lines, groups


def _fill_journal(path, groups, chunk=100, **kw):
    j = Journal(path, clock=lambda: 1_000_000, **kw)
    for lo in range(0, len(groups), chunk):
        part = groups[lo:lo + chunk]
        j.record_batch(part, offsets=list(range(lo, lo + len(part))))
    j.close()
    return j


# ---------------------------------------------------------------------------
# derivation + framing


def test_journal_matches_independent_oracle_replay(tmp_path):
    lines, groups = _wire_groups()
    for name in ("j.jsonl", "j.bin"):
        path = str(tmp_path / name)
        _fill_journal(path, groups)
        got = canonical_lines(read_events(path))
        want = canonical_lines(oracle_events(lines))
        assert got == want and len(got) > len(lines)


def test_binary_and_jsonl_decode_identically(tmp_path):
    _, groups = _wire_groups()
    jp, bp = str(tmp_path / "j.jsonl"), str(tmp_path / "j.bin")
    _fill_journal(jp, groups)
    _fill_journal(bp, groups)
    assert open(bp, "rb").read(len(MAGIC)) == MAGIC
    ev_j, ev_b = read_events(jp), read_events(bp)
    assert ev_j == ev_b                 # full dicts, stamps included
    body = os.path.getsize(bp) - len(MAGIC)
    assert body == len(ev_b) * REC_SIZE


def test_event_order_and_stamps(tmp_path):
    _, groups = _wire_groups()
    path = str(tmp_path / "j.jsonl")
    _fill_journal(path, groups, chunk=50)
    evs = read_events(path)
    seqs = [e["seq"] for e in evs]
    assert seqs == list(range(len(evs)))  # dense + monotonic
    assert all(e["ts"] == 1_000_000 and e["sh"] == 0 for e in evs)
    batches = [e["b"] for e in evs]
    assert batches == sorted(batches)
    # per accepted trade: accept precedes its fills precedes any rest
    by_slot = {}
    for e in evs:
        if e["b"] == 0:
            by_slot.setdefault(e["i"], []).append(e["e"])
    for kinds in by_slot.values():
        assert kinds[0] == "submit"
        if "fill" in kinds:
            assert kinds.index("accept") < kinds.index("fill")
        if "rest" in kinds:
            assert kinds.index("rest") == len(kinds) - 1


def test_drop_and_reject_events():
    lines, _ = _wire_groups(80)
    lines.insert(3, "{not json")
    lines.insert(7, '{"action":2,"oid":1,"aid":1,"sid":0,'
                    '"price":99999999999,"size":1,"next":null,'
                    '"prev":null}')   # price outside int32 -> drop
    evs = oracle_events(lines)
    drops = [e for e in evs if e["e"] == "drop"]
    assert [d["off"] for d in drops] == [3, 7]
    assert all(d["rej"] == REJ_MALFORMED for d in drops)
    rejs = [e for e in evs if e["e"] == "reject"]
    assert rejs and all(e["rej"] > 0 for e in rejs)


def test_window_records_roundtrip(tmp_path):
    for name in ("w.jsonl", "w.bin"):
        path = str(tmp_path / name)
        j = Journal(path, clock=lambda: 5)
        j.record_window("submit", 1.0, 2.5, batch=0)
        j.record_window("collect", 2.5, 3.0, batch=0)
        j.close()
        evs = read_events(path)
        assert [e["e"] for e in evs] == ["win", "win"]
        assert evs[0]["kind"] == "submit"
        assert (evs[0]["t0"], evs[0]["t1"]) == (1_000_000, 2_500_000)
        assert evs[1]["kind"] == "collect"
        # windows are provenance-only: canonical comparison drops them
        assert canonical_lines(evs) == []


# ---------------------------------------------------------------------------
# durability behaviors


def test_rotation_shifts_and_reads_in_order(tmp_path):
    _, groups = _wire_groups(200)
    path = str(tmp_path / "r.jsonl")
    _fill_journal(path, groups, chunk=20, rotate_bytes=4096)
    assert os.path.exists(path + ".1")   # rotated at least once
    evs = read_events(path)
    seqs = [e["seq"] for e in evs]
    assert seqs == list(range(len(evs)))
    live_only = read_events(path, include_rotated=False)
    assert len(live_only) < len(evs)
    assert canonical_lines(evs) == canonical_lines(
        oracle_events([ln for ln in _wire_groups(200)[0]]))


def test_resume_continues_seq_after_torn_tail(tmp_path):
    _, groups = _wire_groups(120)
    for name, torn in (("t.jsonl", b'{"e":"subm'),
                       ("t.bin", b"\x01\x02\x03garbage")):
        path = str(tmp_path / name)
        _fill_journal(path, groups[:60])
        n0 = len(read_events(path))
        top = read_events(path)[-1]["seq"]
        with open(path, "ab") as f:
            f.write(torn)               # crash mid-record
        assert len(read_events(path)) == n0   # reader ignores the tear
        j = Journal(path, clock=lambda: 7)    # resume truncates it
        assert j.next_seq == top + 1
        j.record_batch(groups[60:70],
                       offsets=list(range(60, 70)))
        j.close()
        evs = read_events(path)
        seqs = [e["seq"] for e in evs]
        assert seqs == list(range(len(evs)))  # still dense


def test_rewind_to_offset_dedups_replay(tmp_path):
    _, groups = _wire_groups(100)
    for name in ("rw.jsonl", "rw.bin"):
        path = str(tmp_path / name)
        _fill_journal(path, groups, chunk=25)
        j = Journal(path, clock=lambda: 9)
        j.record_window("submit", 0.0, 1.0)   # off == -1: must survive
        j.rewind_to_offset(50)
        # replay the tail, as the service does after a snapshot resume
        j.record_batch(groups[50:75], offsets=list(range(50, 75)))
        j.record_batch(groups[75:100], offsets=list(range(75, 100)))
        j.close()
        evs = read_events(path)
        offs = [e["off"] for e in evs if e["e"] == "submit"]
        assert offs == list(range(100))       # exactly once each
        assert any(e["e"] == "win" for e in evs)
        seqs = [e["seq"] for e in evs]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


def test_async_writer_preserves_order(tmp_path):
    _, groups = _wire_groups(150)
    path = str(tmp_path / "a.jsonl")
    j = Journal(path, async_write=True, clock=lambda: 1)
    seen = []
    j.observers.append(lambda evs, lines: seen.extend(evs))
    for lo in range(0, len(groups), 30):
        j.record_batch(groups[lo:lo + 30],
                       offsets=list(range(lo, lo + 30)))
    j.flush()
    j.close()
    evs = read_events(path)
    assert [e["seq"] for e in evs] == list(range(len(evs)))
    assert seen == evs                   # observers see committed form
    assert canonical_lines(evs) == canonical_lines(
        batch_events(groups, offsets=list(range(len(groups)))))


def test_fsync_batch_mode_writes_through(tmp_path):
    _, groups = _wire_groups(40)
    path = str(tmp_path / "f.jsonl")
    j = Journal(path, fsync="batch", clock=lambda: 1)
    j.record_batch(groups, offsets=list(range(len(groups))))
    # no close(): batch fsync means the bytes are already durable
    assert len(read_events(path)) > len(groups)
    j.close()


# ---------------------------------------------------------------------------
# pipeline-window math (measured_overlap_s)


def test_measured_overlap_full_and_none():
    # double-buffered: collect(0) runs entirely while batch 1 is
    # submitted-but-not-collected -> the whole window counts
    over = measured_overlap_s([
        ("submit", 0, 0.0, 1.0), ("submit", 1, 1.0, 2.0),
        ("collect", 0, 3.0, 4.0), ("collect", 1, 5.0, 6.0)])
    assert abs(over - 1.0) < 1e-9
    # strictly serial: nothing in flight during any collect
    assert measured_overlap_s([
        ("submit", 0, 0.0, 1.0), ("collect", 0, 1.0, 2.0),
        ("submit", 1, 2.0, 3.0), ("collect", 1, 3.0, 4.0)]) == 0.0
    # partial cover is clipped to the intersection: batch 1 is in
    # flight over [2.0, 2.5], which collect(0)'s [1.5, 3.0] overlaps
    # for 0.5s; nothing is in flight during collect(1)
    over = measured_overlap_s([
        ("submit", 0, 0.0, 1.0), ("submit", 1, 1.0, 2.0),
        ("collect", 0, 1.5, 3.0), ("collect", 1, 2.5, 4.0)])
    assert abs(over - 0.5) < 1e-9


# ---------------------------------------------------------------------------
# lifecycle reconstruction (what kme-trace prints)


def test_order_lifecycle_and_summary(tmp_path):
    lines, groups = _wire_groups(400, seed=5)
    evs = batch_events(groups, offsets=list(range(len(groups))))
    fills = [e for e in evs if e["e"] == "fill"]
    assert fills
    taker = fills[0]["oid"]
    life = order_lifecycle(evs, taker)
    assert [e["e"] for e in life][:2] == ["submit", "accept"]
    assert any(e["e"] == "fill" for e in life)
    summ = lifecycle_summary(life, taker)
    assert summ["oid"] == taker and summ["filled"] > 0
    assert summ["state"] in ("filled", "accepted", "resting")
    # maker-side: the resting order's lifecycle includes the same fill
    maker = fills[0]["moid"]
    mlife = order_lifecycle(evs, maker)
    assert any(e["e"] == "fill" and e.get("moid") == maker
               for e in mlife)
    # account view covers both sides of its fills
    hist = account_history(evs, fills[0]["maid"])
    assert any(e["e"] == "fill" for e in hist)


def test_iter_events_plain_jsonl_without_stamps(tmp_path):
    # a journal written by other tooling (no seq stamps) still parses
    path = str(tmp_path / "x.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"e": "submit", "oid": 1}) + "\n")
        f.write('{"e":"accept","oid":1}')   # torn final line: ignored
    assert list(iter_events(path)) == [{"e": "submit", "oid": 1}]


# ---------------------------------------------------------------------------
# retention: rotate_keep bounded by the snapshot retention guard


def _segments(path):
    n = 1
    while os.path.exists(f"{path}.{n}"):
        n += 1
    return n - 1


def test_rotate_keep_prunes_old_segments(tmp_path):
    _, groups = _wire_groups()
    free = str(tmp_path / "free.jsonl")
    _fill_journal(free, groups, chunk=20, rotate_bytes=2048)
    assert _segments(free) >= 3                # enough history to prune

    kept = str(tmp_path / "kept.jsonl")
    _fill_journal(kept, groups, chunk=20, rotate_bytes=2048,
                  rotate_keep=2)
    assert _segments(kept) == 2
    # the live file plus the kept segments still replay contiguously
    # from SOME offset — the newest events are never the ones pruned
    offs = [ev["off"] for ev in read_events(kept) if "off" in ev]
    assert offs == sorted(offs)
    assert max(offs) == max(ev["off"] for ev in read_events(free)
                            if "off" in ev)


def test_retention_guard_blocks_pruning_of_replayable_segments(tmp_path):
    """The journal/snapshot retention coupling: a rotated segment may
    only be dropped once every event in it is older than the OLDEST
    retained snapshot — a standby restoring that snapshot must still
    be able to replay to the tip."""
    _, groups = _wire_groups()

    # guard pinned at offset 0 (oldest snapshot never pruned): every
    # segment is still replayable, rotate_keep must be overridden
    p = str(tmp_path / "pinned.jsonl")
    _fill_journal(p, groups, chunk=20, rotate_bytes=2048,
                  rotate_keep=1, retention_guard=lambda: 0)
    assert _segments(p) > 1

    # guard beyond the tip: nothing is needed, rotate_keep rules
    t = str(tmp_path / "tip.jsonl")
    _fill_journal(t, groups, chunk=20, rotate_bytes=2048,
                  rotate_keep=1, retention_guard=lambda: 10 ** 9)
    assert _segments(t) == 1

    # fail-safe: a guard that errors, or reports no snapshot at all,
    # keeps everything
    e = str(tmp_path / "err.jsonl")
    _fill_journal(e, groups, chunk=20, rotate_bytes=2048,
                  rotate_keep=1,
                  retention_guard=lambda: (_ for _ in ()).throw(OSError()))
    assert _segments(e) > 1
    n = str(tmp_path / "none.jsonl")
    _fill_journal(n, groups, chunk=20, rotate_bytes=2048,
                  rotate_keep=1, retention_guard=lambda: None)
    assert _segments(n) > 1


def test_retention_guard_wires_to_snapshot_offsets(tmp_path):
    """With the REAL guard (checkpoint.oldest_retained_offset): an old
    snapshot on disk holds every segment; once only a late snapshot
    remains, history behind it becomes prunable."""
    from kme_tpu.oracle import OracleEngine
    from kme_tpu.runtime import checkpoint as ck

    _, groups = _wire_groups()
    ckd = str(tmp_path / "ck")
    guard = lambda: ck.oldest_retained_offset(ckd)

    ora = OracleEngine("fixed")
    ck.save_oracle(ckd, ora, 0)                # snapshot at the start
    held = str(tmp_path / "held.jsonl")
    _fill_journal(held, groups, chunk=20, rotate_bytes=2048,
                  rotate_keep=1, retention_guard=guard)
    assert _segments(held) > 1                 # replay from 0 intact

    ck.save_oracle(ckd, ora, 10 ** 6, keep=1)  # prunes the 0 snapshot
    late = str(tmp_path / "late.jsonl")
    _fill_journal(late, groups, chunk=20, rotate_bytes=2048,
                  rotate_keep=1, retention_guard=guard)
    assert _segments(late) == 1
