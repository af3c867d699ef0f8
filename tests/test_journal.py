"""Order-lifecycle flight recorder (kme_tpu/telemetry/journal.py):
framing round-trips, oracle-replay agreement, rotation, torn-tail
resume, at-least-once rewind, pipeline-window math and lifecycle
reconstruction."""

import json
import os

import numpy as np
import pytest

from kme_tpu import opcodes as op
from kme_tpu.bridge.broker import line_offsets
from kme_tpu.native import load_library
from kme_tpu.oracle import OracleEngine
from kme_tpu.telemetry.journal import (ETYPES, MAGIC, REC_SIZE, EventBatch,
                                       Journal, _decode, _encode,
                                       account_history, batch_events,
                                       buffer_rows, canonical_lines,
                                       iter_events, lifecycle_summary,
                                       measured_overlap_s,
                                       oracle_events, order_lifecycle,
                                       read_events, rec_dtype)
from kme_tpu.telemetry.trace import PhaseTimer
from kme_tpu.wire import (REJ_MALFORMED, OrderMsg, dumps_order, order_json,
                          parse_order)
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


# ---------------------------------------------------------------------------
# a collected batch as one buffer: the records made once, as an array
# (Journal.record_buffer, record_latency_columns — PR 51)


def _buffer(groups):
    """Line groups as `session.collect` hands a batch over: the lines
    back to back, n + 1 offsets, lines per message."""
    flat = [ln for g in groups for ln in g]
    return ("".join(flat).encode("ascii"), line_offsets(flat),
            np.array([len(g) for g in groups], np.int32))


def _oracle_groups(msgs, **kw):
    eng = OracleEngine("fixed", **kw)
    return [[r.wire() for r in eng.process(m)] for m in msgs]


def _lifecycle_groups(seed):
    """The oracle's groups over every kind of message: the harness
    stream (creates, transfers, trades, cancels, payouts), then a
    listing with a book three makers deep, one taker through all of
    them that rests its residual (its echo names its `prev`),
    `next`/`prev` set and null on the way in, a payout with a negative sid, a second listing removed,
    and rejects of each action."""
    msgs = harness_stream(250, seed=seed, num_accounts=6, num_symbols=2,
                          payout_opcode_bug=False, validate=True)
    M = OrderMsg
    tail = [M(op.CREATE_BALANCE, 0, 901), M(op.CREATE_BALANCE, 0, 902),
            M(op.TRANSFER, 0, 901, 0, 0, 10 ** 6),
            M(op.TRANSFER, 0, 902, 0, 0, 10 ** 6),
            M(op.ADD_SYMBOL, 0, 0, 77), M(op.ADD_SYMBOL, 0, 0, 78),
            M(op.ADD_SYMBOL, 0, 0, 77)]                 # reject: listed
    # (the engine links resting orders through next/prev: only a
    # message that never rests may bring its own)
    tail += [M(op.SELL, 9000 + k, 901, 77, 40 + k, 5) for k in range(3)]
    tail += [M(op.BUY, 9010, 902, 77, 60, 20),
             M(op.CANCEL, 9010, 902, 77, next=7, prev=-(2 ** 31)),
             M(op.CANCEL, 9010, 902, 77),
             M(op.BUY, 9011, 902, 79, 50, 1, next=-1),  # reject: no book
             M(op.BUY, 9012, 999, 77, 50, 1),           # reject: no account
             M(op.TRANSFER, 0, 999, 0, 0, 5, prev=2 ** 40),
             M(op.PAYOUT, 0, 0, -77, 0, 100), M(op.PAYOUT, 0, 0, -77, 0, 1),
             M(op.REMOVE_SYMBOL, 0, 0, 78), M(op.REMOVE_SYMBOL, 0, 0, 78)]
    return _oracle_groups(msgs + tail)


def _extreme_groups(_seed):
    """Hand-made groups whose fields sit at the ends of what the record
    holds: int64 ids, int32 prices and sizes, a fill pair between
    them, an unknown action, a message with its IN line alone."""
    big, small = 2 ** 63 - 1, -(2 ** 63)
    hi, lo = 2 ** 31 - 1, -(2 ** 31)

    def ln(key, *f, **kw):
        return f"{key} {order_json(*f, **kw)}"
    return [
        [ln("IN", op.BUY, big, small, hi, hi, hi, next=big, prev=small),
         ln("OUT", op.SOLD, small, big, hi, 0, hi),
         ln("OUT", op.BOUGHT, big, small, hi, lo, hi),
         ln("OUT", op.BUY, big, small, hi, hi, hi, next=big, prev=small)],
        [ln("IN", op.SELL, small, big, lo, lo, lo),
         ln("OUT", op.REJECT, small, big, lo, lo, lo)],
        [ln("IN", op.SELL, 5, 6, 7, 8, 9),
         ln("OUT", op.BOUGHT, 1, 2, 7, 0, 4),
         ln("OUT", op.SOLD, 5, 6, 7, 3, 4),
         ln("OUT", op.SELL, 5, 6, 7, 8, 0)],            # filled: no rest
        [ln("IN", 55, 1, 2, 3, 4, 5), ln("OUT", 55, 1, 2, 3, 4, 5)],
        [ln("IN", hi, 1, 2, 3, 4, 5), ln("OUT", op.REJECT, 1, 2, 3, 4, 5)],
        [ln("IN", op.CANCEL, 1, 2, 3, 4, 5)],
        [ln("IN", op.PAYOUT, 0, 0, lo, 0, hi),
         ln("OUT", op.PAYOUT, 0, 0, lo, 0, hi)],
    ]


needs_native = pytest.mark.skipif(
    load_library() is None,
    reason="native host runtime unavailable (KME_NATIVE=0 or no "
           "toolchain)")


@needs_native
@pytest.mark.parametrize("reasons", ["none", "codes", "zeros"])
@pytest.mark.parametrize("groups_of, seed", [
    (_lifecycle_groups, 3), (_lifecycle_groups, 2 ** 31 + 5),
    (_extreme_groups, 0)])
def test_native_rows_are_the_encoded_events(groups_of, seed, reasons):
    """kme_journal_rows over a batch's buffer gives, byte for byte,
    _encode of batch_events' dicts with _commit's stamps."""
    groups = groups_of(seed)
    kinds = set()
    for lo in range(0, len(groups), 64):
        part = groups[lo:lo + 64]
        rs = {"none": None, "zeros": np.zeros(len(part), np.uint8),
              "codes": np.arange(lo, lo + len(part)) % 10}[reasons]
        offs = list(range(1000 + lo, 1000 + lo + len(part)))
        evs = batch_events(part, rs, offs)
        for k, ev in enumerate(evs):
            ev.update(b=7, seq=500 + k, ts=123456789, sh=3)
        kinds |= {ev["e"] for ev in evs}
        rows = buffer_rows(*_buffer(part), rs, offs, 500, 123456789, 7, 3)
        assert rows is not None and rows.dtype == rec_dtype()
        assert rows.tobytes() == b"".join(_encode(ev) for ev in evs)
        assert list(EventBatch(rows)) == [_decode(_encode(ev))
                                          for ev in evs]
    if groups_of is _lifecycle_groups:
        assert kinds == set(ETYPES) - {"drop", "win", "lat", "span"}


@needs_native
def test_buffer_rows_refuses_what_it_cannot_read():
    """Arguments the native walk would read past, or a record cannot
    hold: None (the caller derives the batch from the lines)."""
    buf, off, ml = _buffer(_extreme_groups(0))
    stamps = (0, 1, 2, 3)
    assert buffer_rows(buf, off, ml, None, None, *stamps) is not None
    wide, short = ml.copy(), ml.copy()
    wide[0], wide[1] = ml[0] + ml[1] + 1, -1      # the sum still fits
    short[-1] = 0
    for lines in (wide, short, ml[:-1], ml.astype(np.int64)):
        assert buffer_rows(buf, off, lines, None, None, *stamps) is None
    assert buffer_rows(buf, off[:-1], ml, None, None, *stamps) is None
    assert buffer_rows(buf[:-1], off, ml, None, None, *stamps) is None
    assert buffer_rows(buf, off, ml, [0], None, *stamps) is None
    assert buffer_rows(buf, off, ml, None, [2 ** 63], *stamps) is None
    assert buffer_rows(buf, off, ml, None, None, 0, 1, 2, 256) is None
    assert buffer_rows(buf, off, ml, None, None, 0, 1, 2 ** 31, 3) is None


ODD_LINES = {
    "spaced": 'OUT {"action": 2, "oid": 1, "aid": 2, "sid": 3, '
              '"price": 4, "size": 0, "next": null, "prev": null}',
    "reordered": 'OUT {"oid":1,"action":2,"aid":2,"sid":3,"price":4,'
                 '"size":0,"next":null,"prev":null}',
    "extra-key": 'OUT {"action":2,"oid":1,"aid":2,"sid":3,"price":4,'
                 '"size":0,"next":null,"prev":null,"tid":9}',
    "float": 'OUT {"action":2,"oid":1,"aid":2,"sid":3,"price":4,'
             '"size":0.0,"next":null,"prev":null}',
}


@needs_native
@pytest.mark.parametrize("odd", sorted(ODD_LINES))
def test_buffer_of_another_shape_takes_the_lines(tmp_path, odd):
    """One line that is not put_order's: the batch is derived from its
    lines, the file holds what record_batch writes, and the native
    counter does not step."""
    groups = _lifecycle_groups(3)[-40:]
    groups[5] = [groups[5][0], ODD_LINES[odd]]
    paths = [str(tmp_path / name) for name in ("buf.kmej", "lines.kmej")]
    spans = PhaseTimer()
    a = Journal(paths[0], clock=lambda: 5, timer=spans)
    b = Journal(paths[1], clock=lambda: 5)
    seen = []
    a.observers.append(lambda evs, lines: seen.append((evs, lines)))
    offs = list(range(len(groups)))
    for j, record in ((a, lambda g: a.record_buffer(*_buffer(g),
                                                    offsets=offs[:len(g)])),
                      (b, lambda g: b.record_batch(g,
                                                   offsets=offs[:len(g)]))):
        record(groups)
        record(groups[:5])
        j.close()
    assert open(paths[0], "rb").read() == open(paths[1], "rb").read()
    assert a.native_batches == 1 and a.events_written == b.events_written
    # the fallback hands observers what record_batch does
    assert isinstance(seen[0][0], list) and seen[0][1] == groups
    assert isinstance(seen[-1][0], EventBatch) and seen[-1][1] is None
    assert spans.counts["journal_lines"] == 3   # refused, lines, taken
    assert spans.counts["journal_events"] == 1


@needs_native
@pytest.mark.parametrize("fmt", ["binary", "jsonl"])
def test_buffer_and_lines_write_one_file(tmp_path, fmt, monkeypatch):
    """record_buffer + record_latency_columns against record_batch +
    record_latency, batch by batch: the same file, counters, offsets
    and observer stream in either framing — and with the native library
    off (KME_NATIVE=0 is `load_library() is None`)."""
    import kme_tpu.native as native

    groups = _lifecycle_groups(4)
    for lib_on in (True, False):
        if not lib_on:
            monkeypatch.setattr(native, "load_library", lambda: None)
        ext = ".kmej" if fmt == "binary" else ".jsonl"
        js, seen = [], ([], [])
        for k, name in enumerate(("buf", "lines")):
            j = Journal(str(tmp_path / f"{name}{lib_on}{ext}"),
                        clock=lambda: 11, fsync="batch")
            j.observers.append(lambda evs, lines, k=k: seen[k].extend(evs))
            js.append(j)
        for b, lo in enumerate(range(0, len(groups), 50)):
            part = groups[lo:lo + 50]
            offs = list(range(lo, lo + len(part)))
            ats = [None if o % 7 == 0 else 1000 + o for o in offs]
            js[0].record_buffer(*_buffer(part), None, offs)
            js[1].record_batch(part, offsets=offs)
            has = np.array([a is not None for a in ats])
            at = np.array([a or 0 for a in ats])
            js[0].record_latency_columns(
                np.array(offs), np.array(offs) * 3,
                np.where(has, np.maximum(0, 1500 - at), 0), 4, 5, 6,
                np.where(has, 9000 - at, 0), batch=b)
            js[1].record_latency(
                [{"off": o, "oid": o * 3, "plan_us": 4, "dev_us": 5,
                  "prod_us": 6,
                  "in_us": max(0, 1500 - a) if a is not None else 0,
                  "e2e_us": 9000 - a if a is not None else 0}
                 for o, a in zip(offs, ats)], batch=b)
            assert js[0].last_offset == js[1].last_offset == offs[-1]
            assert js[0].lag_bytes == js[1].lag_bytes == 0
        for j in js:
            j.close()
        assert (open(js[0].path, "rb").read()
                == open(js[1].path, "rb").read())
        assert js[0].events_written == js[1].events_written
        assert js[0].bytes_written == js[1].bytes_written
        assert js[0].next_seq == js[1].next_seq
        assert seen[0] == seen[1] == read_events(js[1].path)
        n_batches = -(-len(groups) // 50)
        assert js[0].native_batches == (
            n_batches if fmt == "binary" and lib_on else 0)
        assert js[1].native_batches == 0


@needs_native
def test_torn_tail_of_a_buffer_batch_resumes(tmp_path):
    """journal.torn on the blob record_buffer writes: half the batch
    reaches the file and the process dies; the next incarnation cuts
    the torn record and goes on, dense."""
    import subprocess
    import sys

    path = str(tmp_path / "t.kmej")
    child = (
        "import sys; sys.path.insert(0, {tests!r})\n"
        "from kme_tpu import faults\n"
        "from test_journal import _buffer, _lifecycle_groups, Journal\n"
        "g = _lifecycle_groups(3)\n"
        "faults.configure('journal.torn:n=1:after=1')\n"
        "j = Journal({path!r}, fsync='batch')\n"
        "j.record_buffer(*_buffer(g[:40]), None, list(range(40)))\n"
        "j.record_buffer(*_buffer(g[40:90]), None, list(range(40, 90)))\n"
        "print('survived')\n").format(tests=os.path.dirname(__file__),
                                      path=path)
    r = subprocess.run([sys.executable, "-c", child], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == -9 and "survived" not in r.stdout, r.stderr
    groups = _lifecycle_groups(3)
    whole = len(batch_events(groups[:40]))
    body = os.path.getsize(path) - len(MAGIC)
    assert body > whole * REC_SIZE          # part of batch two is there
    assert len(read_events(path)) == body // REC_SIZE
    j = Journal(path, clock=lambda: 7)
    tail = read_events(path)[-1]
    assert j.next_seq == tail["seq"] + 1
    j.rewind_to_offset(40)                  # the service's resume
    j.record_buffer(*_buffer(groups[40:90]), None, list(range(40, 90)))
    j.close()
    evs = read_events(path)
    assert [e["seq"] for e in evs] == list(range(len(evs)))
    assert canonical_lines(evs) == canonical_lines(
        batch_events(groups[:90], offsets=list(range(90))))
    assert j.native_batches == 1


@needs_native
def test_journal_written_from_buffers_verifies(tmp_path):
    """`kme-trace <journal> --verify <input>` on a journal that
    record_buffer wrote: the independent oracle replay agrees."""
    from kme_tpu.cli import trace_main

    msgs = harness_stream(300, seed=8, num_accounts=6, num_symbols=2,
                          payout_opcode_bug=False, validate=True)
    inp = tmp_path / "input.jsonl"
    inp.write_text("".join(dumps_order(m) + "\n" for m in msgs))
    groups = _oracle_groups(msgs)
    jp = str(tmp_path / "j.kmej")
    j = Journal(jp, fsync="batch")
    for lo in range(0, len(groups), 64):
        part = groups[lo:lo + 64]
        offs = np.arange(lo, lo + len(part))
        j.record_buffer(*_buffer(part), None, offs)
        j.record_latency_columns(offs, offs, 1, 2, 3, 4, 5, batch=lo)
    j.close()
    assert j.native_batches == -(-len(groups) // 64)
    assert trace_main([jp, "--verify", str(inp)]) == 0
    bad = tmp_path / "bad.jsonl"
    bad.write_text("".join(dumps_order(m) + "\n" for m in msgs[::-1]))
    assert trace_main([jp, "--verify", str(bad)]) == 1
