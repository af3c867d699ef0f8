"""Feed read path (ISSUE 13): frame codec, deriver-vs-oracle book
reconstruction, determinism, durable snapshots, the snapshot-then-
deltas splice edge cases (checkpoint boundary / mid-payout-storm /
during a PR 8 shard migration), and a live server/client round trip.
"""

import json
import os
import struct
import time

import pytest

from kme_tpu.feed import frames as ff
from kme_tpu.feed.derive import (BookBuilder, BookState, FeedDeriver,
                                 books_from_oracle, canonical_books)
from kme_tpu.feed.frames import (FeedFrameError, decode_feed,
                                 decode_feed_frames)
from kme_tpu.feed.snapshot import (feed_snapshot_path,
                                   list_feed_snapshots,
                                   load_feed_snapshot,
                                   save_feed_snapshot, snapshot_frames)
from kme_tpu.oracle import OracleEngine
from kme_tpu.wire import WIRE_MAGIC, WIRE_VERSION
from kme_tpu.workload import harness_stream, storm_stream


def oracle_lines(msgs, compat="fixed", **kw):
    eng = OracleEngine(compat, **kw)
    lines = []
    for m in msgs:
        lines.extend(r.wire() for r in eng.process(m))
    return eng, lines


def run_deriver(lines, **kw):
    d = FeedDeriver(**kw)
    raw = b""
    for i, ln in enumerate(lines):
        for f in d.on_line(ln, 1, i):
            raw += f.raw
    return d, raw


# ---------------------------------------------------------------------------
# frame codec


def test_codec_roundtrip_every_kind():
    d = decode_feed(ff.encode_delta(3, 7, 2, 99, 11, 1, 500, 40))[0]
    assert (d.kind, d.group, d.seq, d.src_epoch, d.src_seq) == (
        ff.FEED_DELTA, 3, 7, 2, 99)
    assert (d.sid, d.side, d.price, d.size) == (11, 1, 500, 40)

    t = decode_feed(ff.encode_tob(0, 1, 5, 6, 9, 100, 2, 101, 3,
                                  conflated=True))[0]
    assert t.kind == ff.FEED_TOB and t.conflated
    assert (t.bid_price, t.bid_size, t.ask_price, t.ask_size) == (
        100, 2, 101, 3)

    dp = decode_feed(ff.encode_depth(
        1, 4, 5, 6, 9, [(100, 2), (99, 1)], [(101, 7)],
        refresh=True))[0]
    assert dp.kind == ff.FEED_DEPTH and dp.refresh
    assert dp.bids == ((100, 2), (99, 1)) and dp.asks == ((101, 7),)

    sb = decode_feed(ff.encode_snap_begin(2, 5, 6, 12, depth=8))[0]
    assert (sb.kind, sb.count, sb.depth) == (ff.FEED_SNAP_BEGIN, 12, 8)
    se = decode_feed(ff.encode_snap_end(2, 5, 6, 12, b"payload"))[0]
    assert se.kind == ff.FEED_SNAP_END and se.count == 12
    import zlib

    assert se.crc == zlib.crc32(b"payload") & 0xFFFFFFFF

    rs = decode_feed(ff.encode_resync(0, 9, 5, 6, -1))[0]
    assert rs.kind == ff.FEED_RESYNC and rs.sid == -1 and rs.conflated

    # raw preserves the exact encoded bytes on decode
    raw = ff.encode_delta(0, 1, 1, 0, 1, 0, 10, 1)
    assert decode_feed(raw)[0].raw == raw


def _reason(buf):
    with pytest.raises(FeedFrameError) as ei:
        decode_feed(buf)
    return ei.value.reason


def test_codec_error_reasons_mirror_wire():
    good = ff.encode_delta(0, 1, 1, 0, 1, 0, 10, 1)
    assert _reason(good[:4]) == "truncated"
    assert _reason(good[:-1]) == "truncated"
    assert _reason(b"\x00" + good[1:]) == "bad_magic"
    assert _reason(good[:1] + b"\xfe" + good[2:]) == "version_skew"
    bad_kind = bytearray(good)
    bad_kind[2] = 0            # order-frame kind on a feed socket
    assert _reason(bytes(bad_kind)) == "bad_kind"
    bad_len = bytearray(good)
    struct.pack_into("<I", bad_len, 4, 8)     # < common prefix
    assert _reason(bytes(bad_len)) == "bad_length"
    # kind-specific body-size mismatch: delta envelope, tob-sized body
    mixed = bytearray(ff.encode_tob(0, 1, 1, 0, 1, 1, 1, 2, 2))
    mixed[2] = ff.FEED_DELTA
    assert _reason(bytes(mixed)) == "bad_length"
    # depth pair-count vs body-length cross check
    dep = bytearray(ff.encode_depth(0, 1, 1, 0, 1, [(1, 1)], []))
    struct.pack_into("<I", dep, 44, 2)        # nbid lies
    assert _reason(bytes(dep)) == "bad_length"


def test_codec_fuzz_never_hangs_or_misreports(monkeypatch=None):
    import random

    rng = random.Random(13)
    base = (ff.encode_delta(0, 1, 1, 0, 1, 0, 10, 1)
            + ff.encode_tob(0, 2, 1, 1, 1, 10, 1, 11, 2)
            + ff.encode_depth(0, 3, 1, 2, 1, [(10, 1)], [(11, 2)]))
    for _ in range(300):
        buf = bytearray(base)
        for _k in range(rng.randint(1, 4)):
            buf[rng.randrange(len(buf))] = rng.randrange(256)
        try:
            decode_feed_frames(bytes(buf))
        except FeedFrameError as e:
            assert e.reason in ("truncated", "bad_magic",
                                "version_skew", "bad_kind",
                                "bad_length")
    # truncation at every boundary of a valid frame
    f = ff.encode_tob(0, 1, 1, 0, 1, 10, 1, 11, 2)
    for cut in range(len(f)):
        assert ff.feed_frame_length(f[:cut], 0) is None or cut >= 8


def test_frame_constants_share_the_wire_envelope():
    raw = ff.encode_delta(0, 1, 1, 0, 1, 0, 10, 1)
    magic, version, kind, _fl, length = struct.unpack_from("<BBBBI",
                                                           raw)
    assert magic == WIRE_MAGIC and version == WIRE_VERSION
    assert kind == ff.FEED_DELTA and length == len(raw) == ff.DELTA_SIZE


# ---------------------------------------------------------------------------
# deriver vs oracle


@pytest.mark.parametrize("compat", ["fixed", "java"])
def test_deriver_books_match_oracle(compat):
    msgs = harness_stream(800, seed=11, num_accounts=8, num_symbols=4,
                          payout_opcode_bug=(compat == "java"),
                          validate=(compat == "fixed"))
    eng, lines = oracle_lines(msgs, compat)
    _d, raw = run_deriver(lines)
    bb = BookBuilder()
    assert bb.apply_buffer(raw) == len(raw)
    assert not bb.errors and not bb.gaps and bb.dups == 0
    assert canonical_books(bb.book) == canonical_books(
        books_from_oracle(eng))


@pytest.mark.parametrize("profile", ["payout-storm-wide", "hot-book"])
def test_deriver_books_match_oracle_under_storms(profile):
    msgs = storm_stream(profile, 1500, num_symbols=16, seed=3)
    eng, lines = oracle_lines(msgs)
    _d, raw = run_deriver(lines, depth_every=64)
    bb = BookBuilder()
    assert bb.apply_buffer(raw) == len(raw)
    assert not bb.errors and not bb.gaps and bb.dups == 0
    assert canonical_books(bb.book) == canonical_books(
        books_from_oracle(eng))
    # depth views agree at every requested depth, not just full book
    want = BookState()
    want.levels = books_from_oracle(eng)
    for sid in bb.book.sids():
        for n in (1, 4, 8, 0):
            assert bb.book.depth(sid, n) == want.depth(sid, n)
        assert bb.tob.get(sid, (0, 0, 0, 0)) == want.tob(sid)


def test_deriver_is_deterministic_and_densely_sequenced():
    msgs = storm_stream("flash-crowd", 900, num_symbols=8, seed=5)
    _eng, lines = oracle_lines(msgs)
    _d1, raw1 = run_deriver(lines, depth_every=32)
    _d2, raw2 = run_deriver(lines, depth_every=32)
    assert raw1 == raw2, "same stream, different frame bytes"
    # per-symbol seq is dense 1..N: a filtered subscriber still sees
    # no gaps (the reason seq is per-symbol, not per-channel)
    frames = decode_feed_frames(raw1)
    per = {}
    for f in frames:
        if f.kind in (ff.FEED_DELTA, ff.FEED_TOB) or (
                f.kind == ff.FEED_DEPTH and not f.refresh):
            per.setdefault(f.sid, []).append(f.seq)
    assert per, "stream derived no sequenced frames"
    for sid, seqs in per.items():
        assert seqs == list(range(1, len(seqs) + 1)), f"sid {sid}"
    # symbol-filtered builder: gap-free on its subset
    keep = sorted(per)[0]
    bb = BookBuilder()
    for f in frames:
        if f.sid == keep:
            bb.apply(f)
    assert not bb.gaps and bb.dups == 0
    # ... and a dropped frame IS a gap; a replayed one IS a dup
    seq_frames = [f for f in frames if f.sid == keep]
    bb2 = BookBuilder()
    for f in seq_frames[:1] + seq_frames[2:]:
        bb2.apply(f)
    assert bb2.gaps
    bb3 = BookBuilder()
    for f in seq_frames[:2] + seq_frames[1:2]:
        bb3.apply(f)
    assert bb3.dups == 1


# ---------------------------------------------------------------------------
# durable snapshots (checkpoint discipline)


def test_feed_snapshot_roundtrip_continues_byte_identically(tmp_path):
    msgs = harness_stream(600, seed=2, num_accounts=6, num_symbols=3,
                          payout_opcode_bug=False, validate=True)
    _eng, lines = oracle_lines(msgs)
    cut = len(lines) // 2
    d = FeedDeriver(depth_every=16)
    for i, ln in enumerate(lines[:cut]):
        d.on_line(ln, 1, i)
    path = save_feed_snapshot(str(tmp_path), d, cut)
    assert path == feed_snapshot_path(str(tmp_path), cut)
    off, restored = load_feed_snapshot(str(tmp_path))
    assert off == cut
    tail = b""
    tail_restored = b""
    for i, ln in enumerate(lines[cut:], start=cut):
        for f in d.on_line(ln, 1, i):
            tail += f.raw
        for f in restored.on_line(ln, 1, i):
            tail_restored += f.raw
    assert tail == tail_restored, "restored deriver forked the stream"


def test_feed_snapshot_corrupt_falls_back_then_none(tmp_path):
    msgs = harness_stream(200, seed=6, num_accounts=4, num_symbols=2,
                          payout_opcode_bug=False, validate=True)
    _eng, lines = oracle_lines(msgs)
    cut = len(lines) // 2
    d = FeedDeriver()
    for i, ln in enumerate(lines[:cut]):
        d.on_line(ln, 1, i)
    older = canonical_books(d.book)
    save_feed_snapshot(str(tmp_path), d, cut)
    for i, ln in enumerate(lines[cut:], start=cut):
        d.on_line(ln, 1, i)
    newest = save_feed_snapshot(str(tmp_path), d, len(lines))
    # flip a digit inside the newest state: digest verify must reject
    # it and the loader must fall back to the older snapshot
    blob = bytearray(open(newest, "rb").read())
    idx = blob.index(b'"watermark"') + len(b'"watermark":[')
    blob[idx] = ord("7") if blob[idx] != ord("7") else ord("8")
    open(newest, "wb").write(bytes(blob))
    off, restored = load_feed_snapshot(str(tmp_path))
    assert off == cut
    assert canonical_books(restored.book) == older
    # every snapshot corrupt -> None, not an exception
    for _o, p in list_feed_snapshots(str(tmp_path)):
        open(p, "w").write("{not json")
    assert load_feed_snapshot(str(tmp_path)) is None


def test_feed_snapshot_prunes_like_engine_checkpoints(tmp_path):
    d = FeedDeriver()
    for off in range(6):
        save_feed_snapshot(str(tmp_path), d, off, keep=3)
    offs = [o for o, _p in list_feed_snapshots(str(tmp_path))]
    assert offs == [5, 4, 3]


# ---------------------------------------------------------------------------
# snapshot-then-deltas splice edge cases (ISSUE 13 satellite)


def _splice(lines, cut, eng, depth_every=16, sids=None):
    """Serve a snapshot at `cut`, splice deltas from there, and return
    the late joiner's builder (asserting zero gap/dup/error)."""
    server = FeedDeriver(depth_every=depth_every)
    for i, ln in enumerate(lines[:cut]):
        server.on_line(ln, 1, i)
    handover = snapshot_frames(server, sids=sids)
    bb = BookBuilder()
    assert bb.apply_buffer(handover) == len(handover)
    assert bb.watermark == (1, cut - 1 if cut else -1)
    tail = b""
    for i, ln in enumerate(lines[cut:], start=cut):
        for f in server.on_line(ln, 1, i):
            if sids is None or f.sid in sids or f.kind in (
                    ff.FEED_SNAP_BEGIN, ff.FEED_SNAP_END):
                tail += f.raw
    assert bb.apply_buffer(tail) == len(tail)
    assert not bb.errors, bb.errors
    assert not bb.gaps and bb.dups == 0
    want = books_from_oracle(eng)
    if sids is not None:
        want = {k: v for k, v in want.items() if k[0] in sids}
    assert canonical_books(bb.book) == canonical_books(want)
    return bb


def test_splice_exactly_at_checkpoint_boundary(tmp_path):
    """A subscriber that joins at the precise offset a durable feed
    snapshot was written sees the identical reconstruction whether it
    splices off the live deriver or the restored one."""
    msgs = harness_stream(700, seed=9, num_accounts=8, num_symbols=4,
                          payout_opcode_bug=False, validate=True)
    eng, lines = oracle_lines(msgs)
    cut = len(lines) // 3
    live = FeedDeriver(depth_every=16)
    for i, ln in enumerate(lines[:cut]):
        live.on_line(ln, 1, i)
    save_feed_snapshot(str(tmp_path), live, cut)
    _off, restored = load_feed_snapshot(str(tmp_path))
    assert snapshot_frames(restored) == snapshot_frames(live), (
        "restored deriver serves a different wire snapshot")
    _splice(lines, cut, eng)


def test_splice_mid_payout_storm():
    """PAYOUT sweeps whole books away; joining in the middle of the
    storm must still reconstruct exactly (snapshot carries the swept
    state, deltas carry the rest of the sweep)."""
    msgs = storm_stream("payout-storm-wide", 1200, num_symbols=12,
                        seed=7)
    eng, lines = oracle_lines(msgs)
    payout_offs = [i for i, ln in enumerate(lines)
                   if ln.startswith("OUT") and " P " in f" {ln} "]
    # splice inside the storm body: between two payout records
    cut = (len(lines) // 2) | 1
    _splice(lines, cut, eng)
    # and with a filtered subscription (per-symbol seq must stay dense
    # through the sweep for the watched subset)
    sids = {m.sid for m in msgs if m.sid > 0}
    keep = {sorted(sids)[0], sorted(sids)[-1]}
    _splice(lines, cut, eng, sids=keep)


@pytest.mark.slow
def test_splice_during_shard_migration(cpu_devices):
    """PR 8: the elastic mesh migrates hot lanes between shards
    mid-stream. MatchOut bytes are placement-invariant, so a feed
    subscriber splicing while migrations are happening reconstructs
    the identical book — proven against the mesh's own output with
    migrations observed.

    slow: the mesh compile alone is ~60s on CPU; the CI feed job runs
    this file without the tier-1 marker filter, so the splice drill
    still gates every PR."""
    from kme_tpu.engine import seq as SQ
    from kme_tpu.parallel.seqmesh import SeqMeshSession
    from kme_tpu.workload import zipf_hot_stream

    cfg = dict(lanes=8, slots=128, accounts=128, max_fills=16,
               pos_cap=1 << 10, probe_max=8)
    msgs = zipf_hot_stream(1200, num_symbols=8, num_accounts=24,
                           seed=7)
    ses = SeqMeshSession(SQ.SeqConfig(**cfg), shards=2)
    lines = []
    for lo in range(0, len(msgs), 300):
        for per in ses.process_wire(msgs[lo:lo + 300]):
            lines.extend(per)
    assert ses.shard_stats()["migrations"] > 0, (
        "stream produced no migrations; splice test is vacuous")
    eng = OracleEngine("fixed", book_slots=cfg["slots"],
                       max_fills=cfg["max_fills"])
    want = []
    for m in msgs:
        want.extend(r.wire() for r in eng.process(m.copy()))
    assert lines == want, "mesh diverged from oracle"
    # splice mid-stream (migrations happen between batches throughout)
    bb = _splice(lines, len(lines) // 2, eng)
    # the full-replay builder agrees byte-for-byte with the splicer
    _d, raw = run_deriver(lines, depth_every=16)
    full = BookBuilder()
    assert full.apply_buffer(raw) == len(raw)
    assert canonical_books(bb.book) == canonical_books(full.book)


# ---------------------------------------------------------------------------
# server/client integration


def test_feed_server_fanout_filtered_and_wildcard(tmp_path):
    import threading

    from kme_tpu.bridge.broker import InProcessBroker
    from kme_tpu.feed.client import FeedClient
    from kme_tpu.feed.server import FeedServer, write_health
    from kme_tpu.telemetry.registry import Registry

    msgs = harness_stream(400, seed=4, num_accounts=6, num_symbols=3,
                          payout_opcode_bug=False, validate=True)
    eng, lines = oracle_lines(msgs)
    books = books_from_oracle(eng)
    sids = sorted({s for s, _side in books})
    broker = InProcessBroker(persist_dir=str(tmp_path / "b"))
    broker.create_topic("MatchOut")
    srv = FeedServer(broker, port=0, topic="MatchOut", depth_every=64,
                     registry=Registry())
    host, port = srv.address
    stop = threading.Event()
    th = threading.Thread(target=srv.serve_forever, args=(stop,),
                          daemon=True)
    th.start()
    clients = [FeedClient(host, port, symbols=None, timeout=5.0),
               FeedClient(host, port, symbols={sids[0]}, timeout=5.0)]
    try:
        deadline = time.monotonic() + 10
        while srv.stats()["subscribers"] < 2:
            assert time.monotonic() < deadline, "subscribe stalled"
            time.sleep(0.01)
        for i, ln in enumerate(lines):
            broker.produce("MatchOut", None, ln, epoch=1, out_seq=i,
                           ats=time.time_ns() // 1000)
        deadline = time.monotonic() + 15
        while srv.offset < len(lines) or srv.stats()["subscribers"]:
            if srv.offset >= len(lines):
                break
            assert time.monotonic() < deadline, "fan-out stalled"
            time.sleep(0.01)
        # the server is single-threaded: park the serving thread before
        # pumping from this one (two threads in step() raced in _pump)
        stop.set()
        th.join(10)
        assert not th.is_alive()
        srv.drain(10.0)
        write_health(str(tmp_path / "feed.health"), srv)
    finally:
        srv.stop()
        stop.set()
        th.join(10)
        srv.close()
    for c in clients:
        c.drain()                       # to EOF after close()
        c.close()
        bb = c.builder
        assert not bb.errors and not bb.gaps and bb.dups == 0
    assert canonical_books(clients[0].builder.book) == canonical_books(
        books)
    assert canonical_books(clients[1].builder.book) == canonical_books(
        {k: v for k, v in books.items() if k[0] == sids[0]})
    # the heartbeat carries the registry snapshot kme-top renders
    doc = json.load(open(tmp_path / "feed.health"))
    assert doc["role"] == "feed"
    assert doc["metrics"]["gauges"]["feed_offset"] == len(lines)


def test_feed_cli_entrypoint_exists():
    from kme_tpu.cli import feed_main

    with pytest.raises(SystemExit):
        feed_main(["--help"])
