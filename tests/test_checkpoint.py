"""Checkpoint / resume + fault injection.

The durability contract (SURVEY.md §5, replacing the reference's
RocksDB+changelog restore, KProcessor.java:30-49): kill the engine
mid-stream, resume from the snapshot, and the continuation is
bit-identical to an uninterrupted run — with at-least-once replay of
the tail after the last snapshot, exactly like the reference (EOS is
commented out at KProcessor.java:29).
"""

import os

import pytest

from kme_tpu.bridge.broker import InProcessBroker
from kme_tpu.bridge.consume import consume_lines
from kme_tpu.bridge.provision import provision
from kme_tpu.bridge.service import TOPIC_IN, MatchService
from kme_tpu.oracle import OracleEngine
from kme_tpu.runtime import checkpoint as ck
from kme_tpu.wire import dumps_order
from kme_tpu.workload import harness_stream, zipf_symbol_stream

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = dict(lanes=8, slots=128, accounts=128, max_fills=16)


def _seq_session(state=None, **shape):
    from kme_tpu.engine import seq as SQ
    from kme_tpu.runtime.seqsession import SeqSession

    ses = SeqSession(SQ.SeqConfig(**shape))
    if state is not None:
        ses.state = SQ.import_canonical(ses.cfg, state)
    return ses


def _java_cfg():
    from kme_tpu.engine import seq as SQ

    return SQ.SeqConfig(lanes=8, slots=512, accounts=128, max_fills=128,
                        batch=512, pos_cap=1 << 12, probe_max=16,
                        compat="java")


def _java_stream(n=2400, seed=7):
    return harness_stream(n, seed=seed)


# ---------------------------------------------------------------------------
# the snapshot directory's discipline, over every writer there is: the
# two .npz ones a SeqSession has (fixed mode, java mode) and the two of
# the host engines (`native`: a header line and a text dump; `oracle`:
# a pickle)

def _writer(kind, monkeypatch=None):
    """-> (fresh engine, its stream, save, load) of one snapshot
    writer. `fixed-python` is `fixed-native` with the router that
    KME_NATIVE=0 serves with: the same writer, the routes from a dict."""
    from kme_tpu.native import load_library
    from kme_tpu.runtime import seqsession

    if kind == "java":
        return (seqsession.SeqSession(_java_cfg()), _java_stream(n=600),
                ck.save_seq_session, ck.load_seq_session)
    msgs = list(zipf_symbol_stream(900, 8, 64, seed=12, zipf_a=0.0))
    if kind == "oracle":
        return (OracleEngine("fixed", book_slots=128, max_fills=16), msgs,
                ck.save_oracle, ck.load_oracle)
    if kind == "fixed-python":
        monkeypatch.setattr(
            seqsession, "make_seq_router",
            lambda lanes, accounts, compat="fixed":
            seqsession.SeqRouter(lanes, accounts, compat))
    elif load_library() is None:
        pytest.skip("native host runtime unavailable")
    if kind == "native":
        from kme_tpu.native.oracle import NativeOracleEngine

        return (NativeOracleEngine("fixed", book_slots=128, max_fills=16),
                msgs, ck.save_native, ck.load_native)
    ses = _seq_session(**SMALL)
    want = seqsession.SeqRouter if kind == "fixed-python" \
        else seqsession.NativeSeqRouter
    assert type(ses.router) is want
    return ses, msgs, ck.save_seq_session, ck.load_seq_session


WRITERS = ["fixed-native", "java", "native", "oracle"]


def _serve(eng, msgs):
    """The wire lines of `msgs`, a list a message, from any engine."""
    if hasattr(eng, "process_wire"):
        return eng.process_wire([m.copy() for m in msgs])
    return [[r.wire() for r in eng.process(m.copy())] for m in msgs]


def _offsets(ckpt_dir):
    return [off for off, _ in ck.all_snapshots(ckpt_dir)]


def _two_snapshots(kind, ckpt_dir, at=(100, 200)):
    """-> (engine, stream, load, the newest file): `kind`'s engine
    served to each offset of `at` and saved there."""
    eng, msgs, save, load = _writer(kind)
    done = 0
    for off in at:
        _serve(eng, msgs[done:off])
        path = save(ckpt_dir, eng, off)
        done = off
    return eng, msgs, load, path


@pytest.mark.parametrize("kind", WRITERS)
def test_session_kill_resume_bit_identical(kind, tmp_path):
    """Kill the engine after 300 of 600 messages; the resumed engine's
    tail output and final state match the uninterrupted run exactly."""
    full, msgs, save, load = _writer(kind)
    msgs, cut = msgs[:600], 300
    want_lines = _serve(full, msgs[:cut]) + _serve(full, msgs[cut:])
    want_state = full.export_state()

    eng = _writer(kind)[0]
    got_head = _serve(eng, msgs[:cut])
    save(str(tmp_path), eng, cut)
    del eng  # the crash

    resumed, offset = load(str(tmp_path))
    assert offset == cut
    got_tail = _serve(resumed, msgs[cut:])
    assert got_head + got_tail == want_lines
    assert resumed.export_state() == want_state


@pytest.mark.parametrize("kind", WRITERS)
def test_corrupt_latest_snapshot_falls_back(kind, tmp_path):
    _, _, load, newest = _two_snapshots(kind, str(tmp_path))
    # torn write of the newest snapshot
    with open(newest, "r+b") as f:
        f.truncate(100)
    resumed, offset = load(str(tmp_path))
    assert offset == 100  # fell back to the previous good snapshot
    assert resumed is not None


def _crashed_and_resumed(kw, log_dir=None, n=400, first=250, seed=13):
    """Serve `first` of `n` messages with a checkpointing seq service,
    crash it past its last snapshot, restart it on the same broker (a
    fresh one over `log_dir`'s durable log where that is given) and
    checkpoint directory and serve the rest. -> (the broker that holds
    the output, the stream, how far the first service got, the
    snapshot the second started from)."""
    msgs = harness_stream(n, seed=seed, num_symbols=4, num_accounts=8,
                          payout_opcode_bug=False, validate=True)
    broker = InProcessBroker(persist_dir=log_dir)
    provision(broker)
    for m in msgs:
        broker.produce(TOPIC_IN, None, dumps_order(m))
    svc = MatchService(broker, **kw)
    svc.run(max_messages=first)
    served, snap = svc.offset, svc._last_ckpt_offset
    # a pipelined loop drains what it has in flight as run() returns
    assert (served, snap) == (first, first // 100 * 100) \
        or (svc.pipeline and first <= served and 100 <= snap <= served)
    del svc  # crash: the records past the last snapshot replay
    if log_dir is not None:
        del broker  # the whole process died: the log is reloaded
        broker = InProcessBroker(persist_dir=log_dir)
    svc2 = MatchService(broker, **kw)
    assert svc2.offset == snap  # resumed
    rest = len(msgs) - snap
    assert svc2.run(max_messages=rest) == rest
    svc2.close()
    return broker, msgs, served, snap


def _seq_service(tmp_path, pipeline, **more):
    if pipeline:
        from kme_tpu.native import load_library

        if load_library() is None:
            pytest.skip("native host runtime unavailable")
    return dict(engine="seq", compat="fixed", batch=50, symbols=8,
                accounts=16, slots=128, max_fills=32, pipeline=pipeline,
                checkpoint_dir=str(tmp_path / "ckpt"),
                checkpoint_every=100, **more)


def _at_least_once(msgs, served, snap):
    """What the output topic holds after a crash at `served` and a
    resume from `snap`: the tail after the snapshot twice, each copy
    what the oracle says."""
    ora = OracleEngine("fixed", book_slots=128, max_fills=32)
    per_msg = [[r.wire() for r in ora.process(m.copy())] for m in msgs]
    return [ln for lines in per_msg[:served] + per_msg[snap:]
            for ln in lines]


# the two values the benchmark's cells serve with
PIPELINES = [0, 2]


@pytest.mark.parametrize("pipeline", PIPELINES)
def test_service_crash_resume_at_least_once(pipeline, tmp_path):
    """Service-level fault injection: crash a checkpointing service
    mid-stream (after its last snapshot), restart it on the same broker
    and checkpoint dir. The tail after the snapshot replays (at-least-
    once) and every replayed record's output is bit-identical."""
    broker, msgs, served, snap = _crashed_and_resumed(
        _seq_service(tmp_path, pipeline))
    assert list(consume_lines(broker, follow=False)) \
        == _at_least_once(msgs, served, snap)


def test_native_engine_crash_resume(tmp_path):
    """The native quirk-exact engine's checkpoint: crash the service
    mid-stream, restart from the snapshot + durable broker log, and the
    quirk-exact java-mode stream completes byte-identically (with the
    documented at-least-once replay of the post-snapshot tail)."""
    nat = pytest.importorskip("kme_tpu.native.oracle")
    if not nat.native_available():
        pytest.skip("native library unavailable")
    msgs = harness_stream(400, seed=77)
    per_msg = []
    ora = OracleEngine("java")
    for m in msgs:
        per_msg.append([r.wire() for r in ora.process(m.copy())])

    log_dir = str(tmp_path / "broker-log")
    ck_dir = str(tmp_path / "ckpt")
    kw = dict(engine="native", compat="java", batch=50,
              checkpoint_dir=ck_dir, checkpoint_every=100)

    b1 = InProcessBroker(persist_dir=log_dir)
    provision(b1)
    for m in msgs:
        b1.produce(TOPIC_IN, None, dumps_order(m))
    svc1 = MatchService(b1, **kw)
    assert svc1.run(max_messages=150) == 150  # snapshot at 100
    del svc1, b1  # crash

    b2 = InProcessBroker(persist_dir=log_dir)
    svc2 = MatchService(b2, **kw)
    assert svc2.offset == 100
    rest = len(msgs) - 100
    assert svc2.run(max_messages=rest) == rest

    got = list(consume_lines(b2, follow=False))
    want = [ln for lines in per_msg[:150] for ln in lines]
    want += [ln for lines in per_msg[100:] for ln in lines]
    assert got == want


def test_broker_log_persistence_and_torn_tail(tmp_path):
    """The broker's append-only topic logs survive a restart; a torn
    trailing line (crash mid-append) is dropped on reload."""
    d = str(tmp_path)
    b1 = InProcessBroker(persist_dir=d)
    provision(b1)
    b1.produce(TOPIC_IN, None, '{"action":100,"aid":1}')
    b1.produce(TOPIC_IN, "k", '{"action":101,"aid":1,"size":5}')

    b2 = InProcessBroker(persist_dir=d)  # restart
    recs = b2.fetch(TOPIC_IN, 0)
    assert [(r.offset, r.key, r.value) for r in recs] == [
        (0, None, '{"action":100,"aid":1}'),
        (1, "k", '{"action":101,"aid":1,"size":5}')]
    assert b2.produce(TOPIC_IN, None, "x") == 2  # offsets continue

    with open(tmp_path / f"{TOPIC_IN}.log", "a", encoding="utf-8") as f:
        f.write('["k", "torn')  # no newline: crash mid-append
    with open(tmp_path / f"{TOPIC_IN}.log", "rb") as f:
        pre_torn = f.read()
    b3 = InProcessBroker(persist_dir=d)
    assert b3.end_offset(TOPIC_IN) == 3  # torn tail dropped
    # the repair is a TRUNCATE at the torn byte offset — committed
    # records are never rewritten (crash during a full rewrite would
    # lose them)
    with open(tmp_path / f"{TOPIC_IN}.log", "rb") as f:
        assert f.read() == pre_torn[:pre_torn.rfind(b"\n") + 1]


def test_broker_log_corruption_refuses_load(tmp_path):
    """Any undecodable newline-TERMINATED line — interior or final — is
    corruption of committed data (produce appends one whole line per
    record; partial writes are prefixes, so a torn append can never have
    its newline): the broker refuses to load rather than silently
    truncating committed records a checkpoint offset may still address."""
    import pytest

    from kme_tpu.bridge.broker import BrokerError

    d = str(tmp_path)
    b1 = InProcessBroker(persist_dir=d)
    provision(b1)
    for i in range(3):
        b1.produce(TOPIC_IN, None, f'{{"action":100,"aid":{i}}}')
    path = tmp_path / f"{TOPIC_IN}.log"
    pristine = path.read_bytes()
    lines = pristine.splitlines(keepends=True)
    path.write_bytes(b"".join([lines[0], b'NOT JSON\n'] + lines[2:]))
    with pytest.raises(BrokerError, match="corrupt record"):
        InProcessBroker(persist_dir=d)
    # newline-terminated garbage FINAL line: still committed-data
    # corruption, not a repairable torn tail
    path.write_bytes(b"".join(lines[:2] + [b'NOT JSON\n']))
    with pytest.raises(BrokerError, match="corrupt record"):
        InProcessBroker(persist_dir=d)


def test_broker_sync_and_consume_waits_for_topic(tmp_path):
    """broker.sync() fsyncs the topic logs (checkpoint calls it before
    committing an offset); consume_lines with follow=True polls for a
    not-yet-provisioned MatchOut instead of crashing."""
    from kme_tpu.bridge.consume import consume_lines

    d = str(tmp_path)
    b = InProcessBroker(persist_dir=d)
    provision(b)
    b.produce(TOPIC_IN, None, '{"action":100,"aid":1}')
    b.sync()  # must not raise; records durable
    assert InProcessBroker(persist_dir=d).end_offset(TOPIC_IN) == 1

    b2 = InProcessBroker()  # nothing provisioned: MatchOut missing
    # follow=False propagates (fail fast for one-shot reads)
    import pytest

    from kme_tpu.bridge.broker import BrokerError

    with pytest.raises(BrokerError):
        list(consume_lines(b2, follow=False))
    # follow=True + idle_exit polls, then exits cleanly when the topic
    # never appears
    assert list(consume_lines(b2, follow=True, poll_timeout=0.02,
                              idle_exit=0.1)) == []
    # and picks records up once the topic exists
    provision(b2)
    b2.produce("MatchOut", "OUT", "x")
    assert list(consume_lines(b2, follow=True, poll_timeout=0.02,
                              idle_exit=0.2)) == ["OUT x"]


@pytest.mark.parametrize("pipeline", PIPELINES)
def test_service_crash_resume_full_process_restart(pipeline, tmp_path):
    """The kme-serve topology: broker log AND engine snapshot both live
    on disk; a full restart (fresh broker + fresh service) resumes and
    the stream completes bit-identically (at-least-once tail replay)."""
    broker, msgs, served, snap = _crashed_and_resumed(
        _seq_service(tmp_path, pipeline),
        log_dir=str(tmp_path / "broker-log"), n=300, first=150, seed=31)
    assert list(consume_lines(broker, follow=False)) \
        == _at_least_once(msgs, served, snap)


# ---------------------------------------------------------------------------
# java-mode seq checkpoints (runtime/javasnap.py): the 128-bit-key
# canonical form incl. Q11 garbage keys, and cross-engine restore
# seq-java <-> native with byte-identical continuation
# (VERDICT r4 #4; reference: the changelog-restore contract,
# KProcessor.java:30-49)

def _judge_java(msgs):
    from kme_tpu.native.oracle import NativeOracleEngine, native_available

    if not native_available():
        import pytest

        pytest.skip("native toolchain unavailable")
    judge = NativeOracleEngine("java")
    return judge.process_wire([m.copy() for m in msgs])


@pytest.mark.slow
def test_seqjava_checkpoint_mid_stream_resume(cpu_devices, tmp_path):
    """Kill/resume mid-stream: process a prefix on a java-mode
    SeqSession, snapshot, restore into a FRESH session, continue — the
    combined stream is byte-identical to an uninterrupted judge run,
    and the garbage-key position store survives exactly."""
    from kme_tpu.engine import seq as SQ
    from kme_tpu.runtime.checkpoint import (load_seq_session,
                                            save_seq_session)
    from kme_tpu.runtime.seqsession import SeqSession

    cfg = _java_cfg()
    msgs = _java_stream()
    cut = 1500
    ses = SeqSession(cfg)
    head = ses.process_wire(msgs[:cut])
    save_seq_session(str(tmp_path), ses, cut)

    ses2, offset = load_seq_session(str(tmp_path))
    assert offset == cut
    assert ses2.cfg.compat == "java"
    # store parity incl. Q11 garbage keys before continuing
    want_store = SQ.export_java(cfg, ses.state)
    got_store = SQ.export_java(ses2.cfg, ses2.state)
    assert got_store["positions"] == want_store["positions"]
    tail = ses2.process_wire(msgs[cut:])
    got = [ln for per in head + tail for ln in per]
    want = [ln for per in _judge_java(msgs) for ln in per]
    assert got == want


def test_seqjava_to_native_continuation(cpu_devices):
    """seq-java -> native: snapshot the device session, convert to the
    native engine's dump, continue there — byte-identical to the
    uninterrupted judge."""
    from kme_tpu.native.oracle import NativeOracleEngine, native_available
    from kme_tpu.runtime.javasnap import export_seqjava, to_native_dump
    from kme_tpu.runtime.seqsession import SeqSession

    if not native_available():
        import pytest

        pytest.skip("native toolchain unavailable")
    cfg = _java_cfg()
    msgs = _java_stream(n=2000, seed=13)
    cut = 1200
    ses = SeqSession(cfg)
    head = ses.process_wire(msgs[:cut])
    dump = to_native_dump(export_seqjava(ses))
    eng = NativeOracleEngine("java")
    eng.load_state(dump)
    tail = eng.process_wire([m.copy() for m in msgs[cut:]])
    got = [ln for per in head + tail for ln in per]
    want = [ln for per in _judge_java(msgs) for ln in per]
    assert got == want


def test_native_to_seqjava_continuation(cpu_devices):
    """native -> seq-java: the native engine's checkpoint dump restores
    into a java-mode device session which continues byte-identically."""
    from kme_tpu.native.oracle import NativeOracleEngine, native_available
    from kme_tpu.runtime.javasnap import from_native_dump, import_seqjava

    if not native_available():
        import pytest

        pytest.skip("native toolchain unavailable")
    cfg = _java_cfg()
    msgs = _java_stream(n=2000, seed=29)
    cut = 1100
    eng = NativeOracleEngine("java")
    head = eng.process_wire([m.copy() for m in msgs[:cut]])
    ses = import_seqjava(cfg, from_native_dump(eng.dump_state()))
    tail = ses.process_wire(msgs[cut:])
    got = [ln for per in head + tail for ln in per]
    want = [ln for per in _judge_java(msgs) for ln in per]
    assert got == want


def test_seqjava_snapshot_refuses_fixed_restore(cpu_devices, tmp_path):
    """Engine-kind mismatches surface as SnapshotCapacityError /
    ValueError, never silent fallback."""
    import pytest

    from kme_tpu.engine import seq as SQ
    from kme_tpu.runtime.checkpoint import (SnapshotCapacityError,
                                            load_seq_session,
                                            save_seq_session)
    from kme_tpu.runtime.seqsession import SeqSession

    cfg = _java_cfg()
    ses = SeqSession(cfg)
    ses.process_wire(_java_stream(n=400))
    save_seq_session(str(tmp_path), ses, 400)
    with pytest.raises(SnapshotCapacityError):
        load_seq_session(str(tmp_path),
                         SQ.SeqConfig(lanes=8, slots=512, accounts=128,
                                      max_fills=128, batch=512,
                                      pos_cap=1 << 12, probe_max=16))


def test_seqjava_service_kill_resume(cpu_devices, tmp_path):
    """Durable java-mode seq SERVING: a MatchService with engine='seq'
    compat='java' checkpoints mid-stream and a fresh service resumes
    from the snapshot, producing the byte-exact at-least-once stream."""
    from kme_tpu.bridge.broker import InProcessBroker
    from kme_tpu.bridge.provision import provision
    from kme_tpu.bridge.service import MatchService
    from kme_tpu.wire import dumps_order

    msgs = _java_stream(n=1400, seed=3)
    ck = str(tmp_path / "ck")
    broker = InProcessBroker(str(tmp_path / "log"))
    provision(broker)
    for m in msgs[:900]:
        broker.produce("MatchIn", None, dumps_order(m))
    kw = dict(engine="seq", compat="java", symbols=8, accounts=128,
              slots=512, max_fills=128, batch=256, checkpoint_dir=ck,
              checkpoint_every=256)
    svc = MatchService(broker, **kw)
    while svc.step(timeout=0.05):
        pass
    n_first = sum(1 for _ in broker.fetch("MatchOut", 0, 10**9))
    del svc   # "crash" after an arbitrary number of checkpoints
    for m in msgs[900:]:
        broker.produce("MatchIn", None, dumps_order(m))
    svc2 = MatchService(broker, **kw)
    while svc2.step(timeout=0.05):
        pass
    out = [f"{r.key} {r.value}"
           for r in broker.fetch("MatchOut", 0, 10**9)]
    groups = _judge_java(msgs)
    # at-least-once: first-run output for msgs[:900] stands; the
    # resumed service replays from its snapshot offset k <= 900 and the
    # replayed+new segment must be byte-exact for msgs[k:]
    assert out[:n_first] == [ln for per in groups[:900] for ln in per]
    tail = out[n_first:]
    ok = any(tail == [ln for per in groups[k:] for ln in per]
             for k in range(901))
    assert ok, "replayed stream is not an exact judge segment"


@pytest.mark.parametrize("pipeline", PIPELINES)
def test_journal_across_crash_resume(pipeline, tmp_path):
    """Flight-recorder round-trip over a crash/resume cycle: the
    service replays the post-snapshot tail (at-least-once), but the
    journal rewinds to the snapshot offset first — so the final
    journal holds every lifecycle event exactly once, with strictly
    monotonic sequence numbers, and byte-agrees (canonical form) with
    an independent oracle replay of the whole input stream."""
    from kme_tpu.telemetry.journal import (canonical_lines,
                                           oracle_events, read_events)

    jp = str(tmp_path / "journal.jsonl")
    _, msgs, served, snap = _crashed_and_resumed(
        _seq_service(tmp_path, pipeline, journal=jp))
    assert served > snap        # journaled records past the snapshot

    evs = read_events(jp)
    seqs = [e["seq"] for e in evs]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    # exactly-once despite the at-least-once input replay
    offs = [e["off"] for e in evs if e["e"] == "submit"]
    assert offs == list(range(len(msgs)))
    want = canonical_lines(oracle_events(
        [dumps_order(m) for m in msgs], book_slots=128, max_fills=32))
    assert canonical_lines(evs) == want


# ---------------------------------------------------------------------------
# corrupt-newest-snapshot fallback (silent corruption, not just torn
# writes) and retention depth


def _tamper(path):
    """Alter one value of the state `path` holds and keep its stored
    digest as it was: the file still parses, whatever its format."""
    import json
    import pickle

    import numpy as np

    if path.endswith(".npz"):
        # np.load still parses it (so the zipfile's CRCs pass)
        with np.load(path) as z:
            data = {k: z[k].copy() for k in z.files}
        data["bal"].flat[0] += 1              # one balance, one tick off
        with open(path, "wb") as f:
            np.savez(f, **data)
    elif path.endswith(".nat"):
        with open(path, encoding="utf-8") as f:
            header, dump = f.readline(), f.read()
        at = next(i for i, c in enumerate(dump) if c.isdigit())
        dump = dump[:at] + str((int(dump[at]) + 1) % 10) + dump[at + 1:]
        with open(path, "w", encoding="utf-8") as f:
            f.write(header + dump)
        assert json.loads(header)["digest"]
    else:
        # a bit-flip INSIDE the pickled engine bytes leaves the outer
        # blob parseable: only the engine_pkl sha256 can catch it
        with open(path, "rb") as f:
            raw = bytearray(f.read())
        engine_pkl = pickle.loads(bytes(raw))["engine_pkl"]
        raw[raw.index(engine_pkl) + len(engine_pkl) // 2] ^= 0x10
        with open(path, "wb") as f:
            f.write(raw)
        assert pickle.loads(bytes(raw))["engine_pkl"] != engine_pkl


@pytest.mark.parametrize("kind", WRITERS)
def test_digest_mismatch_snapshot_falls_back(kind, tmp_path):
    """Silent corruption: the newest snapshot still parses but one
    value of its state was modified while its stored digest went stale
    — the CONTENT digest must catch it and the loader falls back to the
    previous snapshot."""
    _, _, load, newest = _two_snapshots(kind, str(tmp_path))
    _tamper(newest)
    resumed, offset = load(str(tmp_path))
    assert offset == 100 and resumed is not None
    if newest.endswith(".npz"):
        with pytest.raises(ValueError, match="digest mismatch"):
            ck._load_file(newest)
    elif newest.endswith(".pkl"):
        with pytest.raises(ValueError, match="digest mismatch"):
            ck.load_oracle_file(newest)


@pytest.mark.parametrize("kind", WRITERS)
def test_all_snapshots_corrupt_cold_start(kind, tmp_path):
    """Every snapshot unreadable: the loader returns (None, 0) rather
    than raising, and a service of that engine pointed at the wreckage
    starts cold at offset 0 and replays the whole stream byte-exactly."""
    ck_dir = str(tmp_path / "ck")
    _, msgs, load, _ = _two_snapshots(kind, ck_dir, at=(50, 100))
    assert _offsets(ck_dir) == [100, 50]
    for _, path in ck.all_snapshots(ck_dir):
        with open(path, "r+b") as f:
            f.truncate(64)
    assert load(ck_dir) == (None, 0)

    msgs = msgs[:80]
    want = [ln for lines in _serve(_writer(kind)[0], msgs) for ln in lines]
    broker = InProcessBroker()
    provision(broker)
    for m in msgs:
        broker.produce(TOPIC_IN, None, dumps_order(m))
    how = (dict(engine="seq", compat="java", slots=512, max_fills=128)
           if kind == "java" else
           dict(engine={"fixed-native": "seq"}.get(kind, kind),
                compat="fixed", slots=128, max_fills=16))
    svc = MatchService(broker, batch=16, symbols=8, accounts=128,
                       checkpoint_dir=ck_dir, checkpoint_every=1000,
                       **how)
    assert svc.offset == 0                 # cold start, not a crash
    assert svc.run(max_messages=len(msgs)) == len(msgs)
    got = [f"{r.key} {r.value}" for r in broker.fetch("MatchOut", 0, 10**6)]
    assert got == want


@pytest.mark.parametrize("kind", WRITERS)
def test_retention_keep_depth(kind, tmp_path, monkeypatch):
    """keep= bounds the snapshot tail; KME_CKPT_KEEP sets the default
    (3 — newest + two fallbacks, since kme-chaos both tears AND
    bit-flips)."""
    eng, msgs, save, _ = _writer(kind)
    _serve(eng, msgs[:50])

    d1 = str(tmp_path / "explicit")
    for off in (10, 20, 30, 40):
        save(d1, eng, off, keep=2)
    assert _offsets(d1) == [40, 30]

    d2 = str(tmp_path / "default")
    monkeypatch.delenv("KME_CKPT_KEEP", raising=False)
    for off in (10, 20, 30, 40, 50):
        save(d2, eng, off)
    assert _offsets(d2) == [50, 40, 30]

    d3 = str(tmp_path / "env")
    monkeypatch.setenv("KME_CKPT_KEEP", "1")
    for off in (10, 20):
        save(d3, eng, off)
    assert _offsets(d3) == [20]


@pytest.mark.parametrize("kind", WRITERS)
def test_snapshot_extra_meta_round_trips(kind, tmp_path):
    """The additive `extra` dict (the exactly-once epoch/out_seq
    cursor) survives every snapshot kind, and degrades to {} when
    absent."""
    d = str(tmp_path)
    eng, msgs, save, load = _writer(kind)
    _serve(eng, msgs[:50])
    save(d, eng, 40, extra={"epoch": 3, "out_seq": 99})
    assert ck.snapshot_extra(d, 40) == {"epoch": 3, "out_seq": 99}
    save(d, eng, 50)                           # no extra stored
    assert ck.snapshot_extra(d, 50) == {}
    assert ck.snapshot_extra(d, 999) == {}     # no snapshot at all
    # ...and the snapshot still restores normally alongside the meta
    resumed, offset = load(d)
    assert offset == 50
    assert resumed.export_state() == eng.export_state()


@pytest.mark.parametrize("kind", WRITERS)
def test_oldest_retained_offset_tracks_pruning(kind, tmp_path):
    """The journal retention guard's anchor: the smallest snapshot
    offset on disk, across snapshot kinds, moving forward as `keep`
    prunes old snapshots."""
    d = str(tmp_path / "ck")
    assert ck.oldest_retained_offset(d) is None        # no dir yet
    eng, _, save, _ = _writer(kind)
    save(d, eng, 128)
    save(d, eng, 64)
    assert ck.oldest_retained_offset(d) == 64
    other = _writer("native" if kind == "oracle" else "oracle")
    other[2](d, other[0], 32)                          # other kind
    assert ck.oldest_retained_offset(d) == 32
    save(d, eng, 192, keep=2)                          # prunes 64
    assert _offsets(d) == [192, 128, 32]
    assert ck.oldest_retained_offset(d) == 32          # other untouched


# ---------------------------------------------------------------------------
# fixed-mode seq snapshots: the books and the positions each written by
# their live entries where that is the smaller encoding
# (engine/seq.py:export_snapshot), densified by the one loader

# what export_snapshot says of its device -> host half
FETCH_GAUGES = ("snapshot_fetch_bytes", "snapshot_live_rows",
                "snapshot_fetch_calls", "snapshot_pos_fetch_bytes",
                "snapshot_pos_calls")


def _random_canon(shape, book_load, pos_load, amount=True, seed=5,
                  packed=False):
    """A canonical state with those shares of its slots and positions
    live — and something in EVERY word of every dead slot, as a slot
    freed by a fill or a cancel keeps what it held. The live slots are
    scattered, or `packed` from slot 0 of each side to a depth of its
    own (as the kernel leaves them: a resting order takes the lowest
    free slot of its side)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    S, N, A = shape["lanes"], shape["slots"], shape["accounts"]
    if packed:
        used = np.arange(N) < rng.random((S, 2, 1)) * 2 * book_load * N
    else:
        used = rng.random((S, 2, N)) < book_load
    live = rng.random(S * A) < pos_load

    def words(hi, shape, dtype):
        return rng.integers(1, hi, shape).astype(dtype)

    return {
        "slot_oid": words(1 << 53, (S, 2, N), np.int64),
        "slot_aid": words(A, (S, 2, N), np.int32),
        "slot_price": words(126, (S, 2, N), np.int32),
        "slot_size": words(1000, (S, 2, N), np.int32),
        "slot_seq": words(1 << 20, (S, 2, N), np.int32),
        "slot_used": used,
        "seq": words(1 << 20, S, np.int32),
        "book_exists": rng.random(S) < 0.7,
        # an amount of 0 beside an available balance is a position too
        "pos_amt": np.where(live & amount, rng.integers(-10**12, 10**12,
                                                        S * A), 0),
        "pos_avail": np.where(live, words(10**12, S * A, np.int64), 0),
        "bal": rng.integers(-10**15, 10**15, A),
        "bal_used": rng.random(A) < 0.5,
        "err": np.int32(0),
    }


# name -> (shape, state or None for a fresh session, sparse sections)
SPARSE_CASES = {
    "hbm-books": (dict(lanes=4, slots=1024, accounts=256, max_fills=16,
                       hbm_books=True), (0.02, 0.05), 2),
    # 128 accounts in tiles of 256: half of every position tile is
    # padding (a book plane has none: slots % 128 == 0)
    "vmem-books-padded-tiles": (SMALL, (0.1, 0.1), 2),
    "empty": (SMALL, None, 2),
    "amount-0-available-not": (SMALL, (0.1, 0.1, False), 2),
    "past-the-break-even": (SMALL, (0.9, 0.8), 0),
    "full-books-thin-positions": (SMALL, (1.0, 0.01), 1),
    # a lane count that is no multiple of 8, accounts in a tile and a
    # half: the round trip save -> _load_file -> import (PR 48)
    "40-lanes-384-accounts": (dict(lanes=40, slots=128, accounts=384,
                                   max_fills=16), (0.05, 0.02), 2),
}


@pytest.mark.parametrize("case", SPARSE_CASES)
def test_sparse_snapshot_loads_as_the_canonical_state(case, tmp_path):
    """Whatever the encoding, _load_file hands on export_canonical's
    state: equal on every live slot and position, zero on every dead
    one; a section past its break-even comes out dense. Every file is
    version 3 (the routes are arrays), with or without a `layout`."""
    import json

    import numpy as np

    from kme_tpu.engine import seq as SQ

    shape, loads, n_sparse = SPARSE_CASES[case]
    state = None if loads is None else _random_canon(shape, *loads)
    ses = _seq_session(state, **shape)
    path = ck.save_seq_session(str(tmp_path), ses, 7)
    want = SQ.export_canonical(ses.cfg, ses.state)
    used = want["slot_used"]
    if loads is not None:       # what a dense file would have carried
        assert want["slot_oid"][~used].all() and used.any()
        assert want["pos_avail"].any()
    if case == "amount-0-available-not":
        assert not want["pos_amt"].any()

    with np.load(path) as z:
        raw = {k: z[k] for k in z.files}
    meta = json.loads(bytes(raw["meta"]).decode())
    sparse = meta.get("layout", {}).get("sparse", [])
    assert len(sparse) == n_sparse
    assert meta["version"] == 3 and ("layout" in meta) == bool(sparse)
    assert ("slot_idx" in raw) == ("books" in sparse) \
        == ("slot_used" not in raw)
    assert ("pos_idx" in raw) == ("positions" in sparse)
    if "books" in sparse:
        assert raw["slot_idx"].tolist() == np.flatnonzero(used).tolist()
        assert all(raw[k].shape == raw["slot_idx"].shape
                   for k in SQ.SPARSE_SECTIONS["books"])
    if "positions" in sparse:
        assert raw["pos_idx"].tolist() == np.flatnonzero(
            (want["pos_amt"] != 0) | (want["pos_avail"] != 0)).tolist()

    data, meta = ck._load_file(path)
    for k, v in want.items():
        if v is None:
            continue
        v = np.asarray(v)
        assert data[k].shape == v.shape and data[k].dtype == v.dtype, k
        if k.startswith("slot_") and "books" in sparse:
            assert np.array_equal(data[k][used], v[used]), k
            assert not data[k][~used].any(), k
        else:
            assert np.array_equal(data[k], v), k
    gauges = dict(ses.snapshot_gauges)
    fetch = {k: gauges.pop(k) for k in FETCH_GAUGES}
    live_rows = int((np.asarray(ses.state["bs"]) > 0).any(axis=1).sum())
    assert fetch["snapshot_live_rows"] == live_rows
    assert (fetch["snapshot_fetch_calls"] > 0) \
        == (4 * live_rows <= 2 * ses.cfg.lanes * ses.cfg.nr)
    assert (fetch["snapshot_pos_calls"] > 0) == ("positions" in sparse)
    assert gauges == {
        "snapshot_bytes": os.path.getsize(path),
        "snapshot_routes": 0,
        "snapshot_live_slots": int(used.sum()),
        "snapshot_live_positions": int(
            ((want["pos_amt"] != 0) | (want["pos_avail"] != 0)).sum()),
        "snapshot_sparse_sections": n_sparse,
        # routes in the file beyond its resting orders (this state was
        # planted on the device: no router ever saw its orders)
        "stale_routes": -int(used.sum())}
    # and import_canonical lays the file out as the device held it (a
    # dead slot's words, which are no state, come back 0)
    back = SQ.import_canonical(ses.cfg, data)
    live = np.asarray(ses.state["bs"]) > 0
    for k in SQ._STATE_KEYS:
        got, held = np.asarray(back[k]), np.asarray(ses.state[k])
        if k in SQ.BOOK_KEYS:
            got, held = got[live], held[live]
        assert np.array_equal(got, held), k


def _reuse_stream():
    """Books that fill, thin out by cancels and fills, and fill again:
    `cut` is a point where every side has freed slots that still hold
    the dead order's words; what follows rests new orders in those
    slots at the old makers' prices (time priority across the cut),
    sweeps old and new makers together and cancels live, dead and
    unknown oids."""
    import kme_tpu.opcodes as op
    from kme_tpu.wire import OrderMsg

    accounts, symbols = 16, 4
    msgs = []
    for a in range(accounts):
        msgs += [OrderMsg(action=op.CREATE_BALANCE, aid=a),
                 OrderMsg(action=op.TRANSFER, aid=a, size=10**7)]
    msgs += [OrderMsg(action=op.ADD_SYMBOL, sid=s) for s in range(symbols)]
    oid = 1000
    resting = {s: [] for s in range(symbols)}

    def order(action, aid, sid, price, size):
        nonlocal oid
        oid += 1
        msgs.append(OrderMsg(action=action, oid=oid, aid=aid % accounts,
                             sid=sid, price=price, size=size))
        return oid, aid % accounts

    def cancel(orders):
        msgs.extend(OrderMsg(action=op.CANCEL, oid=o, aid=a)
                    for o, a in orders)

    def rest(s, k, base):
        for i in range(k):
            resting[s].append(order(op.SELL, base + i, s, 60 + i % 30, 5))
            resting[s].append(order(op.BUY, base + i + 1, s, 40 - i % 30,
                                    5))

    def thin(s):
        cancel(resting[s][::3])
        order(op.BUY, 3, s, 63, 37)      # sweeps 60..63, one partly
        order(op.SELL, 5, s, 38, 23)

    for s in range(symbols):
        rest(s, 45, s)
        thin(s)
    cut = len(msgs)
    for s in range(symbols):
        rest(s, 30, s + 7)               # into the freed slots
        order(op.BUY, 9, s, 70, 160)     # old and new makers, in order
        order(op.SELL, 11, s, 30, 160)
        cancel(resting[s][:12])          # live, cancelled and filled
        cancel([(7, 0), resting[s][13][::-1]])      # unknown; not its own
        thin(s)
    return msgs, cut


def _exactly_once(tmp_path, name, msgs):
    broker = InProcessBroker(persist_dir=str(tmp_path / (name + "-log")))
    provision(broker)
    for m in msgs:
        broker.produce(TOPIC_IN, None, dumps_order(m))
    kw = dict(engine="seq", compat="fixed", symbols=8, accounts=128,
              batch=128, checkpoint_dir=str(tmp_path / (name + "-ck")),
              exactly_once=True, checkpoint_every=10**9,
              **{k: SMALL[k] for k in ("slots", "max_fills")})
    return broker, kw


def test_resume_from_sparse_snapshot_reuses_freed_slots(tmp_path):
    """A dense file carried what a freed slot last held and the sparse
    one restores zeros there: served past a restore through orders that
    take those slots, MatchOut is byte-equal to the uninterrupted run
    and to the oracle, with no stamp twice."""
    import numpy as np

    from kme_tpu.bridge.consume import DedupRing
    from kme_tpu.bridge.service import TOPIC_OUT
    from kme_tpu.engine import seq as SQ
    from kme_tpu.native.oracle import NativeOracleEngine

    msgs, cut = _reuse_stream()
    b0, kw0 = _exactly_once(tmp_path, "whole", msgs)
    assert MatchService(b0, **kw0).run(max_messages=len(msgs)) == len(msgs)
    whole = list(consume_lines(b0, follow=False))
    ref = NativeOracleEngine("fixed", book_slots=128, max_fills=16)
    assert whole == [ln for g in ref.process_wire(
        [m.copy() for m in msgs]) for ln in g]

    b1, kw = _exactly_once(tmp_path, "cut", msgs)
    svc = MatchService(b1, **kw)
    cut = svc.run(max_messages=cut)             # whole batches
    assert cut == svc.offset < len(msgs) - 128
    svc.checkpoint()
    canon = SQ.export_canonical(svc._session.cfg, svc._session.state)
    dead = ~canon["slot_used"]
    # freed slots keep the dead order's words, on every side in use
    assert (canon["slot_oid"][dead] != 0).sum() >= 6 * 15
    _, meta = ck._load_file(ck.snapshot_path(kw["checkpoint_dir"], cut))
    assert meta["version"] == 3 and len(meta["layout"]["sparse"]) == 2
    assert svc.run(max_messages=128) == 128     # past the snapshot...
    del svc                                     # ...and killed

    b2 = InProcessBroker(persist_dir=str(tmp_path / "cut-log"))
    svc2 = MatchService(b2, **kw)
    assert svc2.offset == cut and svc2.epoch == 2
    restored = SQ.export_canonical(svc2._session.cfg, svc2._session.state)
    assert not restored["slot_oid"][dead].any()
    assert np.array_equal(restored["slot_oid"][~dead],
                          canon["slot_oid"][~dead])
    rest = len(msgs) - cut
    assert svc2.run(max_messages=rest) == rest
    assert list(consume_lines(b2, follow=False)) == whole
    ring = DedupRing(capacity=1 << 20)
    recs = b2.fetch(TOPIC_OUT, 0, 10**7)
    assert b2.dup_suppressed > 0
    assert not any(ring.is_dup(r.epoch, r.out_seq) for r in recs)


@pytest.mark.parametrize("name,offset,seed", [
    ("seq_pre_pr29.npz", 700, 11),      # hash planes in its meta
    ("seq_dense_pr34.npz", 600, 12),    # written by the parent, ef872a6
])
def test_dense_files_of_older_writers_restore(name, offset, seed, tmp_path):
    """A dense version-1 file — `git archive ef872a6`'s save_seq_session
    wrote seq_dense_pr34.npz at offset 600 of the stream below —
    restores under the loader that also reads sparse ones, and the
    session finishes the stream byte-exact."""
    import shutil

    from kme_tpu.engine import seq as SQ
    from kme_tpu.native.oracle import NativeOracleEngine

    msgs = list(zipf_symbol_stream(900, 8, 64, seed=seed, zipf_a=0.0))
    shutil.copy(os.path.join(HERE, "data", name),
                ck.snapshot_path(str(tmp_path), offset))
    _, meta = ck._load_file(ck.snapshot_path(str(tmp_path), offset))
    assert meta["version"] == 1 and "layout" not in meta
    want = NativeOracleEngine("fixed", book_slots=128, max_fills=16
                              ).process_wire([m.copy() for m in msgs])
    for cfg in (None, SQ.SeqConfig(**SMALL)):
        ses, off = ck.load_seq_session(str(tmp_path), cfg)
        assert off == offset
        assert ses.process_wire([m.copy() for m in msgs[offset:]]) \
            == want[offset:]


def _two_sparse_snapshots(tmp_path, at=(400, 600)):
    msgs = list(zipf_symbol_stream(900, 8, 64, seed=12, zipf_a=0.0))
    ses, done = _seq_session(**SMALL), 0
    for off in at:
        ses.process_wire([m.copy() for m in msgs[done:off]])
        ck.save_seq_session(str(tmp_path), ses, off)
        assert ses.snapshot_gauges["snapshot_sparse_sections"] == 2
        done = off
    return msgs


@pytest.mark.parametrize("damage", ["bitflip", "torn"])
def test_damaged_sparse_snapshot_falls_back(damage, tmp_path):
    """The digest is over the file as written: one value of one live
    slot altered, or the file cut short, and the loader takes the
    snapshot before it."""
    import numpy as np

    from kme_tpu.engine import seq as SQ

    msgs = _two_sparse_snapshots(tmp_path)
    path = ck.snapshot_path(str(tmp_path), 600)
    if damage == "torn":
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) // 2)
    else:
        with np.load(path) as z:
            data = {k: z[k].copy() for k in z.files}
        data["slot_price"][0] ^= 1          # digest array kept STALE
        with open(path, "wb") as f:
            np.savez(f, **data)
        with pytest.raises(ValueError, match="digest mismatch"):
            ck._load_file(path)
    ses, off = ck.load_seq_session(str(tmp_path), SQ.SeqConfig(**SMALL))
    assert off == 400
    ora = OracleEngine("fixed", book_slots=128, max_fills=16)
    per_msg = [[r.wire() for r in ora.process(m.copy())] for m in msgs]
    assert ses.process_wire([m.copy() for m in msgs[off:]]) == per_msg[off:]


def test_snapshot_gauges_read_what_the_file_holds(tmp_path):
    """The serve loop publishes the newest snapshot's size and live
    counts with the batch's other gauges; none before the first
    snapshot."""
    import numpy as np

    msgs, cut = _reuse_stream()
    broker, kw = _exactly_once(tmp_path, "g", msgs)
    svc = MatchService(broker, **kw)
    cut = svc.run(max_messages=cut)
    assert "snapshot_bytes" not in svc.telemetry.snapshot()["gauges"]
    svc.checkpoint()
    svc._publish_spans()
    gauges = svc.telemetry.snapshot()["gauges"]
    path = ck.snapshot_path(kw["checkpoint_dir"], cut)
    with np.load(path) as z:
        assert gauges["snapshot_live_slots"] == len(z["slot_idx"]) \
            == svc.metrics()["open_orders"] > 0
        assert gauges["snapshot_live_positions"] == len(z["pos_idx"]) > 0
    assert gauges["snapshot_bytes"] == os.path.getsize(path)
    assert gauges["snapshot_sparse_sections"] == 2
    assert gauges["snapshot_export_n"] == gauges["snapshot_write_n"] == 1
    # SMALL's sides are one row deep and most hold an order: the books
    # crossed whole, and the loop says so
    assert gauges["snapshot_fetch_calls"] == 0
    assert gauges["snapshot_live_rows"] > 4
    assert gauges["snapshot_fetch_bytes"] > 6 * 16 * 512


# ---------------------------------------------------------------------------
# the books fetched by their live rows (engine/seq.py:
# build_seq_live_rows): whatever crosses, the arrays are those of the
# dense fetch and the host's pass over every slot

# sides 32 rows deep: 512 rows a plane, 16 a call, 128 the most that
# still cross by rows
DEEP = dict(lanes=8, slots=4096, accounts=128, max_fills=16,
            hbm_books=True)


def _dense_export(cfg, state):
    """export_snapshot as the parent (617a85d) wrote it: every plane
    brought to the host, one pass over `bs`, six gathers."""
    import numpy as np

    from kme_tpu.engine import seq as SQ

    S, N, A = cfg.lanes, cfg.slots, cfg.accounts
    h = {k: np.asarray(state[k]) for k in SQ._STATE_KEYS if k != "dep"}
    canon = SQ._small_sections(cfg, h)
    layout = {"slot_shape": [S, 2, N], "pos_size": S * A, "sparse": []}
    idx = np.flatnonzero(h["bs"].reshape(-1) > 0)
    layout["live_slots"] = int(idx.size)
    if idx.size * SQ._SLOT_LIVE_B < S * 2 * N * SQ._SLOT_DENSE_B:
        def at(k):
            return h[k].reshape(-1)[idx]

        canon.update(slot_idx=idx,
                     slot_oid=SQ._j64(at("bo_lo"), at("bo_hi")),
                     slot_aid=at("ba"), slot_price=at("bp"),
                     slot_size=at("bs"), slot_seq=at("bq"))
        layout["sparse"].append("books")
    else:
        canon.update(SQ._dense_books(cfg, h))
    PTL = cfg.pos_tiles_per_lane
    rows = h["pos"].reshape(S, PTL, 2, 4, SQ.LN)
    lane, acct = np.divmod(np.flatnonzero(rows.any(axis=3)),
                           PTL * SQ.POS_TILE_ACCOUNTS)
    lane, acct = lane[acct < A], acct[acct < A]
    layout["live_positions"] = int(lane.size)
    if lane.size * SQ._POS_LIVE_B < S * A * SQ._POS_DENSE_B:
        def word(k):
            return rows[lane, acct >> 8, (acct >> 7) & 1, k,
                        acct & (SQ.LN - 1)]

        canon.update(pos_idx=lane * A + acct,
                     pos_amt=SQ._j64(word(0), word(1)),
                     pos_avail=SQ._j64(word(2), word(3)))
        layout["sparse"].append("positions")
    else:
        canon.update(SQ._dense_positions(cfg, h))
    return canon, layout


def _pos_fetch_of(cfg, state, calls):
    """Bytes the positions' fetch of `calls` calls brings device ->
    host: the count and one chunk (indices + four words an entry) a
    call, and the plane whole where no call brought them (the first
    call's chunk crossed before that was known)."""
    import numpy as np

    from kme_tpu.engine import seq as SQ

    chunk = 4 + SQ.live_positions_chunk(cfg) * (4 + 4 * 4)
    return chunk * max(calls, 1) - 4 * (max(calls, 1) - 1) + (
        0 if calls else np.asarray(state["pos"]).nbytes)


def _fetch_of(cfg, state, calls, pos_calls):
    """Bytes a fetch of `calls` calls for the books and `pos_calls` for
    the positions brings device -> host: the small sections, the
    positions' share, the count and one chunk (row indices + six
    planes' rows) a call, and the planes whole where no call brought
    the books (the first call's chunk crossed before that was known)."""
    import numpy as np

    from kme_tpu.engine import seq as SQ

    R = SQ.live_rows_chunk(cfg)
    rest = sum(np.asarray(state[k]).nbytes for k in SQ._STATE_KEYS
               if k not in SQ.BOOK_KEYS + ("dep", "pos"))
    planes = sum(np.asarray(state[k]).nbytes for k in SQ.BOOK_KEYS)
    chunk = R * 4 + len(SQ.BOOK_KEYS) * R * SQ.LN * 4
    return (rest + _pos_fetch_of(cfg, state, pos_calls) + 4
            + chunk * max(calls, 1) + (0 if calls else planes))


def _same_export(cfg, state):
    """export_snapshot against the dense fetch and the host's pass:
    array for array, dtype for dtype, one digest. -> fetch."""
    import numpy as np

    from kme_tpu.engine import seq as SQ

    want, want_layout = _dense_export(cfg, state)
    canon, layout, fetch = SQ.export_snapshot(cfg, state)
    assert layout == want_layout
    assert list(canon) == list(want)
    for k, v in want.items():
        got = np.asarray(canon[k])
        assert got.dtype == np.asarray(v).dtype, k
        assert np.array_equal(got, v), k
    assert ck._payload_digest(canon) == ck._payload_digest(want)
    rows = 2 * cfg.lanes * cfg.nr
    live_rows = int((np.asarray(state["bs"]) > 0).any(axis=1).sum())
    R = SQ.live_rows_chunk(cfg)
    calls = max(-(-live_rows // R), 1) if 4 * live_rows <= rows else 0
    live, K = layout["live_positions"], SQ.live_positions_chunk(cfg)
    pos_calls = max(-(-live // K), 1) if "positions" in layout["sparse"] \
        else 0
    assert fetch == {
        "snapshot_fetch_bytes": _fetch_of(cfg, state, calls, pos_calls),
        "snapshot_live_rows": live_rows,
        "snapshot_fetch_calls": calls,
        "snapshot_pos_fetch_bytes": _pos_fetch_of(cfg, state, pos_calls),
        "snapshot_pos_calls": pos_calls}
    return fetch


# name -> (book_load, packed)
BOOK_CASES = {
    "empty": (0.0, False),
    "scattered-33-slots": (0.0005, False),
    "scattered-thin": (0.001, False),
    "scattered-a-hundredth": (0.01, False),
    "scattered-half": (0.5, False),
    "scattered-past-the-break-even": (0.9, False),
    "full": (1.0, False),
    "packed-shallow": (0.002, True),
    "packed": (0.02, True),
    "packed-deep": (0.3, True),
}


@pytest.mark.parametrize("shape", ["deep", "small"])
@pytest.mark.parametrize("case", BOOK_CASES)
def test_books_fetched_by_rows_are_the_dense_fetchs_arrays(case, shape):
    """Scattered or packed from slot 0, few live rows or all of them,
    sides 32 rows deep or one: (canon, layout) and the digest are those
    of the parent's dense fetch + host pass, and `fetch` counts the
    bytes that crossed."""
    from kme_tpu.engine import seq as SQ

    shape = DEEP if shape == "deep" else SMALL
    load, packed = BOOK_CASES[case]
    cfg = SQ.SeqConfig(**shape)
    state = SQ.import_canonical(
        cfg, _random_canon(shape, load, 0.05, packed=packed))
    fetch = _same_export(cfg, state)
    if shape is DEEP and case in ("empty", "packed-shallow"):
        assert fetch["snapshot_fetch_calls"] == 1
    if shape is DEEP and case in ("scattered-half", "full", "packed-deep"):
        assert fetch["snapshot_fetch_calls"] == 0       # crossed whole


@pytest.mark.parametrize("case", ["scattered", "packed", "full-side"])
def test_more_live_rows_than_a_call_returns_take_more_calls(case):
    """One program, called again from the row after the last one it
    returned: the same bytes in 2 calls or more. A side that is full
    (32 rows of one lane) still crosses by its rows."""
    import numpy as np

    from kme_tpu.engine import seq as SQ

    cfg = SQ.SeqConfig(**DEEP)
    canon = _random_canon(DEEP, *{"scattered": (0.001, 0.05),
                                  "packed": (0.02, 0.05),
                                  "full-side": (0.002, 0.05)}[case],
                          packed=case != "scattered")
    if case == "full-side":
        canon["slot_used"][3, 1, :] = True
    fetch = _same_export(cfg, SQ.import_canonical(cfg, canon))
    assert fetch["snapshot_live_rows"] > SQ.live_rows_chunk(cfg) == 16
    assert fetch["snapshot_fetch_calls"] >= 2
    if case == "full-side":
        assert fetch["snapshot_live_rows"] >= 32
        assert np.asarray(canon["slot_used"][3, 1]).all()


def test_an_empty_book_is_sparse_and_a_packed_one_crosses_a_tenth():
    """0 live rows: `slot_idx` is empty and the section is still
    written by its live entries. A packed book at a depth worth
    compacting brings under a tenth of what the planes weigh."""
    import numpy as np

    from kme_tpu.engine import seq as SQ

    cfg = SQ.SeqConfig(**DEEP)
    canon, layout, fetch = SQ.export_snapshot(cfg, SQ.make_seq_state(cfg))
    assert fetch["snapshot_live_rows"] == 0
    assert fetch["snapshot_fetch_calls"] == 1
    assert canon["slot_idx"].shape == (0,) \
        and canon["slot_idx"].dtype == np.int64
    assert layout["sparse"] == ["books", "positions"]
    state = SQ.import_canonical(
        cfg, _random_canon(DEEP, 0.002, 0.05, packed=True))
    fetch = _same_export(cfg, state)
    assert fetch["snapshot_fetch_calls"] == 1
    assert fetch["snapshot_fetch_bytes"] * 10 < _fetch_of(cfg, state, 0, 1)


# ---------------------------------------------------------------------------
# the positions fetched by their live entries (engine/seq.py:
# build_seq_live_positions, PR 48): whatever crosses, the arrays are
# those of the dense fetch and the host's pass over every word

# 10 lanes x 4096 accounts: 40,960 entries, 8,192 a call
WIDE = dict(lanes=10, slots=128, accounts=4096, max_fills=16)
# 384 accounts in two tiles of 256: a padding half-tile a lane
PADDED = dict(lanes=5, slots=128, accounts=384, max_fills=16)


def _with_positions(shape, flat_idx, amount=True, seed=9):
    """A state whose position store holds exactly the entries
    `flat_idx` (lane * A + account)."""
    import numpy as np

    from kme_tpu.engine import seq as SQ

    rng = np.random.default_rng(seed)
    canon = _random_canon(shape, 0.01, 0.0)
    n = len(flat_idx)
    if amount:
        canon["pos_amt"][flat_idx] = rng.integers(1, 10**12, n)
    canon["pos_avail"][flat_idx] = rng.integers(1, 10**12, n)
    cfg = SQ.SeqConfig(**shape)
    return cfg, SQ.import_canonical(cfg, canon)


def _spread(shape, n, seed=4):
    import numpy as np

    size = shape["lanes"] * shape["accounts"]
    return np.sort(np.random.default_rng(seed).choice(size, n,
                                                      replace=False))


# name -> (shape, the live entries' flat indices, calls)
POS_CASES = {
    "empty": (WIDE, lambda: [], 1),
    "one-in-the-last-tile-of-the-last-lane":
        (WIDE, lambda: [10 * 4096 - 3], 1),
    "first-and-last": (PADDED, lambda: [0, 5 * 384 - 1], 1),
    "a-padding-half-tile": (PADDED, lambda: _spread(PADDED, 300), 1),
    "amount-0-available-not": (WIDE, lambda: _spread(WIDE, 500), 1),
    "exactly-a-call": (WIDE, lambda: _spread(WIDE, 8192), 1),
    "a-call-and-one": (WIDE, lambda: _spread(WIDE, 8193), 2),
    "three-calls-and-five": (WIDE, lambda: _spread(WIDE, 3 * 8192 + 5), 4),
    "a-lane-full": (WIDE, lambda: list(range(3 * 4096, 4 * 4096 + 1)), 1),
    "dense-enough-to-cross-whole":
        (WIDE, lambda: _spread(WIDE, 30000), 0),
}


@pytest.mark.parametrize("case", POS_CASES)
def test_positions_fetched_by_entries_are_the_dense_fetchs_arrays(case):
    """Few entries or most, one call or four, a lane's padding
    half-tile holding words the kernel never wrote: (canon, layout)
    and the digest are those of the parent's dense fetch + host pass
    (_dense_export), on either branch, and `fetch` counts the bytes."""
    import jax.numpy as jnp
    import numpy as np

    from kme_tpu.engine import seq as SQ

    shape, entries, calls = POS_CASES[case]
    entries = np.asarray(entries(), np.int64)
    cfg, state = _with_positions(shape, entries,
                                 amount=case != "amount-0-available-not")
    assert SQ.live_positions_chunk(cfg) == (8192 if shape is WIDE else 1920)
    if case == "a-padding-half-tile":
        # the second half of each lane's last tile is no account's
        pos = np.array(state["pos"]).reshape(5, 2, 2, 4, SQ.LN)
        assert not pos[:, 1, 1].any()
        pos[:, 1, 1] = 7
        state["pos"] = jnp.asarray(pos.reshape(-1, SQ.LN))
    fetch = _same_export(cfg, state)
    assert fetch["snapshot_pos_calls"] == calls
    canon, layout, _ = SQ.export_snapshot(cfg, state)
    assert layout["live_positions"] == entries.size
    if calls:
        assert canon["pos_idx"].tolist() == entries.tolist()
        assert canon["pos_idx"].dtype == np.int64
    else:
        assert "pos_idx" not in canon
        assert fetch["snapshot_pos_fetch_bytes"] > np.asarray(
            state["pos"]).nbytes


def test_the_entry_program_continues_from_any_index():
    """Called from an index in the middle of a row, at a live entry,
    after the last one and past the end: the entries at or after it, in
    order, and the store's count whatever the start."""
    import jax
    import numpy as np

    from kme_tpu.engine import seq as SQ

    live = _spread(PADDED, 200)
    cfg, state = _with_positions(PADDED, live)
    size = cfg.lanes * cfg.accounts
    both = SQ.pos_to_values(cfg, np.asarray(state["pos"]))
    for start in (0, int(live[0]), int(live[0]) + 1, int(live[77]),
                  int(live[-1]), int(live[-1]) + 1, size - 1, size):
        n, idx, words = jax.device_get(
            SQ.live_positions_call(cfg, state, start))
        want = live[live >= start]
        assert n == 200
        assert idx[:want.size].tolist() == want.tolist(), start
        assert (idx[want.size:] == size).all()
        lane, acct = np.divmod(want, cfg.accounts)
        words = words[:want.size]
        assert np.array_equal(SQ._j64(words[:, 0], words[:, 1]),
                              both[0, lane, acct])
        assert np.array_equal(SQ._j64(words[:, 2], words[:, 3]),
                              both[1, lane, acct])


@pytest.mark.parametrize("build, gauge", [
    ("build_seq_live_rows", "snapshot_live_rows"),
    ("build_seq_live_positions", "snapshot_live_positions")])
def test_a_snapshot_of_a_serving_session_compiles_nothing(build, gauge,
                                                          tmp_path):
    """The live-row and the live-entry program are built and compiled
    in SeqSession.__init__ (a compile inside a served batch is a
    stall): batches served, two snapshots taken, a restore — no new
    program, no new signature of the one there is."""
    from kme_tpu.engine import seq as SQ

    build = getattr(SQ, build)

    def calls():
        info = build.cache_info()
        return info.hits + info.misses

    n = calls()
    ses = _seq_session(**SMALL)
    assert calls() == n + 1                     # __init__ asked for it
    program = build(ses.cfg)
    misses = build.cache_info().misses
    compiled = program._cache_size()
    assert compiled >= 1                        # and ran it
    msgs = list(zipf_symbol_stream(900, 8, 64, seed=12, zipf_a=0.0))
    done = 0
    for off in (400, 600):
        ses.process_wire([m.copy() for m in msgs[done:off]])
        ck.save_seq_session(str(tmp_path), ses, off)
        assert program._cache_size() == compiled
        done = off
    back, off = ck.load_seq_session(str(tmp_path))
    assert off == 600
    ck.save_seq_session(str(tmp_path / "again"), back, off)
    assert program._cache_size() == compiled
    assert build.cache_info().misses == misses
    assert back.snapshot_gauges[gauge] == ses.snapshot_gauges[gauge] > 0
    assert back.snapshot_gauges["snapshot_pos_calls"] == 1


# ---------------------------------------------------------------------------
# the routes as two int64 arrays of the payload (version 3): no writer
# copies `oid_sid` into a dict, sorts its pairs or prints them as text

def _raw(path):
    """(arrays, meta) of a file as written, nothing converted."""
    import json

    import numpy as np

    with np.load(path) as z:
        raw = {k: z[k] for k in z.files}
    return raw, json.loads(bytes(raw["meta"]).decode())


NPZ_WRITERS = ["fixed-native", "fixed-python", "java"]


@pytest.mark.parametrize("kind", NPZ_WRITERS)
def test_routes_round_trip_as_two_sorted_arrays(kind, tmp_path,
                                                monkeypatch):
    """Every writer puts the routes in the payload, keys ascending and
    values in their order, and none in the meta; the restored router
    holds the same map and serves the same continuation."""
    import numpy as np

    ses, msgs, save, load = _writer(kind, monkeypatch)
    cut = 400
    ses.process_wire([m.copy() for m in msgs[:cut]])
    want = dict(ses.router.oid_sid)
    # a fixed-mode seq router holds the resting orders' routes only
    assert len(want) > (50 if kind.startswith("fixed") else 100)
    raw, meta = _raw(save(str(tmp_path), ses, cut))
    assert meta["version"] == 3 and "oid_sid" not in meta
    for k in ("route_oid", "route_sid"):
        assert raw[k].dtype == np.int64 and raw[k].shape == (len(want),)
    assert raw["route_oid"].tolist() == sorted(want)
    assert raw["route_sid"].tolist() == [want[k] for k in sorted(want)]
    back, off = load(str(tmp_path))
    assert off == cut and type(back.router) is type(ses.router)
    assert dict(back.router.oid_sid) == want
    assert back.process_wire([m.copy() for m in msgs[cut:]]) \
        == ses.process_wire([m.copy() for m in msgs[cut:]])
    assert dict(back.router.oid_sid) == dict(ses.router.oid_sid)


RECORDED = ["seq_pre_pr29.npz", "seq_dense_pr34.npz", "seq_sparse_pr35.npz",
            "seq_routes_json_pr39.npz"]


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_files_restore_the_routes_their_meta_lists(name, tmp_path):
    """Every file written before version 3 lists its routes in the meta
    (`oid_sid`, sorted pairs): the loader hands them on as the arrays a
    new file carries, and the restored router holds that map."""
    import shutil

    src = os.path.join(HERE, "data", name)
    raw, meta = _raw(src)
    assert meta["version"] in (1, 2) and "route_oid" not in raw
    want = {int(k): int(v) for k, v in meta["oid_sid"]}
    assert len(want) > 100
    path = ck.snapshot_path(str(tmp_path), meta["offset"])
    shutil.copy(src, path)
    data, loaded = ck._load_file(path)
    assert "oid_sid" not in loaded
    assert data["route_oid"].tolist() == sorted(want)
    assert data["route_sid"].tolist() == [want[k] for k in sorted(want)]
    ses, off = ck.load_seq_session(str(tmp_path))
    assert off == meta["offset"] and dict(ses.router.oid_sid) == want


def test_the_parents_newest_file_restores_and_serves_on(tmp_path):
    """seq_routes_json_pr39.npz — written by `git archive 89e385f`'s
    save_seq_session (version 2, routes as JSON) at offset 600 of the
    stream below, after a payout freed a lane and purged its routes:
    the guard for the next change of format."""
    import shutil

    import kme_tpu.opcodes as op
    from kme_tpu.native.oracle import NativeOracleEngine
    from kme_tpu.wire import OrderMsg

    msgs = list(zipf_symbol_stream(900, 8, 64, seed=13, zipf_a=0.0))
    msgs.insert(500, OrderMsg(action=op.PAYOUT, sid=5, size=97))
    msgs += [OrderMsg(action=op.ADD_SYMBOL, sid=100),
             OrderMsg(action=op.BUY, oid=5, aid=1, sid=100, price=50,
                      size=2)]
    shutil.copy(os.path.join(HERE, "data", "seq_routes_json_pr39.npz"),
                ck.snapshot_path(str(tmp_path), 600))
    want = NativeOracleEngine("fixed", book_slots=128, max_fills=16
                              ).process_wire([m.copy() for m in msgs])
    ses, off = ck.load_seq_session(str(tmp_path))
    assert off == 600 and 5 not in ses.router.sid_lane
    assert 5 not in set(ses.router.oid_sid.values())
    assert ses.process_wire([m.copy() for m in msgs[600:]]) == want[600:]
    assert ses.router.sid_lane[100] == 5        # the lowest free lane


def test_a_lanes_engines_file_restores_and_serves_on(tmp_path):
    """lanes_pr53.npz — written by `git archive 06d92bd`'s save_session,
    the writer of the sweep engine that PR 54 removed, from a
    LaneSession of LaneConfig(lanes=8, slots=64, accounts=32,
    max_fills=32, steps=16) at offset 400 of the stream below (kind
    "lanes", version 3). load_seq_session is the way from `--engine
    lanes` to `--engine seq`: the restored session serves the
    continuation the writer's own session served (its sha256 as
    recorded with the file, and what the oracle says), and a service
    asked for another envelope than the file's is refused by name,
    never started cold."""
    import hashlib
    import json
    import shutil

    msgs = list(zipf_symbol_stream(600, num_symbols=8, num_accounts=24,
                                   seed=21, zipf_a=1.0))
    shutil.copy(os.path.join(HERE, "data", "lanes_pr53.npz"),
                ck.snapshot_path(str(tmp_path), 400))
    _, meta = _raw(ck.snapshot_path(str(tmp_path), 400))
    assert (meta["kind"], meta["version"]) == ("lanes", 3)
    ses, off = ck.load_seq_session(str(tmp_path))
    assert off == 400 and len(ses.router.oid_sid) == 306
    tail = ses.process_wire([m.copy() for m in msgs[400:]])
    assert hashlib.sha256(json.dumps(tail).encode()).hexdigest() == (
        "f1a579755549e1129d6fba8f2563306db1af9148f2561417a3c3470c4f2e5776")
    ora = OracleEngine("fixed", book_slots=64, max_fills=32)
    assert tail == _serve(ora, msgs)[400:]
    exp = ses.export_state()
    assert exp["balances"] == dict(ora.balances)
    assert exp["positions"] == dict(ora.positions)

    broker = InProcessBroker()
    provision(broker)
    with pytest.raises(ck.SnapshotCapacityError, match="slots=64"):
        MatchService(broker, engine="seq", symbols=8, accounts=32,
                     slots=128, max_fills=32, checkpoint_dir=str(tmp_path))


@pytest.mark.parametrize("kind", ["fixed-native", "java"])
def test_a_session_that_routed_nothing_saves_zero_routes(kind, tmp_path):
    import numpy as np

    ses, _, save, load = _writer(kind)
    raw, _ = _raw(save(str(tmp_path), ses, 0))
    for k in ("route_oid", "route_sid"):
        assert raw[k].dtype == np.int64 and raw[k].shape == (0,)
    back, _ = load(str(tmp_path))
    assert dict(back.router.oid_sid) == {}


def test_two_snapshots_of_one_state_carry_one_digest(tmp_path):
    """An unordered_map's order follows its history: a router restored
    from the sorted arrays and the one that routed the stream hold one
    map in two orders, and their files are equal byte for byte in
    every array."""
    import numpy as np

    ses, msgs, save, load = _writer("fixed-native")
    ses.process_wire([m.copy() for m in msgs[:600]])
    a, _ = _raw(save(str(tmp_path / "a"), ses, 600))
    back, _ = load(str(tmp_path / "a"))
    own = lambda r: r._export_arrays(r._lib.kme_router_n_routes,
                                     r._lib.kme_router_export_routes,
                                     np.int64)[0].tolist()
    assert own(back.router) != own(ses.router)      # two orders...
    assert sorted(own(back.router)) == sorted(own(ses.router))
    b, _ = _raw(save(str(tmp_path / "b"), back, 600))
    assert bytes(a["digest"]) == bytes(b["digest"])     # ...one file
    assert a.keys() == b.keys()
    assert all(np.array_equal(a[k], b[k]) for k in a)


def test_a_native_routers_map_is_never_made_a_dict(tmp_path, monkeypatch):
    """Neither the save nor the restore touches the `oid_sid` dict
    property of a native router: the arrays go C++ -> file -> C++."""
    ses, msgs, save, load = _writer("fixed-native")
    ses.process_wire([m.copy() for m in msgs[:400]])
    router = ses.router
    want = dict(router.oid_sid)

    def never(self, *a):
        raise AssertionError("oid_sid copied into a dict")

    with monkeypatch.context() as mp:
        mp.setattr(type(router), "oid_sid", property(never, never))
        raw, _ = _raw(save(str(tmp_path), ses, 400))
        back, _ = load(str(tmp_path))
    assert len(raw["route_oid"]) == len(want)
    assert type(back.router) is type(router)
    assert dict(back.router.oid_sid) == want


def test_a_bit_flipped_in_the_routes_fails_the_digest(tmp_path):
    """The routes are under the content digest as arrays: one oid's sid
    altered, and the loader takes the snapshot before it."""
    import numpy as np

    from kme_tpu.engine import seq as SQ

    msgs = _two_sparse_snapshots(tmp_path)
    path = ck.snapshot_path(str(tmp_path), 600)
    with np.load(path) as z:
        data = {k: z[k].copy() for k in z.files}
    data["route_sid"][len(data["route_sid"]) // 2] ^= 1  # digest kept STALE
    with open(path, "wb") as f:
        np.savez(f, **data)
    with pytest.raises(ValueError, match="digest mismatch"):
        ck._load_file(path)
    ses, off = ck.load_seq_session(str(tmp_path), SQ.SeqConfig(**SMALL))
    assert off == 400
    ora = OracleEngine("fixed", book_slots=128, max_fills=16)
    per_msg = [[r.wire() for r in ora.process(m.copy())] for m in msgs]
    assert ses.process_wire([m.copy() for m in msgs[off:]]) == per_msg[off:]


def _version_rule_of_the_parent(meta):
    """runtime/checkpoint.py:_load_file's version check as 89e385f has
    it (the last binary that knows only versions 1 and 2)."""
    version, kind = meta.get("version"), meta.get("kind")
    if version == 2 and kind == "seq":
        return
    elif version != 1 or kind not in ("lanes", "seq", "seqjava"):
        raise ValueError("unsupported snapshot")


@pytest.mark.parametrize("kind", ["fixed-native", "java"])
def test_a_reader_of_versions_1_and_2_refuses_a_new_file(kind, tmp_path):
    """The rule itself passes every recorded file, and stops a version-3
    file of each kind before anything reads `meta["oid_sid"]`."""
    for name in RECORDED:
        _version_rule_of_the_parent(_raw(os.path.join(HERE, "data",
                                                      name))[1])
    ses, msgs, save, _ = _writer(kind)
    ses.process_wire([m.copy() for m in msgs[:200]])
    _, meta = _raw(save(str(tmp_path), ses, 200))
    with pytest.raises(ValueError, match="unsupported snapshot"):
        _version_rule_of_the_parent(meta)


def test_a_file_of_a_later_version_is_refused_in_load_file(tmp_path,
                                                           monkeypatch):
    """The same rule looking forward: this binary refuses a version it
    does not know in _load_file, where the loaders fall back, and not
    with a KeyError in the unguarded restore."""
    from kme_tpu.engine import seq as SQ

    msgs = list(zipf_symbol_stream(900, 8, 64, seed=12, zipf_a=0.0))
    ses = _seq_session(**SMALL)
    ses.process_wire([m.copy() for m in msgs[:400]])
    ck.save_seq_session(str(tmp_path), ses, 400)
    ses.process_wire([m.copy() for m in msgs[400:600]])
    monkeypatch.setattr(ck, "_VERSION", 4)
    path = ck.save_seq_session(str(tmp_path), ses, 600)
    with pytest.raises(ValueError, match="unsupported snapshot"):
        ck._load_file(path)
    back, off = ck.load_seq_session(str(tmp_path), SQ.SeqConfig(**SMALL))
    assert off == 400


@pytest.mark.parametrize("compat", ["fixed", "java"])
def test_snapshot_meta_span_and_routes_gauge_are_published(compat,
                                                           tmp_path):
    """`snapshot_meta` splits `snapshot_save` with `snapshot_export`
    and `snapshot_write`; `snapshot_routes` / `snapshot_bytes` say what
    the newest file holds — in java mode too."""
    if compat == "fixed":
        msgs, cut = _reuse_stream()
        broker, kw = _exactly_once(tmp_path, "m", msgs)
    else:
        msgs, cut = _java_stream(n=600), 512
        broker = InProcessBroker(persist_dir=str(tmp_path / "m-log"))
        provision(broker)
        for m in msgs:
            broker.produce(TOPIC_IN, None, dumps_order(m))
        kw = dict(engine="seq", compat="java", symbols=8, accounts=128,
                  slots=512, max_fills=128, batch=256,
                  checkpoint_dir=str(tmp_path / "m-ck"),
                  checkpoint_every=10**9)
    svc = MatchService(broker, **kw)
    cut = svc.run(max_messages=cut)
    before = svc.telemetry.snapshot()["gauges"]
    assert before["snapshot_meta_s"] == 0 == before["snapshot_meta_n"]
    assert "snapshot_routes" not in before
    svc.checkpoint()
    svc._publish_spans()
    gauges = svc.telemetry.snapshot()["gauges"]
    path = ck.snapshot_path(kw["checkpoint_dir"], cut)
    raw, _ = _raw(path)
    assert gauges["snapshot_routes"] == len(raw["route_oid"]) \
        == len(svc._session.router.oid_sid) > 100
    assert gauges["snapshot_bytes"] == os.path.getsize(path)
    for span in ("snapshot_export", "snapshot_meta", "snapshot_write"):
        assert gauges[span + "_n"] == 1 and gauges[span + "_s"] > 0
    assert gauges["snapshot_export_s"] + gauges["snapshot_meta_s"] \
        + gauges["snapshot_write_s"] <= gauges["snapshot_save_s"]


# ---------------------------------------------------------------------------
# a cadenced snapshot is made beside the loop (PR 53): the serve thread
# captures the boundary and hands it to the snapshot writer's thread


def _writer_kinds():
    return {
        "pipelined": dict(compat="fixed", pipeline=2, exactly_once=True,
                          slots=128, max_fills=16, batch=64),
        "serial": dict(compat="fixed", pipeline=0, slots=128,
                       max_fills=16, batch=64),
        "java": dict(compat="java", slots=512, max_fills=128, batch=64),
    }


def _cadenced(tmp_path, name, kind, n=1024, every=256, **more):
    """A broker that holds `n` messages and the arguments of a seq
    service that snapshots every `every` of them, keeping every file."""
    how = _writer_kinds()[kind]
    msgs = (_java_stream(n=n, seed=5) if how["compat"] == "java"
            else list(zipf_symbol_stream(n, num_symbols=8, num_accounts=24,
                                         seed=17, zipf_a=1.0)))
    if how.get("pipeline"):
        from kme_tpu.native import load_library

        if load_library() is None:
            pytest.skip("native host runtime unavailable")
    broker = InProcessBroker(persist_dir=str(tmp_path / (name + "-log")))
    provision(broker)
    for m in msgs:
        broker.produce(TOPIC_IN, None, dumps_order(m))
    kw = dict(engine="seq", symbols=8, accounts=128,
              checkpoint_dir=str(tmp_path / (name + "-ck")),
              checkpoint_every=every, checkpoint_keep=100, **how)
    kw.update(more)
    return broker, kw, len(msgs)


def _snapshot_offsets(kw):
    return sorted(off for off, _ in
                  ck.list_snapshots(kw["checkpoint_dir"]))


def _writers_alive():
    import threading

    return [t for t in threading.enumerate()
            if t.name == "kme-snapshot-writer"]


@pytest.fixture
def held_writer(monkeypatch):
    """Every snapshot writer stops before _atomic_savez until the serve
    thread has come to wait for it (or 20 s have passed: a test that
    never waits fails by its own assertions, and does not hang). ->
    the offsets whose write was let through, in order."""
    import threading

    waiting, written = threading.Event(), []
    savez, wait = ck._atomic_savez, MatchService._snapshot_writer_wait

    def held(ckpt_dir, offset, payload, keep=None):
        if threading.current_thread().name == "kme-snapshot-writer":
            waiting.wait(20.0)
            waiting.clear()
        written.append(offset)
        return savez(ckpt_dir, offset, payload, keep=keep)

    def announced(self):
        if self._snap_writer is not None:
            waiting.set()
        return wait(self)

    monkeypatch.setattr(ck, "_atomic_savez", held)
    monkeypatch.setattr(MatchService, "_snapshot_writer_wait", announced)
    return written


@pytest.mark.parametrize("kind", sorted(_writer_kinds()))
def test_files_made_beside_the_loop_are_those_of_a_loop_that_waits(
        kind, tmp_path, monkeypatch):
    """Same offsets, and at each the same file: every array, the meta
    and the digest. The reference waits for the writer inside every
    handoff (the save on the serve thread, as it was); the run under
    test starts each fetch only once the loop has gone on past the
    boundary, so what it reads is the boundary's state by capture and
    not by luck."""
    import time

    import numpy as np

    handoff = MatchService._snapshot_handoff

    def waited(self, extra):
        fetched = handoff(self, extra)
        self._snapshot_writer_wait()
        return fetched

    with monkeypatch.context() as mp:
        mp.setattr(MatchService, "_snapshot_handoff", waited)
        b0, kw0, n = _cadenced(tmp_path, "waits", kind)
        ref = MatchService(b0, **kw0)
        assert ref.run(max_messages=n) == n
        ref.close()

    b1, kw1, n = _cadenced(tmp_path, "beside", kind)
    svc = MatchService(b1, **kw1)
    write, ahead = ck.write_seq_snapshot, []

    def late(ckpt_dir, snap, keep=None, fetched=None):
        deadline = time.monotonic() + 3.0
        while svc.offset == snap.offset and time.monotonic() < deadline:
            time.sleep(0.005)
        ahead.append(svc.offset > snap.offset)
        return write(ckpt_dir, snap, keep=keep, fetched=fetched)

    monkeypatch.setattr(ck, "write_seq_snapshot", late)
    assert svc.run(max_messages=n) == n
    svc.close()
    assert not _writers_alive()
    offsets = _snapshot_offsets(kw1)
    assert offsets == _snapshot_offsets(kw0) == list(range(256, n + 1, 256))
    # all but the stream's last boundary were fetched with the loop ahead
    assert len(ahead) == len(offsets) and sum(ahead) >= len(offsets) - 1
    for off in offsets:
        want, wmeta = _raw(ck.snapshot_path(kw0["checkpoint_dir"], off))
        got, gmeta = _raw(ck.snapshot_path(kw1["checkpoint_dir"], off))
        assert gmeta == wmeta and gmeta["offset"] == off
        assert sorted(got) == sorted(want)
        for k in want:
            assert got[k].dtype == want[k].dtype, (off, k)
            assert np.array_equal(got[k], want[k]), (off, k)
    assert list(consume_lines(b1, follow=False)) \
        == list(consume_lines(b0, follow=False))


def test_a_slow_writer_skips_no_snapshot_and_the_loop_waits_for_it(
        tmp_path, held_writer):
    """K x checkpoint_every records give K files, at the cadence's own
    offsets and written in order, when no file is durable before the
    next boundary comes due; the wait is span `snapshot_writer_wait`."""
    broker, kw, n = _cadenced(tmp_path, "slow", "pipelined", n=768,
                              every=128)
    svc = MatchService(broker, **kw)
    assert svc.run(max_messages=n) == n
    want = list(range(128, n + 1, 128))
    assert held_writer == want == _snapshot_offsets(kw)
    svc._publish_spans()
    g = svc.telemetry.snapshot()["gauges"]
    assert g["snapshot_handoff_n"] == g["snapshot_save_n"] == len(want)
    assert g["snapshot_writer_wait_n"] == len(want)
    assert 0 < g["snapshot_writer_wait_s"] <= g["snapshot_save_s"]
    assert g["snapshot_writer_wait_s"] + g["snapshot_handoff_s"] \
        <= g["checkpoint_s"]
    svc.close()


def test_a_snapshot_in_flight_is_no_snapshot_yet(tmp_path, held_writer):
    """Between the handoff and the rename the cadence has moved on and
    nothing else has: the directory, the retention guard's oldest
    offset and the session's gauges speak of the file before; once
    durable, the gauges are one new dict."""
    broker, kw, n = _cadenced(tmp_path, "flight", "pipelined", n=512,
                              every=128)
    svc = MatchService(broker, **kw)
    svc.checkpoint()                           # a file at offset 0
    first = svc._session.snapshot_gauges
    assert first["snapshot_routes"] == 0 and _snapshot_offsets(kw) == [0]
    while svc._snap_writer is None:
        assert svc.step(timeout=0.0) > 0
    at = svc._last_ckpt_offset
    assert at == svc.offset == 128             # the cadence counts on
    assert _snapshot_offsets(kw) == [0] and held_writer == [0]
    assert ck.oldest_retained_offset(kw["checkpoint_dir"]) == 0
    assert svc._session.snapshot_gauges is first
    assert ck.snapshot_extra(kw["checkpoint_dir"], at) == {}
    svc._snapshot_writer_wait()
    assert _snapshot_offsets(kw) == [0, 128]
    now = svc._session.snapshot_gauges
    assert now is not first and now["snapshot_routes"] > 0
    assert set(now) >= set(first) | set(FETCH_GAUGES)
    assert now["snapshot_bytes"] == os.path.getsize(
        ck.snapshot_path(kw["checkpoint_dir"], 128))
    svc.close()


def test_a_crash_between_handoff_and_rename_resumes_from_the_file_before(
        tmp_path, monkeypatch):
    """The writer dies with the second file written and not renamed
    (what a kill inside _atomic_savez leaves): the next leader resumes
    from the first, replays, and MatchOut holds the uninterrupted run's
    bytes with no stamp twice."""
    import numpy as np

    from kme_tpu.bridge.consume import DedupRing
    from kme_tpu.bridge.service import TOPIC_OUT

    b0, kw0, n = _cadenced(tmp_path, "whole", "pipelined", n=768)
    assert MatchService(b0, **kw0).run(max_messages=n) == n
    whole = list(consume_lines(b0, follow=False))

    savez = ck._atomic_savez

    def dies_at_512(ckpt_dir, offset, payload, keep=None):
        if offset != 512:
            return savez(ckpt_dir, offset, payload, keep=keep)
        with open(ck.snapshot_path(ckpt_dir, offset) + ".tmp", "wb") as f:
            np.savez(f, **payload)
        raise RuntimeError("killed before the rename")

    b1, kw, n = _cadenced(tmp_path, "cut", "pipelined", n=768)
    with monkeypatch.context() as mp:
        mp.setattr(ck, "_atomic_savez", dies_at_512)
        svc = MatchService(b1, **kw)
        with pytest.raises(RuntimeError, match="before the rename"):
            svc.run(max_messages=n)
        assert svc.offset > 512                # the loop had gone on
    del svc                                    # no close(): it is dead
    assert _snapshot_offsets(kw) == [256]
    assert os.path.exists(ck.snapshot_path(kw["checkpoint_dir"], 512)
                          + ".tmp")

    b2 = InProcessBroker(persist_dir=str(tmp_path / "cut-log"))
    svc2 = MatchService(b2, **kw)
    assert svc2.offset == 256 and svc2.epoch == 2
    assert svc2.run(max_messages=n - 256) == n - 256
    svc2.close()
    assert _snapshot_offsets(kw) == [256, 512, 768]
    assert list(consume_lines(b2, follow=False)) == whole
    ring = DedupRing(capacity=1 << 20)
    recs = b2.fetch(TOPIC_OUT, 0, 10**7)
    assert b2.dup_suppressed > 0
    assert not any(ring.is_dup(r.epoch, r.out_seq) for r in recs)


@pytest.mark.parametrize("how", ["checkpoint", "close", "run"])
def test_outside_the_cadence_the_caller_returns_with_the_file_durable(
        how, tmp_path, held_writer):
    """An explicit checkpoint() is the same handoff, waited for;
    close() and a run() that returns take the writer in flight back."""
    broker, kw, n = _cadenced(tmp_path, how, "serial", n=384, every=128)
    svc = MatchService(broker, **kw)
    if how == "run":
        assert svc.run(max_messages=n) == n
        want = [128, 256, 384]
    else:
        while svc._snap_writer is None:
            assert svc.step(timeout=0.0) > 0
        assert _snapshot_offsets(kw) == [] and _writers_alive()
        if how == "checkpoint":
            assert svc.step(timeout=0.0) == 64     # off the cadence
            assert _snapshot_offsets(kw) == [] and svc.offset == 192
            svc.checkpoint()
            want = [128, 192]
        else:
            svc.close()
            want = [128]
    assert _snapshot_offsets(kw) == want == held_writer
    assert svc._snap_writer is None and not _writers_alive()
    assert not [f for f in os.listdir(kw["checkpoint_dir"])
                if f.endswith(".tmp")]
    svc.close()


@pytest.mark.parametrize("engine", ["oracle", "native", "follower"])
def test_engines_saved_on_the_serve_thread_start_no_writer(
        engine, tmp_path, monkeypatch):
    """The engines no deployment serves are saved inside the handoff
    (their state is the host's own, made a file at the boundary); a
    follower takes no snapshot at all."""
    from kme_tpu.bridge import service

    def no_writer(self, write):
        raise AssertionError("a snapshot writer was started")

    monkeypatch.setattr(service._SnapshotWriter, "__init__", no_writer)
    msgs = harness_stream(300, seed=13, num_symbols=4, num_accounts=8,
                          payout_opcode_bug=False, validate=True)
    broker = InProcessBroker()
    provision(broker)
    for m in msgs:
        broker.produce(TOPIC_IN, None, dumps_order(m))
    kw = dict(compat="fixed", batch=50, symbols=8, accounts=16, slots=64,
              max_fills=32, checkpoint_dir=str(tmp_path),
              checkpoint_every=100)
    if engine == "follower":
        kw.update(engine="oracle", follower=True)
    else:
        if engine == "native":
            nat = pytest.importorskip("kme_tpu.native.oracle")
            if not nat.native_available():
                pytest.skip("native library unavailable")
        kw.update(engine=engine)
    svc = MatchService(broker, **kw)
    seen = []
    while svc.offset < 300:
        assert svc.step(timeout=0.0) > 0
        seen.append(sorted(off for off, _ in
                           ck.all_snapshots(kw["checkpoint_dir"])))
        assert svc._snap_writer is None
    svc.close()
    if engine == "follower":
        assert seen[-1] == []
    else:
        # each file was there when the step that crossed its boundary
        # returned
        assert seen[1] == [100] and seen[3] == [100, 200]
        assert seen[-1] == [100, 200, 300]
        g = svc._ptimer.gauges()
        assert g["snapshot_handoff_n"] == g["snapshot_save_n"] == 3
        assert g["snapshot_save_s"] <= g["snapshot_handoff_s"]


def test_with_an_auditor_on_the_loop_waits_for_each_file_and_compares(
        tmp_path, held_writer):
    """The auditor's compare reads the boundary's state (the snapshot's
    own fetch) against a shadow ledger that moves with the next batch:
    the serve thread has the file durable and the fetch in hand before
    it compares, and nothing is in flight when a step returns."""
    broker, kw, n = _cadenced(
        tmp_path, "audit", "pipelined", n=512, every=128,
        journal=str(tmp_path / "audit-journal.kmej"), audit=True)
    svc = MatchService(broker, **kw)
    check, compared = svc._audit_check_engine, []

    def at_the_boundary(fetched):
        compared.append((svc.offset, _snapshot_offsets(kw)[-1],
                         len(fetched), svc._snap_writer))
        check(fetched)

    svc._audit_check_engine = at_the_boundary
    while svc.offset < n:
        svc.step(timeout=0.0)      # (0: the pipeline drains on an empty poll)
        assert svc._snap_writer is None
    assert compared == [(off, off, 2, None) for off in (128, 256, 384, 512)]
    assert svc.auditor.violations == [] and svc.degraded is None
    g = svc._ptimer.gauges()
    assert g["snapshot_writer_wait_n"] == g["audit_check_engine_n"] == 4
    svc.close()


@pytest.mark.parametrize("kind", ["fixed-native", "fixed-python", "java"])
def test_a_routers_capture_is_the_router_as_it_stood(kind, monkeypatch):
    """capture() copies the three id maps now and sorts them when
    called: what it gives is the router at the capture, whatever was
    routed, dropped or purged in between."""
    import numpy as np

    from kme_tpu.engine import seq as SQ
    from kme_tpu.runtime.seqsession import SeqSession

    if kind == "fixed-python":      # the router KME_NATIVE=0 serves with
        monkeypatch.setattr("kme_tpu.native.require_library", lambda: None)
    compat = "java" if kind == "java" else "fixed"
    shape = dict(SMALL, slots=512, max_fills=128) if kind == "java" \
        else SMALL
    ses = SeqSession(SQ.SeqConfig(compat=compat, **shape))
    r = ses.router
    assert ("Native" in type(r).__name__) == (kind == "fixed-native") \
        or pytest.skip("native host runtime unavailable")
    msgs = (_java_stream(n=900, seed=3) if kind == "java"
            else list(zipf_symbol_stream(900, num_symbols=8,
                                         num_accounts=100, seed=3)))
    ses.process_wire([m.copy() for m in msgs[:500]])
    want = (sorted(r.aid_idx.items()), sorted(r.sid_lane.items()),
            *r.routes_arrays())
    assert len(want[0]) > 8 and len(want[2]) > 50
    later = r.capture()
    ses.process_wire([m.copy() for m in msgs[500:]])
    assert not np.array_equal(r.routes_arrays()[0], want[2])
    got = later()
    assert got[:2] == want[:2]
    for g, w in zip(got[2:], want[2:]):
        assert g.dtype == np.int64 and np.array_equal(g, w)
    assert np.all(np.diff(got[2]) > 0)
