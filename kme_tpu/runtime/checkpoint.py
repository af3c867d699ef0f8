"""Checkpoint / resume: the durability story.

The reference gets durability from RocksDB-backed stores + Kafka
changelog topics; resume = Kafka Streams restoring store state and
continuing from the committed input offset
(/root/reference/src/main/java/KProcessor.java:30-49; commit :125).
Exactly-once is commented out (:29), so its guarantee is AT-LEAST-ONCE:
on crash, records after the last commit replay.

The TPU-native equivalent: an explicit `(state_pytree, input_offset)`
snapshot at a batch boundary (SURVEY.md §5). Because the engine is
deterministic, resume = load snapshot + replay the input tail, and the
replayed outputs are bit-identical — the same at-least-once contract
with replay bounded by the checkpoint interval instead of one record.

The exactly-once layer (bridge/broker.py fencing + idempotent produce)
upgrades that: every save accepts an additive ``extra`` meta dict — the
service stores its ``{"epoch", "out_seq"}`` produce-stamp cursor there —
and `snapshot_extra` reads it back on resume, so the replayed tail
re-produces with the SAME stamps and the broker suppresses it.

Snapshots are self-describing single files: every state array plus a
JSON `meta` blob (config, input offset, the small id maps) in one
.npz, written atomically (tmp + rename) and named ckpt-<offset>.npz so
the latest valid one wins; a torn or corrupt file falls back to the
previous snapshot. The router's oid -> sid
routes — every oid ever routed that no payout or removal dropped, the
one id map that grows with the stream — are two int64 arrays of the
payload, `route_oid` ascending and `route_sid` in its order (version 3;
older files list them in the meta, and `_load_file` hands both on as
the arrays).

The device's output planes (fills among them) are intentionally NOT
saved: at a batch boundary they have been fetched, and every call
starts them anew.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
from typing import List, Optional, Tuple

import numpy as np

from kme_tpu import faults

_CKPT_RE = re.compile(r"^ckpt-(\d+)\.npz$")


def _keep_default() -> int:
    """Snapshot retention depth. Two is the bare minimum (newest + one
    fallback); the default keeps a deeper tail so several consecutive
    corrupt/torn snapshots still leave a valid restore point
    (kme-chaos tears AND bit-flips). KME_CKPT_KEEP / --checkpoint-keep
    override."""
    try:
        return max(1, int(os.environ.get("KME_CKPT_KEEP", "3")))
    except ValueError:
        return 3


class SnapshotCapacityError(ValueError):
    """The snapshot cannot restore into the requested capacity/engine
    config (a state migration, not a resume) — callers must NOT
    silently fall back to a fresh engine."""


# the version every .npz writer here gives its files: the routes as the
# payload arrays `route_oid` / `route_sid` (versions 1 and 2 list them
# in the meta as `oid_sid`; 2 is a "seq" file with a sparse section)
_VERSION = 3


def snapshot_path(ckpt_dir: str, offset: int) -> str:
    return os.path.join(ckpt_dir, f"ckpt-{offset}.npz")


def _payload_digest(payload: dict) -> str:
    """sha256 over every array's dtype/shape/bytes (sorted key order,
    'digest' excluded) — the content integrity check _load_file
    verifies. A bit-flipped payload that still np.load-parses fails
    HERE instead of silently restoring wrong state."""
    h = hashlib.sha256()
    for k in sorted(payload):
        if k == "digest":
            continue
        arr = np.ascontiguousarray(np.asarray(payload[k]))
        h.update(k.encode())
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def _atomic_savez(ckpt_dir: str, offset: int, payload: dict,
                  keep: Optional[int] = None) -> str:
    """THE durable snapshot write: content digest + tmp file + fsync +
    atomic rename + directory fsync + prune. Every .npz save path goes
    through here so the crash-safety sequence cannot fork."""
    payload = dict(payload)
    payload["digest"] = np.frombuffer(
        _payload_digest(payload).encode(), dtype=np.uint8)
    path = snapshot_path(ckpt_dir, offset)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **payload)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    _fsync_dir(ckpt_dir)
    _post_write_faults(path)
    _prune(ckpt_dir, _CKPT_RE, keep=keep)
    return path


def _post_write_faults(path: str) -> None:
    """kme-chaos injection points: tear or bit-flip the snapshot that
    was just made durable (the load path must detect either and fall
    back to the previous snapshot)."""
    faults.damage_file("ckpt.torn", path)
    faults.damage_file("ckpt.bitflip", path)


def list_snapshots(ckpt_dir: str) -> List[Tuple[int, str]]:
    """(offset, path) pairs, newest first."""
    if not os.path.isdir(ckpt_dir):
        return []
    out = []
    for name in os.listdir(ckpt_dir):
        m = _CKPT_RE.match(name)
        if m:
            out.append((int(m.group(1)), os.path.join(ckpt_dir, name)))
    out.sort(reverse=True)
    return out


def _fsync_dir(d: str) -> None:
    fd = os.open(d, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _prune(ckpt_dir: str, pattern, keep: Optional[int] = None) -> None:
    """Unlink all but the newest `keep` snapshots. keep=None uses the
    configured default (_keep_default) — deep enough that multi-step
    fallback past several corrupt snapshots still finds a valid one."""
    if keep is None:
        keep = _keep_default()
    keep = max(1, int(keep))
    cands = []
    for name in os.listdir(ckpt_dir):
        m = pattern.match(name)
        if m:
            cands.append((int(m.group(1)), name))
    cands.sort(reverse=True)
    for _, name in cands[keep:]:
        try:
            os.unlink(os.path.join(ckpt_dir, name))
        except OSError:
            pass


def _load_file(path: str):
    """(arrays, meta) of one .npz snapshot, digest-verified over the
    file as written; the arrays in the DENSE canonical layout whatever
    the file's encoding, so every reader sees one form."""
    with np.load(path) as npz:
        data = {k: npz[k] for k in npz.files}
    if "digest" in data:
        want = bytes(data["digest"]).decode()
        got = _payload_digest(data)
        if got != want:
            raise ValueError(
                f"content digest mismatch in {path} (stored "
                f"{want[:12]}…, computed {got[:12]}…): corrupt snapshot")
    # pre-digest snapshots (older writers) load unverified
    meta = json.loads(bytes(data["meta"]).decode())
    # "seq" snapshots and those of kind "lanes" (written by the sweep
    # engine, removed in PR 54; last writer 06d92bd) share the canonical
    # payload layout and both restore into a SeqSession; "seqjava"
    # is the java-mode canonical form (runtime/javasnap.py), restorable
    # into SeqSession(compat='java') and convertible to/from the native
    # engine's dump. Version 2 is a "seq" snapshot with a section given
    # by its live entries (meta "layout"); version 3 (any kind) carries
    # the routes as arrays, and a "seq" one may have such sections too.
    # A binary that does not know a file's version refuses it HERE and
    # falls back to an older file
    version, kind = meta.get("version"), meta.get("kind")
    if (kind not in ("lanes", "seq", "seqjava") or version not in (1, 2, 3)
            or (version == 2 and kind != "seq")):
        raise ValueError(f"unsupported snapshot {path}")
    if kind == "seq" and "layout" in meta:
        from kme_tpu.engine import seq as SQ

        data = SQ.densify_canonical(data, meta["layout"])
    if version < 3:
        pairs = np.array(meta.pop("oid_sid"), np.int64).reshape(-1, 2)
        data["route_oid"] = np.ascontiguousarray(pairs[:, 0])
        data["route_sid"] = np.ascontiguousarray(pairs[:, 1])
    routes = [data.get("route_oid"), data.get("route_sid")]
    if any(r is None or r.dtype != np.int64 or r.ndim != 1
           for r in routes) or len(routes[0]) != len(routes[1]):
        raise ValueError(f"snapshot {path}: no int64 route_oid / "
                         f"route_sid arrays of one length")
    return data, meta


@dataclasses.dataclass
class SeqCapture:
    """A SeqSession as of one batch boundary: all that its snapshot at
    `offset` needs and the session changes afterwards, so that the file
    can be made by another thread while the session goes on
    (capture_seq_session makes one, write_seq_snapshot writes it).
    `state` is a reference and no copy: the scan is not donated
    (engine/seq.py:build_seq_step), so a boundary's arrays stay as they
    are for as long as somebody holds them, and the next submit makes
    new ones."""
    session: object         # its cfg, kind and timer; never its state
    offset: int
    extra: Optional[dict]
    state: object
    metrics: np.ndarray
    hist: np.ndarray
    router: object          # SeqRouter.capture(): the id maps, a call


def capture_seq_session(session, offset: int,
                        extra: Optional[dict] = None) -> SeqCapture:
    """The session's side of a snapshot, on the session's own thread at
    a batch boundary: a reference to the device state, copies of the
    host state (the counters; the router's three id maps as they come
    out of it, unsorted). What costs more — the fetch, the host's
    passes, the maps' sorting, the meta's JSON, the digest and the
    write — is write_seq_snapshot's."""
    return SeqCapture(
        session=session, offset=int(offset),
        extra=dict(extra) if extra else None, state=session.state,
        metrics=session._metrics.copy(), hist=session._hist.copy(),
        router=session.router.capture())


def _snapshot_export(session, state):
    """The device -> host half of a seq snapshot (span
    `snapshot_export` of the session's timer) from `state`, the
    session's as of the boundary: (arrays, layout, fetch), with
    `layout` None where the arrays are dense throughout and `fetch`
    what engine/seq.py:export_snapshot says crossed (a fixed-mode
    SeqSession's books cross by their live rows and its positions by
    their live entries, both gathered on the device; every other
    export brings its planes whole and says nothing)."""
    from kme_tpu.runtime.seqsession import SeqSession

    with session.timer.phase("snapshot_export"):
        if session.cfg.compat == "java":
            from kme_tpu.runtime.javasnap import export_seqjava_device

            return export_seqjava_device(session.cfg, state), None, {}
        from kme_tpu.engine import seq as SQ

        if type(session) is SeqSession:
            return SQ.export_snapshot(session.cfg, state)
        # a subclass keeps its state elsewhere (SeqMeshSession: sharded
        # across devices) and its dense export
        return SQ.export_canonical(session.cfg, state), None, {}


def _snapshot_payload(snap: SeqCapture, kind: str, arrays: dict,
                      layout: Optional[dict] = None) -> dict:
    """The host half of a seq snapshot between the fetch and the write
    (span `snapshot_meta` of the session's timer): the file's payload —
    `arrays`, the routes' two (sorted here) and the meta JSON, which
    holds all that is not an array, the two small id maps included
    (<= `accounts` and <= `lanes` entries)."""
    with snap.session.timer.phase("snapshot_meta"):
        aid_idx, sid_lane, route_oid, route_sid = snap.router()
        meta = {
            "version": _VERSION,
            "kind": kind,
            "offset": snap.offset,
            "cfg": dataclasses.asdict(snap.session.cfg),
            "metrics": [int(x) for x in snap.metrics],
            "hist": [[int(x) for x in row] for row in snap.hist],
            "aid_idx": aid_idx,
            "sid_lane": sid_lane,
        }
        if kind == "seq":
            # read by the lanes engine's restore of a binary of PR 53 or
            # before, and by nothing here: part of the version-3 format
            meta.update(rr_lane=0, width=0, shards=1)
        if layout and layout["sparse"]:
            meta["layout"] = layout
        if snap.extra:
            meta["extra"] = snap.extra
        payload = dict(arrays, route_oid=route_oid, route_sid=route_sid)
        payload["meta"] = np.frombuffer(
            json.dumps(meta).encode(), dtype=np.uint8)
        return payload


def write_seq_snapshot(ckpt_dir: str, snap: SeqCapture,
                       keep: Optional[int] = None, fetched=None) -> str:
    """Make the file of a captured boundary durable, on whichever
    thread calls: three spans of the session's timer split it,
    `snapshot_export` (the device's gather of the books' live rows and
    of the positions' live entries, the device -> host fetch of those
    and the small sections, and the host's pass over them),
    `snapshot_meta` (the meta's JSON over the routes' arrays) and
    `snapshot_write` (_atomic_savez, whole). Once the file is durable
    the session's `snapshot_gauges` are replaced, whole, with what it
    holds; nothing of the session is touched before. `fetched`, where
    given, is a list that then takes the export's canon and layout: a
    second reader of the state at that offset (the auditor's compare:
    SeqSession.export_live) shares this snapshot's one fetch. It stays
    empty where the export has no live-entry layout (java mode: the
    canonical java form of runtime/javasnap.py, flat 128-bit-key
    position arrays with the Q11 garbage keys, resting orders with
    direction tags and bucket seq, balances; a subclass with a dense
    export)."""
    session = snap.session
    os.makedirs(ckpt_dir, exist_ok=True)
    canon, layout, fetch = _snapshot_export(session, snap.state)
    snap.state = None       # fetched: the boundary's arrays may go
    if session.cfg.compat == "java":
        kind, arrays = "seqjava", {k: np.asarray(v)
                                   for k, v in canon.items()}
    else:
        kind, arrays = "seq", {k: v for k, v in canon.items()
                               if k != "metrics" and v is not None}
        arrays["err"] = np.asarray(canon["err"])
        # as are the three meta fields of _snapshot_payload: read by
        # the restore of a binary of PR 53 or before alone
        arrays["filloff"] = np.zeros(1, np.int64)
    payload = _snapshot_payload(snap, kind, arrays, layout)
    with session.timer.phase("snapshot_write"):
        path = _atomic_savez(ckpt_dir, snap.offset, payload, keep=keep)
    gauges = {"snapshot_bytes": os.path.getsize(path),
              "snapshot_routes": len(payload["route_oid"]), **fetch}
    if layout:
        gauges.update(
            snapshot_live_slots=layout["live_slots"],
            snapshot_live_positions=layout["live_positions"],
            snapshot_sparse_sections=len(layout["sparse"]),
            # routes in the file beyond the orders that rest in it: 0
            # since routes die with their orders (a snapshot is taken
            # after the drain, so both speak of one input prefix)
            stale_routes=(gauges["snapshot_routes"]
                          - layout["live_slots"]))
        if fetched is not None:
            fetched += [canon, layout]
    session.snapshot_gauges = gauges
    return path


def save_seq_session(ckpt_dir: str, session, offset: int,
                     keep: Optional[int] = None,
                     extra: Optional[dict] = None,
                     fetched=None) -> str:
    """Snapshot a SeqSession at input offset `offset`, here and now
    (capture_seq_session, then write_seq_snapshot on the caller's
    thread), in the canonical layout (slot_* / flat s64 positions /
    bal), which no device layout shows through. The books and the
    positions are each written by their live entries where that is the
    smaller encoding (engine/seq.py:export_snapshot; _load_file
    densifies), so a file's size follows what is live and not the
    configured capacity. A java-mode session writes its own canonical
    form."""
    return write_seq_snapshot(
        ckpt_dir, capture_seq_session(session, offset, extra), keep,
        fetched)


def _seqjava_snap_from_file(data, meta) -> dict:
    snap = {k: v for k, v in data.items() if k != "meta"}
    snap["aid_idx"] = {int(k): int(v) for k, v in meta["aid_idx"]}
    snap["sid_lane"] = {int(k): int(v) for k, v in meta["sid_lane"]}
    return snap


def load_seq_session(ckpt_dir: str, cfg=None):
    """Restore the newest valid snapshot into a SeqSession. `cfg` (a
    SeqConfig) sets the RESTORE topology — snapshots are canonical, so
    any slots >= the snapshot's depth works, and a kind "lanes" file
    of the sweep engine (`--engine lanes` until PR 54) restores here
    too: the way from that engine to this one. Returns (session,
    offset) or (None, 0)."""
    for offset, path in list_snapshots(ckpt_dir):
        try:
            # host-only read + parse + digest: whatever a torn or
            # bit-flipped file raises in here means "unreadable"
            data, meta = _load_file(path)
        except Exception as e:
            import sys

            print(f"kme_tpu.checkpoint: skipping unreadable snapshot "
                  f"{path}: {e}", file=sys.stderr)
            continue
        # the restore itself is NOT guarded: a capacity mismatch is an
        # operator error, and a device failure while importing the
        # planes must not pass for "no snapshot, start fresh"
        return _restore_seq(data, meta, cfg), offset
    return None, 0


def _restore_seq(data, meta, cfg):
    from kme_tpu.engine import seq as SQ
    from kme_tpu.runtime.seqsession import SeqSession

    explicit_cfg = cfg is not None
    if meta["kind"] == "seqjava":
        from kme_tpu.runtime.javasnap import import_seqjava

        if cfg is None:
            cfg = SQ.SeqConfig(**meta["cfg"])
        if cfg.compat != "java":
            raise SnapshotCapacityError(
                "java-mode snapshot requires SeqConfig(compat='java') "
                "(or conversion to the native engine, "
                "runtime/javasnap.py)")
        if explicit_cfg:
            # same contract as the fixed path: the device capacity
            # envelope must not change across a resume (a changed
            # slots/max_fills alters where the fatal java capacity
            # error trips mid-stream)
            n0 = int(meta["cfg"]["slots"])
            mf = int(meta["cfg"]["max_fills"])
            if cfg.slots != n0 or cfg.max_fills != mf:
                raise SnapshotCapacityError(
                    f"snapshot capacity (slots={n0}, max_fills={mf}) "
                    f"!= requested (slots={cfg.slots}, max_fills="
                    f"{cfg.max_fills}) — capacity changes need a "
                    f"state migration, not a resume")
        try:
            ses = import_seqjava(cfg, _seqjava_snap_from_file(data, meta))
        except ValueError as e:
            raise SnapshotCapacityError(str(e)) from e
        if "metrics" in meta:
            ses._metrics = np.asarray(meta["metrics"], np.int64)
        if "hist" in meta:
            ses._hist = np.asarray(meta["hist"], np.int64)
        return ses
    if cfg is not None and cfg.compat == "java":
        raise SnapshotCapacityError(
            "fixed-mode snapshot cannot restore into a java-mode "
            "session")
    if cfg is None:
        if meta["kind"] == "seq":
            cfg = SQ.SeqConfig(**meta["cfg"])
        else:  # kind "lanes": map the capacity fields of its config
            mc = meta["cfg"]
            slots = -(-int(mc["slots"]) // 128) * 128
            cfg = SQ.SeqConfig(
                lanes=int(mc["lanes"]), slots=slots,
                accounts=-(-int(mc["accounts"]) // 128) * 128,
                max_fills=int(mc["max_fills"]),
                hbm_books=slots > 512)
    canon = {k: v for k, v in data.items() if k != "meta"}
    canon.setdefault("err", np.int32(0))
    if explicit_cfg:
        # service resume: the matching ENVELOPE must not change across
        # a resume (the native path enforces the same; deeper
        # books or a different max_fills alter reject behavior
        # mid-stream — that is a state migration, not a resume)
        n0 = int(np.asarray(canon["slot_oid"]).shape[2])
        mf = int(meta["cfg"].get("max_fills", cfg.max_fills))
        if cfg.slots != n0 or cfg.max_fills != mf:
            raise SnapshotCapacityError(
                f"snapshot envelope (slots={n0}, max_fills={mf}) != "
                f"requested (slots={cfg.slots}, max_fills="
                f"{cfg.max_fills}) — capacity changes need a state "
                f"migration, not a resume")
    ses = SeqSession(cfg)
    try:
        # every ValueError here is a config-vs-snapshot mismatch
        # (corruption surfaces earlier, in _load_file) — never treat it
        # as a skippable corrupt snapshot
        ses.state = SQ.import_canonical(cfg, canon)
    except ValueError as e:
        raise SnapshotCapacityError(str(e)) from e
    if "metrics" in meta:
        ses._metrics = np.asarray(meta["metrics"], np.int64)
    if "hist" in meta:
        ses._hist = np.asarray(meta["hist"], np.int64)
    r = ses.router
    r.aid_idx = {int(k): int(i) for k, i in meta["aid_idx"]}
    r.sid_lane = {int(k): int(l) for k, l in meta["sid_lane"]}
    r.import_routes(data["route_oid"], data["route_sid"])
    # the router's pool of free lanes is rebuilt from `sid_lane` by its
    # setter; which bound ids hold no book, from the restored books
    r.set_listed(np.asarray(canon["book_exists"]).reshape(-1))
    return ses


# ---------------------------------------------------------------------------
# native-engine snapshots (text store dump + a JSON header line)

def save_native(ckpt_dir: str, engine, offset: int,
                keep: Optional[int] = None,
                extra: Optional[dict] = None) -> str:
    """Snapshot a NativeOracleEngine: JSON header (compat + envelope +
    offset + dump digest) on line one, then the store dump."""
    os.makedirs(ckpt_dir, exist_ok=True)
    dump = engine.dump_state()
    head = {
        "version": 1, "kind": "native", "offset": int(offset),
        "compat": "java" if engine.java else "fixed",
        "book_slots": engine.book_slots, "max_fills": engine.max_fills,
        "digest": hashlib.sha256(dump.encode("utf-8")).hexdigest(),
    }
    if extra:
        head["extra"] = dict(extra)
    header = json.dumps(head)
    path = os.path.join(ckpt_dir, f"ckpt-{offset}.nat")
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        f.write(header + "\n")
        f.write(dump)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    _fsync_dir(ckpt_dir)
    _post_write_faults(path)
    _prune(ckpt_dir, re.compile(r"^ckpt-(\d+)\.nat$"), keep=keep)
    return path


def load_native(ckpt_dir: str):
    """Returns (engine, offset) or (None, 0); corrupt files fall back."""
    import sys

    from kme_tpu.native.oracle import NativeOracleEngine

    if not os.path.isdir(ckpt_dir):
        return None, 0
    cands = []
    for name in os.listdir(ckpt_dir):
        m = re.match(r"^ckpt-(\d+)\.nat$", name)
        if m:
            cands.append((int(m.group(1)), os.path.join(ckpt_dir, name)))
    cands.sort(reverse=True)
    for offset, path in cands:
        try:
            with open(path, "r", encoding="utf-8") as f:
                header = json.loads(f.readline())
                if header.get("version") != 1 or header.get("kind") != "native":
                    raise ValueError("unsupported snapshot")
                dump = f.read()
                want = header.get("digest")
                if want is not None:  # pre-digest snapshots load as-is
                    got = hashlib.sha256(dump.encode("utf-8")).hexdigest()
                    if got != want:
                        raise ValueError(
                            f"content digest mismatch (stored "
                            f"{want[:12]}…, computed {got[:12]}…): "
                            f"corrupt snapshot")
                eng = NativeOracleEngine(header["compat"],
                                         book_slots=header["book_slots"],
                                         max_fills=header["max_fills"])
                eng.load_state(dump)
            return eng, offset
        except Exception as e:
            print(f"kme_tpu.checkpoint: skipping unreadable snapshot "
                  f"{path}: {e}", file=sys.stderr)
    return None, 0


# ---------------------------------------------------------------------------
# oracle-engine snapshots (the scalar replica is plain host state)

def save_oracle(ckpt_dir: str, oracle, offset: int,
                keep: Optional[int] = None,
                extra: Optional[dict] = None) -> str:
    """The engine is pickled to bytes FIRST so the blob can carry a
    sha256 of exactly those bytes — load verifies the digest before
    unpickling, so a bit-flip that still pickle-parses is caught."""
    import pickle

    os.makedirs(ckpt_dir, exist_ok=True)
    engine_pkl = pickle.dumps(oracle)
    path = os.path.join(ckpt_dir, f"ckpt-{offset}.pkl")
    tmp = path + ".tmp"
    blob = {"version": 1, "kind": "oracle", "offset": int(offset),
            "engine_pkl": engine_pkl,
            "digest": hashlib.sha256(engine_pkl).hexdigest()}
    if extra:
        blob["extra"] = dict(extra)
    with open(tmp, "wb") as f:
        pickle.dump(blob, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    _fsync_dir(ckpt_dir)
    _post_write_faults(path)
    _prune(ckpt_dir, re.compile(r"^ckpt-(\d+)\.pkl$"), keep=keep)
    return path


def load_oracle_file(path: str):
    """Restore ONE oracle snapshot file (digest-verified). Raises on
    corruption — callers own the fallback-to-older decision."""
    import pickle

    with open(path, "rb") as f:
        blob = pickle.load(f)
    if blob.get("version") != 1 or blob.get("kind") != "oracle":
        raise ValueError("unsupported snapshot")
    if "engine_pkl" in blob:
        got = hashlib.sha256(blob["engine_pkl"]).hexdigest()
        if got != blob.get("digest"):
            raise ValueError(
                f"content digest mismatch (stored "
                f"{str(blob.get('digest'))[:12]}…, computed "
                f"{got[:12]}…): corrupt snapshot")
        return pickle.loads(blob["engine_pkl"])
    return blob["engine"]   # pre-digest snapshot format


def load_oracle(ckpt_dir: str):
    """Returns (oracle, offset) or (None, 0)."""
    import sys

    if not os.path.isdir(ckpt_dir):
        return None, 0
    cands = []
    for name in os.listdir(ckpt_dir):
        m = re.match(r"^ckpt-(\d+)\.pkl$", name)
        if m:
            cands.append((int(m.group(1)), os.path.join(ckpt_dir, name)))
    cands.sort(reverse=True)
    for offset, path in cands:
        try:
            return load_oracle_file(path), offset
        except Exception as e:
            print(f"kme_tpu.checkpoint: skipping unreadable snapshot "
                  f"{path}: {e}", file=sys.stderr)
    return None, 0


def restore_seq_snapshot(path: str, cfg=None):
    """Restore ONE .npz snapshot file (seq/seqjava/lanes canonical
    form) into a SeqSession. Raises on corruption or capacity mismatch
    — the offset-addressed loaders (telemetry/xray.py) use this to
    restore a SPECIFIC anchor instead of the newest snapshot."""
    return _restore_seq(*_load_file(path), cfg)


# ---------------------------------------------------------------------------
# cross-kind snapshot metadata (the exactly-once produce-stamp cursor)

_ALL_SNAP_RES = (_CKPT_RE,
                 re.compile(r"^ckpt-(\d+)\.nat$"),
                 re.compile(r"^ckpt-(\d+)\.pkl$"))


def snapshot_extra(ckpt_dir: str, offset: int) -> dict:
    """The additive ``extra`` meta dict stored with the snapshot at
    exactly `offset` (any snapshot kind); {} when absent or unreadable.
    The caller already loaded the snapshot itself, so failures here
    degrade to an empty cursor (epoch 0 / out_seq 0), which the broker's
    recovered watermark still keeps duplicate-free."""
    import pickle

    npz = snapshot_path(ckpt_dir, offset)
    if os.path.exists(npz):
        try:
            data = np.load(npz)
            meta = json.loads(bytes(data["meta"]).decode())
            return dict(meta.get("extra") or {})
        except Exception:
            return {}
    nat = os.path.join(ckpt_dir, f"ckpt-{offset}.nat")
    if os.path.exists(nat):
        try:
            with open(nat, "r", encoding="utf-8") as f:
                header = json.loads(f.readline())
            return dict(header.get("extra") or {})
        except Exception:
            return {}
    pkl = os.path.join(ckpt_dir, f"ckpt-{offset}.pkl")
    if os.path.exists(pkl):
        try:
            with open(pkl, "rb") as f:
                blob = pickle.load(f)
            return dict(blob.get("extra") or {})
        except Exception:
            return {}
    return {}


def all_snapshots(ckpt_dir: str) -> List[Tuple[int, str]]:
    """(offset, path) pairs across ALL snapshot kinds (.npz/.nat/.pkl),
    newest first. The offset-addressed restore path (telemetry/xray.py)
    walks this to find the nearest anchor <= a target offset; ties at
    the same offset sort .pkl > .npz > .nat so the exact-state oracle
    snapshot wins when several kinds exist."""
    if not os.path.isdir(ckpt_dir):
        return []
    rank = {".pkl": 2, ".npz": 1, ".nat": 0}
    out = []
    for name in os.listdir(ckpt_dir):
        for pat in _ALL_SNAP_RES:
            m = pat.match(name)
            if m:
                ext = os.path.splitext(name)[1]
                out.append((int(m.group(1)), rank.get(ext, 0),
                            os.path.join(ckpt_dir, name)))
                break
    out.sort(reverse=True)
    return [(off, path) for off, _r, path in out]


def oldest_retained_offset(ckpt_dir: str) -> Optional[int]:
    """Smallest snapshot offset still on disk (any kind), or None when
    there are no snapshots. The journal's retention guard
    (telemetry/journal.py): a rotated journal segment may only be
    pruned once every event in it is OLDER than this — a standby
    restoring the oldest snapshot must still be able to replay to the
    tip."""
    if not os.path.isdir(ckpt_dir):
        return None
    oldest = None
    for name in os.listdir(ckpt_dir):
        for pat in _ALL_SNAP_RES:
            m = pat.match(name)
            if m:
                off = int(m.group(1))
                if oldest is None or off < oldest:
                    oldest = off
                break
    return oldest
