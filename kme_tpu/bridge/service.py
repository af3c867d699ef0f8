"""MatchService: the engine service behind the MatchIn/MatchOut topics.

The reference role: Kafka Streams pulls records from `MatchIn`, the
processor forwards the pre-image with key "IN", processes, and forwards
the result/fill stream with key "OUT" to `MatchOut`
(/root/reference/src/main/java/KProcessor.java:96-126). Here the same
contract is a poll loop over the broker API with a pluggable engine:

- engine="seq"    — the device engine, and the default (as kme-serve's):
  the sequential Pallas mega-kernel behind SeqSession (fixed mode, and
  the java-compat device surface), micro-batched; with `pipeline`,
  batch N+1 is planned and dispatched under batch N's device step. The
  batch boundary replaces the reference's per-record commit
  (KProcessor.java:125, SURVEY.md §7 H5): offsets advance only after a
  batch's outputs are produced.
- engine="oracle" — the scalar reference replica (compat java|fixed),
  quirk-exact per message; the slow-but-byte-faithful configuration.
- engine="native" — the C++ port of the same quirk-exact semantics
  (kme_tpu/native/oracle.py): the FAST host-side java-compat path, and
  where a java-mode seq service continues when a stream leaves the
  device surface (COMPAT.md).

Malformed values (JSON Jackson would reject) kill the reference's
stream thread (KProcessor.java:513-517); the service instead drops the
record with a stderr note — a deliberate fix, flagged by `strict=True`
which replicates the reference behavior by raising.

Output contract: by default AT-LEAST-ONCE (the reference, with Kafka's
exactly-once commented out at KProcessor.java:29 — crash + resume
replays the post-snapshot tail). `exactly_once=True` upgrades that to
exactly-once VISIBLE output: the service acquires a leader epoch
(bridge/lease.py), stamps every MatchOut produce with
`(epoch, out_seq)` (wire.ProduceStamp), and the broker fences stale
epochs and suppresses replayed stamps (bridge/broker.py), so the
durable MatchOut log itself carries each record exactly once.
`follower=True` runs the service as a hot-standby replica: produces are
discarded (but out_seq still counts them, so a promotion can continue
the stamp stream), checkpoints are skipped, and no lease is held until
promotion (bridge/replica.py).
"""

from __future__ import annotations

import sys
from typing import Optional

from kme_tpu import faults

TOPIC_IN = "MatchIn"    # topic.js:17
TOPIC_OUT = "MatchOut"  # topic.js:21

_DEVICE_MS_HELP = ("what the host waited on the device for the last "
                   "batch (ms): dispatch + fetch on the serial path; "
                   "under --pipeline only the fetch's wait, the device "
                   "work the pipeline hid is not in it")


_LIFECYCLE_COUNTERS = {
    "symbols_listed": "ADD_SYMBOLs the seq router routed to a lane "
                      "that held no book (the device accepts those)",
    "symbols_settled": "PAYOUTs the seq router routed to a listed "
                       "symbol: its books wiped, its positions paid out",
    "lanes_released": "lanes that went back to the router's pool, one "
                      "a settled symbol",
    "lanes_reused": "new symbol ids that took a lane another id had "
                    "held before",
    "unlisted_rejects": "trades, cancels and barriers host-rejected "
                        "because their symbol id holds no lane",
    "routes_made": "trades the seq router wrote an oid route for",
    "routes_dropped": "oid routes the seq router dropped because their "
                      "order left the book (refused, filled, cancelled)",
    "cancels_routed": "cancels that found their order's route and went "
                      "to the device",
    "cancels_host_rejected": "cancels host-rejected because no route "
                             "names their oid",
    "barrier_wiped_orders": "resting orders the seq kernel's barrier "
                            "section took off the books (its own count)",
    "barrier_credited_positions": "positions a YES payout credited in "
                                  "the seq kernel (its own count)",
}

_FETCH_COUNTERS = {
    "fetch_early": "seq dispatches whose output prefix was sliced and "
                   "sent to the host with them, ahead of any later scan",
    "fetch_ready": "seq fetches that found that prefix's slice already "
                   "run (jax.Array.is_ready(): no wait for the "
                   "device's queue)",
    "fetch_second_rounds": "seq fetches that needed a second round of "
                           "slices: a call's fills overflowed the hint "
                           "its prefix was cut by",
}


class _SnapshotWriter:
    """One snapshot on its way to the disk beside the serve loop: a
    thread that runs `write` (MatchService._snapshot_save over a
    captured boundary) from the moment it is made. At most one is in
    flight; the serve thread takes it back with `join`, which raises,
    there, what the write raised. `write` (and with it the boundary's
    device state) is the thread's argument and held nowhere else: it
    goes when the write ends, not when the writer is taken back.
    A daemon: a process that dies without close() leaves a `.tmp`
    behind like any crash inside the write, and is not kept alive."""

    def __init__(self, write) -> None:
        import threading

        self._error = None
        self._thread = threading.Thread(
            target=self._run, args=(write,), name="kme-snapshot-writer",
            daemon=True)
        self._thread.start()

    def _run(self, write) -> None:
        try:
            write()
        except BaseException as e:      # raised again by join()
            self._error = e

    def join(self) -> None:
        self._thread.join()
        if self._error is not None:
            raise self._error


class MatchService:
    # the spans that PARTITION one iteration of the serve loop (names of
    # its PhaseTimer): what is left of the loop's wall after their sum
    # is time no span covers (benchmark metric loop_other_ms_per_batch)
    LOOP_SPANS = ("poll_wait", "parse_batch", "session_submit",
                  "session_collect", "process_wire", "produce_buffer",
                  "produce_lines", "publish_batch", "checkpoint")
    # spans nested inside those: `checkpoint` holds the drain, the
    # broker sync, the wait for the snapshot before (where its file is
    # still being written) and the handoff of this one. And one beside
    # them: `snapshot_save`, the making of a snapshot's file from start
    # to durable, on the snapshot writer's thread for a SeqSession (its
    # three stages are spans of the session's timer) and inside
    # `snapshot_handoff` for the engines that are saved there; of its
    # wall, `snapshot_writer_wait` is what the loop waited for
    INNER_SPANS = ("engine_refresh", "checkpoint_drain", "broker_sync",
                   "snapshot_writer_wait", "snapshot_handoff",
                   "snapshot_save")
    # spans BETWEEN those of LOOP_SPANS: named parts of what
    # loop_other_ms_per_batch reads, which still subtracts LOOP_SPANS
    # alone (loop_unnamed_ms_per_batch subtracts these too): the
    # per-order latency stamping, the broker's watermark commit (a wait
    # for its lock), and the publishing of these very gauges
    BETWEEN_SPANS = ("latency_stamp", "commit_watermark", "publish_spans")
    # spans of the observability planes, in the gauges of a service that
    # has the plane on: the flight recorder's (journal_lines: a
    # pipelined batch's buffer turned into what the journal takes —
    # its records, by the native walk inside Journal.record_buffer, or
    # its lines; journal_record: all of Journal.record_buffer /
    # record_batch on the serve thread, with journal_events, the
    # batch's journal_write and the auditor's audit_observe inside it;
    # journal_write: the latency stamps' write too), the auditor's
    # snapshot-cadence compare, the metrics history's append
    # (heartbeat cadence)
    JOURNAL_SPANS = ("journal_lines", "journal_record", "journal_events",
                     "journal_write")
    AUDIT_SPANS = ("audit_observe", "audit_check_engine")
    TSDB_SPANS = ("tsdb_append",)

    def __init__(self, broker, engine: str = "seq",
                 compat: str = "fixed", batch: int = 1024,
                 symbols: int = 1024, accounts: int = 4096,
                 slots: int = 128, max_fills: int = 16,
                 strict: bool = False,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 4096,
                 checkpoint_keep: Optional[int] = None,
                 journal=None, journal_rotate_mb: Optional[int] = None,
                 journal_fsync: str = "off",
                 journal_keep: Optional[int] = None,
                 audit: bool = False,
                 audit_repro_dir: Optional[str] = None,
                 annotate_rejects: bool = False,
                 exactly_once: bool = False,
                 follower: bool = False,
                 pipeline: int = 0,
                 group=None,
                 slo=None,
                 trace_spans: bool = False,
                 tsdb: Optional[str] = None,
                 profile: bool = False,
                 profile_artifact: Optional[str] = None,
                 capture_dir: Optional[str] = None,
                 capture_p99_us: Optional[int] = None,
                 watch=None, clock=None) -> None:
        if engine not in ("seq", "oracle", "native"):
            raise ValueError(f"unknown engine {engine!r}")
        if compat not in ("java", "fixed"):
            raise ValueError(f"unknown compat {compat!r}")
        # java-mode seq sessions checkpoint via the seqjava canonical
        # form (runtime/javasnap.py) since round 5 — no engine/compat
        # combination is excluded from durability
        self.broker = broker
        # the clock seam (bridge/clock.py): every sleep/backoff and
        # interval read below goes through this object so the simulator
        # can own time; production passes None and pays one attribute
        # hop to the shared WallClock
        from kme_tpu.bridge.clock import WALL

        self.clock = clock or WALL
        # multi-leader shard group (ISSUE 9): group=(k, n) namespaces
        # every durable artifact this service touches on the broker —
        # its input/output topics become "MatchIn.g{k}"/"MatchOut.g{k}"
        # and front-injected cross-shard transfer legs are diverted to
        # a stamped per-group "Xfer.g{k}" topic (the durable dedup
        # evidence) instead of the merged MatchOut feed. Lease, journal
        # and checkpoint namespacing happens one level up: kme-serve
        # gives each group its own --checkpoint-dir root.
        if group is not None:
            gk, gn = int(group[0]), int(group[1])
            if gn < 1 or not (0 <= gk < gn):
                raise ValueError(f"group {gk}/{gn} out of range")
        else:
            gk, gn = 0, 1
        self.group_id, self.group_count = gk, gn
        grouped = group is not None and gn > 1
        self.topic_in = f"{TOPIC_IN}.g{gk}" if grouped else TOPIC_IN
        self.topic_out = f"{TOPIC_OUT}.g{gk}" if grouped else TOPIC_OUT
        self.topic_xfer = f"Xfer.g{gk}" if grouped else None
        # cross-shard balance-transfer ledger (checkpointed in the
        # snapshot's extra meta so a resume reports continuous totals):
        # legs = applied transfer legs, credits/debits = amounts moved
        # in/out of this group's accounts, rejected = legs the engine
        # refused (shadow-ledger shortfall at the front door),
        # broadcasts = CREATE_BALANCE copies suppressed here
        self._xfer = {"legs": 0, "credits": 0, "debits": 0,
                      "rejected": 0, "broadcasts": 0}
        self._xfer_mark = None
        if grouped:
            from kme_tpu.bridge.front import _MARK_SUB

            self._xfer_mark = _MARK_SUB
            create = getattr(broker, "create_topic", None)
            if create is not None:
                from kme_tpu.bridge.broker import BrokerError

                try:
                    create(self.topic_xfer)
                except BrokerError:
                    pass    # already provisioned
        self.engine_kind = engine
        self._compat = compat
        self.batch = batch
        self.strict = strict
        self.offset = 0
        self._session = self._oracle = self._native = None
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = checkpoint_every
        self.checkpoint_keep = checkpoint_keep
        self._last_ckpt_offset = 0
        # the snapshot being written beside the loop (_SnapshotWriter),
        # until the serve thread has taken it back
        self._snap_writer = None
        self._req_symbols, self._req_accounts = symbols, accounts
        self._req_slots, self._req_max_fills = slots, max_fills
        self._last_engine_pub = 0.0
        self._journal_arg = journal
        self._journal_rotate_mb = journal_rotate_mb
        self._journal_fsync = journal_fsync
        self._journal_keep = journal_keep
        self._audit_arg = audit
        self._audit_repro_dir = audit_repro_dir
        self.annotate_rejects = annotate_rejects
        self.exactly_once = exactly_once
        self.follower = follower
        # double-buffered serving (SURVEY.md §7 H5): up to `pipeline`
        # batches stay in flight — batch N+1's parse/plan/dispatch runs
        # under batch N's device step; offsets/checkpoints advance only
        # at collect time, so the durability contract is unchanged.
        # Needs the seq engine (submit/collect), fixed mode and the
        # native host runtime (buffer reconstruction). A configuration
        # that cannot pipeline serves serial with a note, and so does
        # an explicit KME_NATIVE=0; a native library that failed to
        # build is an error (native.require_library).
        self.pipeline = 0
        self._pipe = None
        if pipeline:
            from kme_tpu.native import require_library

            if (engine == "seq" and compat == "fixed"
                    and not annotate_rejects
                    and require_library() is not None):
                import collections

                self.pipeline = int(pipeline)
                self._pipe = collections.deque()
            else:
                print("kme-serve: --pipeline needs engine=seq, "
                      "compat=fixed, the native host runtime and no "
                      "--annotate-rejects; serving serial",
                      file=sys.stderr)
        self.epoch: Optional[int] = None  # leader fencing token
        self.out_seq = 0                  # next MatchOut produce stamp
        # output-stream records handed to the broker and the calls that
        # took them (counters matchout_records / matchout_produce_calls),
        # and those of them that went inside a buffer run (counter
        # matchout_records_buffered)
        self._out_calls = self._out_records = self._out_buffered = 0
        # the run of organic records _produce_records is gathering for
        # one produce_stamped call; None where records go out one by one
        self._run = None
        if exactly_once and checkpoint_dir is None:
            raise ValueError("exactly_once needs checkpoint_dir (the "
                             "leader-epoch lease lives there)")
        if exactly_once and annotate_rejects:
            # REJ annotations interleave at BATCH boundaries, and batch
            # boundaries are not deterministic across a resume — the
            # out_seq stamp stream would diverge from the original and
            # the broker would dedup the wrong records
            raise ValueError("exactly_once is incompatible with "
                             "annotate_rejects (REJ records interleave "
                             "at non-deterministic batch boundaries)")
        self.degraded = None        # set by the invariant auditor
        # distributed tracing (telemetry/dtrace.py): journal per-order
        # "span" events keyed by local_tid(group, broker offset) — the
        # stitcher joins them to the front's global trace ids offline
        self.trace_spans = bool(trace_spans)
        # continuous profiling & history (ISSUE 16): metrics history on
        # disk at heartbeat cadence, the sampling host profiler, the
        # per-backend transfer/compute artifact, trigger captures
        self._tsdb_arg = tsdb
        self._profile_arg = bool(profile)
        self._profile_artifact = profile_artifact
        self._capture_dir = capture_dir
        self._capture_p99_us = capture_p99_us
        self.tsdb = None
        self.profiler = None
        self.capture = None
        # live watchpoints (ISSUE 17): deterministic predicates over the
        # shadow ledger, evaluated at every batch barrier. Read-only:
        # they never gate admission and never touch MatchOut bytes
        self._watch_arg = list(watch or [])
        self.watch = None
        # monotonic heartbeat-sample sequence: persisted across restart
        # via the checkpoint's additive `extra` meta so TSDB ingestion
        # dedups replayed samples exactly like the broker dedups
        # (epoch, out_seq) produce stamps
        self.sample_seq = 0
        self._slo_arg = slo         # dict of SLO kwargs, or None
        self.slo = None
        self._slo_reason = None
        # adaptive-shed annotations: controller sheds happen on the TCP
        # produce thread; queue the details and emit REJ rows (with
        # backlog/threshold/state) from the poll thread so shed storms
        # are debuggable from the output stream alone
        self._shed_pending = None
        if (annotate_rejects
                and getattr(broker, "overload", None) is not None
                and hasattr(broker, "shed_observer")):
            import collections

            q = collections.deque(maxlen=65536)
            self._shed_pending = q
            broker.shed_observer = lambda _topic, d: q.append(d)
        # control-plane flight recorder (telemetry/events.py): the serve
        # process's own durable event stream — lease grants, overload
        # state transitions — living next to the checkpoints so
        # kme-events merges it with the supervisor/standby logs. The
        # heartbeat exports its committed-bytes cursor
        # (events_last_offset/events_lag_bytes) so kme-agg can flag a
        # frozen recorder under an otherwise-live process
        self.events = None
        if checkpoint_dir is not None:
            from kme_tpu.telemetry import events as cpevents

            src = "follower" if self.follower else "serve"
            if self.group_count > 1:
                src = f"{src}.g{self.group_id}"
            try:
                self.events = cpevents.open_log(
                    checkpoint_dir, src, clock=self.clock.time)
            except OSError:
                self.events = None
            ctl = getattr(broker, "overload", None)
            if self.events is not None and ctl is not None:
                ev = self.events
                gid = self.group_id if self.group_count > 1 else None
                names = type(ctl).STATE_NAMES

                def _overload_event(prev, new):
                    ev.emit("overload.transition",
                            severity="warn" if new else "info",
                            group=gid, from_state=names[prev],
                            to_state=names[new],
                            backoff_ms=ctl.backoff_ms)

                ctl.on_transition = _overload_event
        # what this process runs on (start-up line + heartbeat). The
        # device engines resolve the backend HERE, before any state is
        # built or restored: no TPU and no JAX_PLATFORMS=cpu raises
        # (kme_tpu/_jaxsetup.py). Host engines stay off jax.
        self.runs_on = {}
        if engine == "seq":
            from kme_tpu import _jaxsetup

            self.runs_on = _jaxsetup.describe()
        import time as _t

        t_session0 = _t.perf_counter()
        self._startup_session_s = 0.0
        resumed = False
        if checkpoint_dir is not None:
            resumed = self._try_resume(engine, compat)
        if resumed:
            self._startup_session_s = _t.perf_counter() - t_session0
            self._restore_sample_seq()
            self._init_exactly_once(resumed=True)
            self._init_telemetry()
            self._init_observability(resumed=True)
            self._commit_watermark()
            return
        if engine == "seq":
            self._session = self._make_seq_session()
        elif engine == "native":
            from kme_tpu.native.oracle import NativeOracleEngine

            kw = ({"book_slots": slots, "max_fills": max_fills}
                  if compat == "fixed" else {})
            self._native = NativeOracleEngine(compat, **kw)
        elif engine == "oracle":
            from kme_tpu.oracle import OracleEngine

            # the capacity envelope is a fixed-mode concept; java compat
            # replicates the reference's unbounded stores
            kw = ({"book_slots": slots, "max_fills": max_fills}
                  if compat == "fixed" else {})
            self._oracle = OracleEngine(compat, **kw)
        else:
            raise ValueError(f"unknown engine {engine!r}")
        self._startup_session_s = _t.perf_counter() - t_session0
        self._init_exactly_once(resumed=False)
        self._init_telemetry()
        self._init_observability(resumed=False)
        self._commit_watermark()

    def _restore_sample_seq(self) -> None:
        """Heartbeat sample_seq continuation across a resume — read
        from the snapshot's additive extra meta REGARDLESS of the
        exactly-once setting (metrics history is not an exactly-once
        feature; any checkpointed service keeps a continuous TSDB
        sequence)."""
        from kme_tpu.runtime import checkpoint as ck

        extra = ck.snapshot_extra(self.checkpoint_dir, self.offset)
        try:
            self.sample_seq = max(0, int(extra.get("sample_seq", 0)))
        except (TypeError, ValueError):
            self.sample_seq = 0

    def _init_exactly_once(self, resumed: bool) -> None:
        """Exactly-once startup: restore the produce-stamp cursor from
        the snapshot's extra meta, then (leaders only) acquire the next
        leader epoch and fence every predecessor at the broker. The
        explicit fence matters: a promoted/restarted broker reload only
        learns PRIOR epochs from the log stamps, so without it a zombie
        old leader holding the previous epoch would still get through.
        A follower restores the cursor but holds no lease — its
        produces are discarded until promotion
        (bridge/replica.py)."""
        if not self.exactly_once:
            return
        if resumed:
            from kme_tpu.runtime import checkpoint as ck

            extra = ck.snapshot_extra(self.checkpoint_dir, self.offset)
            try:
                self.out_seq = int(extra.get("out_seq", 0))
            except (TypeError, ValueError):
                self.out_seq = 0
            pending = extra.get("pending_reserve")
            if isinstance(pending, dict):
                # cross-shard transfer ledger survives the restart so
                # replayed legs regenerate the same totals (the broker
                # watermark suppresses their duplicate stamps)
                for k in self._xfer:
                    try:
                        self._xfer[k] = int(pending.get(k, 0))
                    except (TypeError, ValueError):
                        pass
        if self.follower:
            return
        import inspect

        from kme_tpu.bridge import lease

        try:
            params = inspect.signature(self.broker.produce).parameters
        except (TypeError, ValueError):
            params = {}
        if "out_seq" not in params:
            # e.g. the Kafka transport: no produce stamps, no fencing —
            # fall back loudly to the at-least-once contract
            print("kme-serve: broker transport has no produce stamps; "
                  "exactly-once disabled (at-least-once output)",
                  file=sys.stderr)
            self.exactly_once = False
            return
        self.epoch = lease.acquire(self.checkpoint_dir,
                                   events=self.events)
        fence = getattr(self.broker, "fence", None)
        if fence is not None:
            fence(self.epoch)
        print(f"kme-serve: leader epoch {self.epoch} (out_seq resumes "
              f"at {self.out_seq})", file=sys.stderr)

    def _commit_watermark(self) -> None:
        """Advance the broker's consumer watermark for MatchIn — this
        arms (and continuously re-arms) the bounded-ingress max_lag
        check: producers past the bound get a wire-level rej_overload
        (BrokerOverload) instead of growing the backlog unboundedly."""
        commit = getattr(self.broker, "commit", None)
        if commit is None:
            return
        from kme_tpu.bridge.broker import BrokerError

        with self._span("commit_watermark"):
            try:
                commit(self.topic_in, self.offset)
            except BrokerError:
                pass        # topic not provisioned yet / transport blip

    def _init_observability(self, resumed: bool) -> None:
        self._init_planes(resumed)
        if self._plane_spans:
            # the planes' counters and gauges, before the first heartbeat
            self._publish_spans()

    def _init_planes(self, resumed: bool) -> None:
        """Flight recorder + invariant auditor wiring. The journal
        subscribes the auditor as an observer, so the shadow replay
        sees exactly what lands in the journal file; on resume the
        journal is rewound to the snapshot offset (the at-least-once
        tail replay would otherwise journal twice) and the auditor is
        seeded from the restored engine state."""
        import os

        from kme_tpu.telemetry import InvariantAuditor, Journal

        self.journal = None
        self.auditor = None
        j = self._journal_arg
        if isinstance(j, str):
            rb = (self._journal_rotate_mb * (1 << 20)
                  if self._journal_rotate_mb else None)
            guard = None
            if self.checkpoint_dir is not None:
                # retention coupling: rotated journal segments may only
                # be pruned once every event in them is older than the
                # oldest retained snapshot — a standby restoring that
                # snapshot must still replay to the tip
                ckpt_dir = self.checkpoint_dir

                def guard():
                    from kme_tpu.runtime import checkpoint as ck

                    return ck.oldest_retained_offset(ckpt_dir)
            j = Journal(j, rotate_bytes=rb, fsync=self._journal_fsync,
                        rotate_keep=self._journal_keep,
                        retention_guard=guard, timer=self._ptimer)
        self.journal = j
        if j is not None and resumed:
            j.rewind_to_offset(self.offset)
        # journal-side corruption drill (KME_AUDIT_TAMPER=journal_fill_qty):
        # one-shot, bumps the first journaled fill's taker quantity in a
        # COPY of the output line groups — the journal then LIES about a
        # batch while MatchOut stays untouched, which is exactly the
        # divergence class `kme-xray --bisect` must pin to a batch (the
        # auditor, a journal observer, trips on the same tampered events
        # and its repro dump carries the ready-to-run bisect line)
        self._journal_tamper = None
        self._tampered_batch = None
        tamper_env = os.environ.get("KME_AUDIT_TAMPER", "")
        if j is not None and tamper_env.startswith("journal_fill_qty"):
            from kme_tpu import opcodes as op
            import json as _json

            # "journal_fill_qty@K" arms the tamper from the K-th
            # journaled batch on (default 0) — so the bisect drill has
            # a non-trivial prefix of clean batches to rule out
            _, _, at_s = tamper_env.partition("@")
            arm_batch = int(at_s) if at_s.isdigit() else 0
            done = []
            seen = [0]     # record_batch calls == journal batch ids

            def line_tamper(out):
                b = seen[0]
                seen[0] += 1
                if done or b < arm_batch:
                    return out
                for gi, grp in enumerate(out):
                    if len(grp) < 4:   # no fill pairs (IN + result echo)
                        continue
                    for k in range(1, len(grp) - 1, 2):
                        key, _, val = grp[k + 1].partition(" ")
                        try:
                            tk = _json.loads(val)
                        except ValueError:
                            continue
                        if tk.get("action") not in (op.BOUGHT, op.SOLD):
                            continue   # not a fill-pair taker echo
                        tk["size"] = int(tk["size"]) + 1
                        new = list(grp)
                        new[k + 1] = (f"{key} "
                                      f"{_json.dumps(tk, separators=(',', ':'))}")
                        out = list(out)
                        out[gi] = new
                        done.append(True)
                        self._tampered_batch = b
                        return out
                return out

            self._journal_tamper = line_tamper
        self._init_profiling(resumed)
        self._init_watch(resumed)
        if not self._audit_arg:
            return
        if self._compat != "fixed":
            print("kme-serve: --audit needs fixed-mode money semantics; "
                  "auditing disabled for compat=java", file=sys.stderr)
            return
        if j is None:
            raise ValueError("--audit requires --journal-out (the "
                             "auditor replays the journal stream)")

        def on_violation(violations, dump):
            self.degraded = violations[0]["kind"]
            where = f" (repro: {dump})" if dump else ""
            print(f"kme-serve: AUDIT VIOLATION {violations[0]}{where}",
                  file=sys.stderr)

        self.auditor = InvariantAuditor(
            registry=self.telemetry, repro_dir=self._audit_repro_dir,
            on_violation=on_violation,
            checkpoint_ref=self.checkpoint_dir,
            journal_ref=getattr(j, "path", None),
            log_ref=getattr(self.broker, "_persist_dir", None),
            timer=self._ptimer, counts_live=False)
        if resumed and self._session is not None:
            self.auditor.seed(self._session.export_state(),
                              self._session.histograms())
        # deliberate-corruption hook for end-to-end violation tests:
        # KME_AUDIT_TAMPER=fill_qty bumps the first journaled fill's
        # quantity by one, which must trip the auditor
        if os.environ.get("KME_AUDIT_TAMPER") == "fill_qty":
            done = []

            def tamper(events):
                if not done:
                    for ev in events:
                        if ev.get("e") == "fill":
                            ev["qty"] += 1
                            done.append(True)
                            break
                return events

            self.auditor.tamper = tamper
        j.observers.append(self.auditor.observe)

    def _init_watch(self, resumed: bool) -> None:
        """Live watchpoint wiring (ISSUE 17). Predicates evaluate
        inline at the batch barrier — directly against the serving
        OracleEngine when that IS the engine (zero-derivation: an
        armed watchpoint is free), else against an auditor-shaped
        shadow ledger fed from the batch's own (untampered) output
        lines. Both are pure functions of exported state, so two
        seeded runs fire identical (offset, predicate) hit sets. Hits
        write bounded TriggerCapture-style captures into --capture-dir
        carrying the offset, the batch's slow-order trace exemplars
        and the `kme-xray` one-liner that reproduces the hit
        offline."""
        self.watch = None
        if not self._watch_arg:
            return
        if self._compat != "fixed":
            print("kme-serve: --watch needs fixed-mode money "
                  "semantics; watchpoints disabled for compat=java",
                  file=sys.stderr)
            return
        from kme_tpu.telemetry.xray import WatchEngine

        repro = {"log_dir": getattr(self.broker, "_persist_dir", None),
                 "topic": self.topic_in,
                 "checkpoint_dir": self.checkpoint_dir}
        self.watch = WatchEngine(
            self._watch_arg, out_dir=self._capture_dir,
            registry=self.telemetry, repro=repro)
        if resumed:
            state = None
            if self._session is not None:
                state = self._session.export_state()
            elif self._oracle is not None and not self._oracle.java:
                state = self._oracle.export_state()
            if state is not None:
                self.watch.seed(state)
            else:
                print("kme-serve: --watch cannot seed its shadow from "
                      "a resumed native engine; watchpoints disabled",
                      file=sys.stderr)
                self.watch = None

    def _init_profiling(self, resumed: bool) -> None:
        """Continuous profiling & history wiring (ISSUE 16): the TSDB
        heartbeat feed, the sampling host profiler, and the SLO/p99
        trigger capture. All additive: a failure to open the history
        store degrades the observability surface, never the engine."""
        if self._tsdb_arg is not None:
            from kme_tpu.telemetry.tsdb import TSDB

            source = ("follower" if self.follower else "serve")
            if self.group_count > 1:
                source = f"{source}.g{self.group_id}"
            try:
                self.tsdb = TSDB(self._tsdb_arg, source=source)
            except (OSError, ValueError) as e:
                print(f"kme-serve: TSDB disabled ({e})", file=sys.stderr)
            if self.tsdb is not None and not resumed:
                # no checkpoint cursor to continue: adopt the store's
                # high-water mark so a plain restart keeps appending
                # instead of deduping against its own history
                self.sample_seq = max(self.sample_seq,
                                      self.tsdb.next_seq())
        if self._profile_arg:
            from kme_tpu.telemetry.profiler import StageProfiler

            self.profiler = StageProfiler(registry=self.telemetry)
            self.profiler.start()
        if self._capture_dir is not None:
            from kme_tpu.telemetry.profiler import TriggerCapture

            self.capture = TriggerCapture(
                self._capture_dir, p99_us=self._capture_p99_us,
                registry=self.telemetry)

    def close(self) -> None:
        """Finish what is in flight (the pipeline's batches, the
        snapshot being written: what its writer raised is raised here),
        then flush + close the flight recorder (serve shutdown path)."""
        try:
            if getattr(self, "_pipe", None):
                self._drain_pipeline()
            if getattr(self, "_snap_writer", None) is not None:
                self._snapshot_writer_wait()
        finally:
            self._close_planes()

    def _close_planes(self) -> None:
        if getattr(self, "profiler", None) is not None:
            self.profiler.stop()
        if getattr(self, "_profile_artifact", None) is not None:
            from kme_tpu.telemetry.profiler import (device_plane,
                                                    write_transfer_artifact)

            try:
                # a session-less engine (oracle) still records the
                # host plane: backend + measured H2D bandwidth
                plane = device_plane(session=self._session)
                write_transfer_artifact(self._profile_artifact, plane)
                print(f"kme-serve: transfer/compute artifact written to "
                      f"{self._profile_artifact}", file=sys.stderr)
            except (OSError, ValueError) as e:
                print(f"kme-serve: transfer artifact failed ({e})",
                      file=sys.stderr)
        if getattr(self, "tsdb", None) is not None:
            self.tsdb.close()
        if getattr(self, "events", None) is not None:
            self.events.close()
        if getattr(self, "journal", None) is not None:
            self.journal.close()

    def _init_telemetry(self) -> None:
        """The service's metrics surface (/metrics, heartbeat). Session
        engines already own a Registry — share it so engine counters,
        histograms and service counters expose through ONE endpoint;
        host-only engines (native/oracle) get a service-local one.

        Supervision provenance rides in via environment: kme-supervise
        stamps each incarnation with its restart ordinal and the wall
        time of the failure it is recovering from, so restarts_total
        and recovery_seconds surface on THIS process's /metrics."""
        import os
        import time

        from kme_tpu.telemetry import Registry

        self.telemetry = (self._session.telemetry
                          if self._session is not None else Registry())
        try:
            ordinal = int(os.environ.get("KME_RESTART_ORDINAL", "0"))
        except ValueError:
            ordinal = 0
        self.telemetry.gauge("restarts_total").set(ordinal)
        failed_at = os.environ.get("KME_FAILED_AT")
        if failed_at:
            try:
                self.telemetry.gauge("recovery_seconds").set(
                    round(max(0.0, self.clock.time() - float(failed_at)),
                          3))
            except ValueError:
                pass
        self._init_latency()

    def _init_latency(self) -> None:
        """End-to-end latency attribution: one always-on streaming
        quantile histogram per pipeline stage (telemetry/registry.py
        LatencyHistogram — O(1) memory, lock-consistent snapshots).

        Stage boundaries, all measured from the broker-admission stamp
        (Record.ats — the INTENDED start, so queueing under overload
        shows up as latency instead of being coordinated-omission'd
        away):
          ingress — admission -> the serve loop fetches the record
          plan    — host batch planning (session plan_s delta, charged
                    to every order in the batch)
          device  — dispatch + device fetch (dispatch_s + fetch_s)
          inflight — pipelined path only: session_submit returned ->
                    _collect_one takes the batch up (the loop submits up
                    to `pipeline` more batches in between); no other
                    stage holds this interval
          produce — MatchOut produce wall time for the batch
          e2e     — admission -> the batch's outputs are visible
          consume — admission -> a consumer's fetch delivers the
                    MatchOut record (observed broker-side via
                    deliver_observer, since serve hosts the broker)
        """
        import threading
        import time as _t

        from kme_tpu.telemetry import PhaseTimer

        t = self.telemetry
        self._lat = {
            s: t.latency(f"lat_{s}", h) for s, h in (
                ("ingress", "broker admission to serve-loop fetch"),
                ("plan", "host batch planning"),
                ("device", "device dispatch + fetch"),
                ("inflight", "submit returned to collect begun "
                             "(--pipeline > 0)"),
                ("produce", "MatchOut produce wall time"),
                ("e2e", "broker admission to produce visible"),
                ("consume", "broker admission to consumer delivery"),
            )}
        if self.topic_xfer is not None:
            self._lat["transfer"] = t.latency(
                "transfer_rtt", "cross-shard transfer leg: durable "
                "stamped produce to the group Xfer topic")
        # serve-side spans land on their own trace track when a
        # TraceRecorder is installed (kme-serve --trace-out)
        self._ptimer = PhaseTimer(track="serve")
        self._plane_spans = (
            (self.JOURNAL_SPANS if self._journal_arg is not None else ())
            + (self.AUDIT_SPANS if self._audit_arg else ())
            + (self.TSDB_SPANS if self._tsdb_arg is not None else ()))
        self._batch_ordinal = 0
        self._last_produce_s = 0.0
        self._phase_snap = {}
        # one heartbeat writer at a time: the beater thread and the
        # serve loop share `<health-file>.tmp`
        self._hb_lock = threading.Lock()
        # every span and counter is in the registry before the first
        # heartbeat: a reader of two snapshots needs the key in both
        self._loop_t0 = _t.perf_counter()
        self._process_cpu0 = _t.process_time()
        # the poll thread's CPU seconds since then (gauge serve_cpu_s)
        # and where they were last read: (thread, its thread_time())
        self._serve_cpu_s = 0.0
        self._serve_cpu_mark = (threading.get_ident(), _t.thread_time())
        # None: no batch yet; (ordinal, t0): the first one is in
        # flight since t0; False: gauge first_batch_s is set
        self._first_batch = None
        jaxsetup = sys.modules.get("kme_tpu._jaxsetup")
        t.publish_gauges({
            "startup_import_s": 0.0, "startup_backend_s": 0.0,
            **(jaxsetup.startup if jaxsetup is not None else {}),
            "startup_session_s": round(self._startup_session_s, 3),
            "first_batch_s": 0.0, **self.state_homes()})
        self._publish_pos_store({})
        t.gauge("left_device_at_offset",
                "input offset of the batch at which a java-mode seq "
                "service left the device for the native engine "
                "(_degrade_to_native); -1: it has not").set(-1)
        self._publish_spans()
        # slowest recent orders, worst first: published as registry
        # exemplars so a cluster p99 outlier (kme-agg) resolves to a
        # concrete waterfall (kme-trace --order AID:OID)
        self._slow: list = []
        if self._slo_arg is not None:
            from kme_tpu.telemetry.slo import SLO

            self.slo = SLO(t, **self._slo_arg)
        # consume-stage visibility: serve hosts the broker, so consumer
        # receipt of MatchOut records is observable in-process
        if getattr(self.broker, "deliver_observer", None) is None \
                and hasattr(self.broker, "deliver_observer"):
            lat_consume = self._lat["consume"]
            topic_out = self.topic_out

            def _on_deliver(topic, recs, now_us):
                if topic != topic_out:
                    return
                # a Run (broker.fetch_runs) has one ats for its n
                # records: one observation with its count
                for r in recs:
                    ats = getattr(r, "ats", None)
                    if ats is not None:
                        lat_consume.observe(max(0, now_us - ats) * 1e-6,
                                            getattr(r, "n", 1))

            self.broker.deliver_observer = _on_deliver

    _EXEMPLARS = 8

    def _stamp_latency(self, in_atss, atss, offs, oids, aids, fetch_us,
                       done_us, plan_d, dev_d, batch) -> None:
        """The per-order stamping of one batch, serial and pipelined
        path alike; its callers open span `latency_stamp` around it and
        the lists they make for it. `lat_ingress` for every record
        fetched (`in_atss`: admission stamps), `lat_e2e` and the
        overload controller's feed for every message served (`atss`),
        then _stamp_orders. Three Python walks over the batch, always
        on and all for instrumentation: the span says what they cost."""
        lat = self._lat
        for ats in in_atss:
            if ats is not None:
                # ingress = broker admission -> the loop's fetch; per
                # record, from the intended-start stamp
                lat["ingress"].observe(max(0, fetch_us - ats) * 1e-6)
        if not atss:
            return
        e2e_hot = 0.0
        for ats in atss:
            if ats is not None:
                d = max(0, done_us - ats) * 1e-6
                lat["e2e"].observe(d)
                if d > e2e_hot:
                    e2e_hot = d
        ctl = getattr(self.broker, "overload", None)
        if ctl is not None and e2e_hot > 0:
            # admission-to-produce feed for the degradation state
            # machine (latency can trip shedding before backlog does)
            ctl.observe_latency(e2e_hot)
        # full batch wall per order (what the order EXPERIENCED — same
        # convention as the histograms), not an amortized share
        self._stamp_orders(
            offs, oids, aids, atss, fetch_us, done_us, int(plan_d * 1e6),
            int(dev_d * 1e6), int(self._last_produce_s * 1e6), batch=batch)

    def _stamp_orders(self, offs, oids, aids, atss, fetch_us, done_us,
                      plan_us, dev_us, prod_us, batch) -> None:
        """Per-order stage attribution, shared by the serial and
        pipelined collect paths: journal "lat" stamps, "span" events
        when tracing is on (--trace-spans), and the slow-order exemplar
        surface. Span bounds are contiguous from the admission stamp —
        the exact layout telemetry/dtrace.py synthesizes from "lat"
        events, so traced and untraced journals stitch identically.
        Span identity is local_tid(group, broker offset): pure durable
        identity, so a crash-replay re-emits the SAME ids and the
        stitcher dedups the overlap by (group, off, kind)."""
        n = len(offs)
        if not n:
            return
        from kme_tpu.telemetry.dtrace import local_tid

        g = self.group_id
        if self.journal is not None:
            import numpy as np

            # the stamps as columns; an order with no admission stamp
            # reads 0 in both
            has = np.array([ats is not None for ats in atss])
            ats = np.array([ats or 0 for ats in atss], np.int64)
            self.journal.record_latency_columns(
                np.array(offs, np.int64), np.array(oids, np.int64),
                np.where(has, np.maximum(0, fetch_us - ats), 0),
                plan_us, dev_us, prod_us,
                np.where(has, np.maximum(0, done_us - ats), 0),
                batch=batch)
            if self.trace_spans:
                spans = []
                for i in range(n):
                    t = atss[i] if atss[i] is not None else fetch_us
                    tid = local_tid(g, offs[i])
                    for kind, dur in (
                            ("ingress", (max(0, fetch_us - atss[i])
                                         if atss[i] is not None
                                         else 0)),
                            ("plan", plan_us), ("device", dev_us),
                            ("produce", prod_us)):
                        spans.append(
                            {"kind": kind, "g": g, "off": offs[i],
                             "oid": oids[i], "aid": aids[i],
                             "tid": tid, "ptid": 0, "t0": t,
                             "t1": t + dur, "li": -1})
                        t += dur
                self.journal.record_spans(spans, batch=batch)
        cap = self._EXEMPLARS
        floor = (self._slow[-1]["e2e_us"]
                 if len(self._slow) >= cap else -1)
        changed = False
        for i in range(n):
            if atss[i] is None:
                continue
            e2e = max(0, done_us - atss[i])
            if e2e > floor or len(self._slow) < cap:
                self._slow.append(
                    {"tid": local_tid(g, offs[i]), "off": offs[i],
                     "oid": oids[i], "aid": aids[i], "g": g,
                     "e2e_us": e2e})
                changed = True
        if changed:
            self._slow.sort(key=lambda x: -x["e2e_us"])
            del self._slow[cap:]
            self.telemetry.set_exemplars(self._slow)

    # ------------------------------------------------------------------
    # durability: snapshot at batch boundaries, resume = load + replay
    # the MatchIn tail from the snapshot offset (at-least-once, like the
    # reference with exactly-once commented out — KProcessor.java:29)

    def _make_seq_session(self):
        from kme_tpu.runtime.seqsession import SeqSession

        return SeqSession(self._seq_cfg())

    def _seq_cfg(self):
        from kme_tpu.engine import seq as SQ

        slots = self._req_slots
        if slots % 128 != 0:
            raise ValueError(
                f"the seq engine needs slots % 128 == 0, got {slots}")
        return SQ.SeqConfig(
            lanes=self._req_symbols, slots=slots,
            accounts=-(-self._req_accounts // 128) * 128,
            max_fills=self._req_max_fills, hbm_books=slots > 512,
            compat=self._compat)

    def _try_resume(self, engine: str, compat: str) -> bool:
        from kme_tpu.runtime import checkpoint as ck

        if engine == "seq":
            if compat == "java":
                # the previous incarnation may have DEGRADED to the
                # native engine mid-stream (a barrier left the java
                # device surface, _degrade_to_native) and checkpointed
                # there — the NEWEST snapshot across kinds wins; the
                # .npz offsets are listed WITHOUT restoring so the
                # common degraded-restart path never pays the device
                # import
                seq_snaps = ck.list_snapshots(self.checkpoint_dir)
                seq_off = seq_snaps[0][0] if seq_snaps else -1
                nat, noff = ck.load_native(self.checkpoint_dir)
                if nat is not None and nat.java and noff > seq_off:
                    self._native = nat
                    self.offset = self._last_ckpt_offset = noff
                    print(f"kme-serve: resumed DEGRADED (native) "
                          f"java continuation at offset {noff}",
                          file=sys.stderr)
                    return True
            ses, offset = ck.load_seq_session(self.checkpoint_dir,
                                              self._seq_cfg())
            if ses is None:
                return False
            self._session = ses
        elif engine == "native":
            nat, offset = ck.load_native(self.checkpoint_dir)
            if nat is None:
                return False
            self._check_resume_compat(nat, compat)
            if not nat.java:
                want = (self._req_slots, self._req_max_fills)
                have = (nat.book_slots, nat.max_fills)
                if want != have:
                    raise ValueError(
                        f"snapshot in {self.checkpoint_dir} has envelope "
                        f"(slots, max_fills)={have}, but {want} was "
                        f"requested — capacity changes need a state "
                        f"migration, not a resume")
            self._native = nat
        else:
            ora, offset = ck.load_oracle(self.checkpoint_dir)
            if ora is None:
                return False
            self._check_resume_compat(ora, compat)
            self._oracle = ora
        self.offset = self._last_ckpt_offset = offset
        print(f"kme-serve: resumed from snapshot at offset {offset}",
              file=sys.stderr)
        return True

    def _check_resume_compat(self, engine_obj, compat: str) -> None:
        snap_compat = "java" if engine_obj.java else "fixed"
        if snap_compat != compat:
            raise ValueError(
                f"snapshot in {self.checkpoint_dir} was taken with "
                f"compat={snap_compat!r}, but compat={compat!r} was "
                f"requested")

    def _maybe_checkpoint(self) -> None:
        if self.checkpoint_dir is None or self.follower:
            # a follower shares the leader's checkpoint dir read-only:
            # writing snapshots from two processes would race the prune
            return
        if self.offset - self._last_ckpt_offset < self.checkpoint_every:
            return
        # on the cadence the loop goes on while the file is written
        self.checkpoint(wait=False)

    def _span(self, name: str, ordinal: Optional[int] = None):
        """One span of the serve loop's timer, tied to its batch."""
        return self._ptimer.phase(
            name, batch=self._batch_ordinal if ordinal is None else ordinal)

    def checkpoint(self, wait: bool = True) -> None:
        """Snapshot engine state + input offset (batch boundary): what
        the file needs is captured here, and a SeqSession's file is
        made by the snapshot writer's thread. A caller outside the
        cadence (shutdown, promotion, a test) returns with the file
        durable: the same handoff, waited for."""
        with self._span("checkpoint"):
            self._checkpoint()
            if wait:
                self._snapshot_writer_wait()

    def _checkpoint_drain(self) -> None:
        """A snapshot must capture engine state at a committed offset
        boundary — collect every in-flight batch first."""
        with self._span("checkpoint_drain"):
            if getattr(self, "_pipe", None):
                self._drain_pipeline()

    def _broker_sync(self) -> bool:
        """Make the input log durable BEFORE committing an offset into
        it: the snapshot is fsync'd, so without this a power loss could
        leave an offset addressing MatchIn records the OS never wrote
        (resume would silently skip input). False: the sync failed and
        the snapshot is deferred."""
        sync = getattr(self.broker, "sync", None)
        if sync is None:
            return True
        from kme_tpu.bridge.broker import BrokerError

        with self._span("broker_sync"):
            try:
                sync()
            except (BrokerError, OSError) as e:
                # OSError covers the in-process broker's own fsync
                # failing (disk full / EIO) — defer, don't die
                print(f"kme-serve: broker sync failed before checkpoint "
                      f"({e}); snapshot deferred", file=sys.stderr)
                return False
        return True

    def _snapshot_writer_wait(self) -> None:
        """Take back the snapshot in flight, if there is one (span
        `snapshot_writer_wait`): returns once its file is durable, and
        raises here, on the serve thread, what its writer raised. At
        most one snapshot is in flight, none is skipped or merged: a
        boundary that comes due before the file of the last is durable
        waits here, then hands off."""
        writer, self._snap_writer = self._snap_writer, None
        if writer is not None:
            with self._span("snapshot_writer_wait"):
                writer.join()

    def _snapshot_save(self, write) -> None:
        """Make one snapshot's file (span `snapshot_save`, start to
        durable): `write` is the save of runtime/checkpoint.py that
        _snapshot_handoff chose, with what it captured."""
        with self._span("snapshot_save"):
            write()

    def _snapshot_handoff(self, extra: dict) -> list:
        """Capture, as of `self.offset`, all that the snapshot's file
        needs and the loop changes afterwards, and give it to the
        snapshot writer, which starts at once (span `snapshot_handoff`).
        A SeqSession's device state goes by reference (the scan is not
        donated: the boundary's arrays stay valid while the writer
        holds them), its host state and `extra` by copy
        (checkpoint.capture_seq_session); the fetch, the host's passes,
        the digest, the write and the fsyncs are the writer's. The
        host engines (native, oracle: their state is the host's own, a
        text dump or a pickle that has to be made at the boundary
        anyway) are saved here, on the serve thread. -> the list that
        takes what a fixed-mode SeqSession's snapshot fetched, for the
        auditor's compare, once the file is written."""
        import functools

        from kme_tpu.runtime import checkpoint as ck

        fetched = []
        with self._span("snapshot_handoff"):
            if self._session is not None:
                write = functools.partial(
                    ck.write_seq_snapshot, self.checkpoint_dir,
                    ck.capture_seq_session(self._session, self.offset,
                                           extra),
                    keep=self.checkpoint_keep,
                    fetched=fetched if self.auditor is not None else None)
                self._snap_writer = _SnapshotWriter(
                    functools.partial(self._snapshot_save, write))
                return fetched
            if self._native is not None:
                save, engine = ck.save_native, self._native
            else:
                save, engine = ck.save_oracle, self._oracle
            self._snapshot_save(functools.partial(
                save, self.checkpoint_dir, engine, self.offset,
                keep=self.checkpoint_keep, extra=extra))
        return fetched

    def _checkpoint(self) -> None:
        self._checkpoint_drain()
        if not self._broker_sync():
            return
        # the snapshot before this one, where it is still being written
        # (it has had the drain and the sync to finish in): after this
        # line nothing is in flight, so a fence raised below leaves no
        # writer that renames afterwards
        self._snapshot_writer_wait()
        # the heartbeat sample cursor rides EVERY snapshot (not just
        # exactly-once leaders'): a resumed service continues the TSDB
        # sequence so replayed heartbeat samples dedup on ingestion
        extra = {"sample_seq": self.sample_seq}
        if self.epoch is not None:
            from kme_tpu.bridge import lease
            from kme_tpu.bridge.broker import BrokerFenced

            if faults.should("lease.steal", offset=self.offset):
                # split-brain drill: another incarnation grabs the next
                # epoch (and, like any real new leader, fences us at
                # the broker)
                stolen = lease.steal(self.checkpoint_dir)
                fence = getattr(self.broker, "fence", None)
                if fence is not None:
                    fence(stolen)
                print(f"kme-faults: lease stolen (epoch {stolen}) at "
                      f"offset {self.offset}", file=sys.stderr)
            cur = lease.current_epoch(self.checkpoint_dir)
            if cur > self.epoch:
                # self-fence before writing anything: a newer leader
                # owns the stream; our snapshot would roll ITS state
                # machine back
                raise BrokerFenced(
                    f"fenced: leader epoch {self.epoch} superseded by "
                    f"{cur}; refusing to checkpoint")
            extra.update(epoch=self.epoch, out_seq=self.out_seq)
            if self.topic_xfer is not None:
                # the pending_reserve ledger rides the snapshot so a
                # resumed leader reports continuous cross-shard totals;
                # the transfer LEGS themselves regenerate from MatchIn
                # replay and dedup on their (epoch, out_seq) stamps
                extra["pending_reserve"] = dict(self._xfer)
        # the epoch was checked directly above and the writer starts
        # inside the handoff: check-to-rename is no longer than it was
        # with the save on this thread
        fetched = self._snapshot_handoff(extra)
        # the cadence counts from the handoff; what reads "the newest
        # durable snapshot" reads the directory, which the writer's
        # rename updates (oldest_retained_offset, recovery)
        self._last_ckpt_offset = self.offset
        if self.journal is not None:
            # the journal is best-effort relative to the broker log, but
            # a snapshot is a natural durability point for it too
            self.journal.flush()
        if self.auditor is not None and self._session is not None:
            # the compare reads the state at THIS boundary (the
            # snapshot's own fetch) against a shadow ledger that moves
            # with the next batch: the loop waits for the file first
            self._snapshot_writer_wait()
            self._audit_check_engine(fetched)

    def _audit_check_engine(self, fetched: list) -> None:
        """Checkpoint-cadence cross-check (span `audit_check_engine`):
        the shadow ledger against the engine's stores and the device
        histograms, every snapshot. The engine's side is built from the
        live entries the snapshot itself fetched (`fetched`: its canon
        and layout) where there are such — no second device fetch, no
        walk over dead slots — and from export_state() otherwise; the
        dict compared is the same either way."""
        with self._span("audit_check_engine"):
            state = (self._session.export_live(*fetched) if fetched
                     else self._session.export_state())
            self.auditor.check_engine(state, self._session.histograms())

    # ------------------------------------------------------------------

    def _parse(self, value: str):
        from kme_tpu.wire import EnvelopeError, parse_order

        try:
            m = parse_order(value)
            # the Jackson envelope: price/size are Java int fields, so
            # out-of-int32 values kill the reference's deserializer
            # (KProcessor.java:513-517) exactly like non-JSON input —
            # same drop/strict policy, for every engine
            if not (-2**31 <= m.price < 2**31 and -2**31 <= m.size < 2**31):
                raise EnvelopeError(
                    f"price/size outside int32 (price={m.price}, "
                    f"size={m.size})")
            return m
        except (ValueError, EnvelopeError):
            if self.strict:
                raise
            print(f"kme-serve: dropping malformed record: {value[:120]!r}",
                  file=sys.stderr)
            return None

    def step(self, timeout: float = 0.5) -> int:
        """Poll once: fetch up to `batch` records, process, produce the
        record stream. Returns the number of input records consumed."""
        if self._pipe is not None and self._session is not None:
            return self._step_pipelined(timeout)
        recs = self._poll(self.offset, timeout)
        if not recs:
            return 0
        return self._process_batch(recs)

    def _poll(self, offset: int, timeout: float):
        """Wait up to `timeout` for input from `offset` on. None where
        the topics are not provisioned yet — keep polling, like a
        Streams app waiting for its source topic."""
        from kme_tpu.bridge.broker import BrokerError

        with self._ptimer.phase("poll_wait"):
            try:
                return self.broker.fetch(self.topic_in, offset, self.batch,
                                         timeout=timeout)
            except BrokerError:
                self.clock.sleep(min(timeout, 0.05))
                return None

    def _parse_records(self, recs) -> tuple:
        """Per-record parse of a fetched batch, the serial path's (drop
        or die on a malformed record, `_parse`) -> (msgs, offsets,
        drops, the messages' admission stamps, every record's)."""
        msgs, offs, drops, atss, in_atss = [], [], [], [], []
        with self._span("parse_batch"):
            for r in recs:
                ats = getattr(r, "ats", None)
                in_atss.append(ats)
                m = self._parse(r.value)
                if m is not None:
                    msgs.append(m)
                    offs.append(r.offset)
                    atss.append(ats)
                else:
                    drops.append((-1, r.offset))
        return msgs, offs, drops, atss, in_atss

    def _process_batch(self, recs) -> int:
        """Serial batch processing: parse, engine, produce, commit —
        the per-record authority every engine/compat combination
        supports (the pipelined path above delegates here for batches
        with malformed or out-of-envelope records)."""
        import time as _t

        fetch_us = self.clock.time_us()
        lat = self._lat
        self._batch_ordinal += 1
        msgs, offs, drops, atss, in_atss = self._parse_records(recs)
        out = reasons = None
        self._last_produce_s = 0.0
        phases = getattr(self._session, "phases", None)
        p0 = dict(phases) if phases is not None else {}
        t_engine0 = _t.perf_counter()
        if msgs:
            if self._native is not None:
                out = self._native_produce(msgs)
            elif self._session is not None:
                try:
                    with self._span("process_wire"):
                        self._flow("s")
                        out = self._session.process_wire(msgs)
                    if self._first_batch is None:
                        self._first_batch_done(t_engine0)
                except Exception as e:
                    from kme_tpu.runtime.seqsession import \
                        UnsupportedJavaOp

                    if not isinstance(e, UnsupportedJavaOp):
                        raise
                    # a java-mode stream left the device surface
                    # (barrier / negative-sid symbol, COMPAT.md): the
                    # router raises BEFORE any device mutation, so the
                    # session's state converts losslessly to the native
                    # engine (runtime/javasnap.py) and serving
                    # continues there — the batch replays on the
                    # native engine from the same state
                    self._degrade_to_native(str(e))
                    out = self._native_produce(msgs, flow=False)
                else:
                    reasons = self._session.last_reasons
                    self._produce_lines(out)
            else:
                from kme_tpu.wire import dumps_order

                with self._span("process_wire"):
                    self._flow("s")
                    out = [[f"{rec.key} {dumps_order(rec.value)}"
                            for rec in self._oracle.process(m)]
                           for m in msgs]
                self._produce_lines(out)
            if self.annotate_rejects and out is not None:
                self._produce_rej_annotations(out, reasons)
        # -- latency attribution: charge the batch's stage wall times to
        # every order in it (per-order quantiles); ingress and e2e from
        # each record's own admission stamp (_stamp_latency, below)
        done_us = self.clock.time_us()
        n = len(msgs)
        plan_d = dev_d = 0.0
        if n:
            if phases is not None:
                p1 = self._session.phases if self._session is not None \
                    else p0
                plan_d = p1.get("plan_s", 0.0) - p0.get("plan_s", 0.0)
                dev_d = (p1.get("dispatch_s", 0.0) + p1.get("fetch_s", 0.0)
                         - p0.get("dispatch_s", 0.0) - p0.get("fetch_s", 0.0))
            else:
                # host engines (native/oracle) have no plan/device
                # split; the whole engine wall is "device" time
                dev_d = max(0.0, _t.perf_counter() - t_engine0
                            - self._last_produce_s)
            if plan_d > 0:
                lat["plan"].observe(plan_d, n)
            if dev_d > 0:
                lat["device"].observe(dev_d, n)
                self.telemetry.gauge(
                    "device_ms_per_batch", _DEVICE_MS_HELP).set(
                    round(dev_d * 1e3, 3))
            if self._last_produce_s > 0:
                lat["produce"].observe(self._last_produce_s, n)
        if self.journal is not None and (out or drops):
            jout = out or []
            if self._journal_tamper is not None:
                jout = self._journal_tamper(jout)
            with self._span("journal_record"):
                self.journal.record_batch(jout, reasons=reasons,
                                          offsets=offs[:len(out or [])],
                                          drops=drops)
        with self._span("latency_stamp"):
            self._stamp_latency(
                in_atss, atss, offs[:n], [int(m.oid) for m in msgs],
                [int(m.aid) for m in msgs], fetch_us, done_us,
                plan_d, dev_d, self._batch_ordinal)
        if self.watch is not None and n:
            # batch barrier: the serving oracle IS the deterministic
            # state machine, so predicates read it directly — no
            # lifecycle re-derivation, no shadow ledger, and never the
            # journal-tamper copy. After _stamp_orders so a firing
            # capture embeds this batch's trace exemplars. Drop-only
            # batches change no state and cannot transition a
            # predicate, so they are skipped.
            self.watch.observe_engine(self._oracle, offs[n - 1],
                                      exemplars=self._slow)
        # batch-boundary commit (H5): offsets advance only after the
        # outputs for the whole batch are on MatchOut
        self.offset = recs[-1].offset + 1
        # crash window the chaos harness targets: outputs are on
        # MatchOut but the snapshot has not caught up — recovery MUST
        # replay from the last checkpoint and reproduce these bytes.
        # (Leader-only: a follower tails the raw input log and can run
        # ahead of the leader, so it must not consume the kill budget.)
        if not self.follower:
            faults.kill_now("serve.kill", offset=self.offset)
        self._maybe_checkpoint()
        self._commit_watermark()
        self._publish_batch(len(recs), len(recs) - len(msgs))
        return len(recs)

    # -- pipelined serving (H5): submit N+1 while N runs on the device

    def _parse_batch(self, recs):
        """Columnar parse of a fetched batch (native kme_parse when
        built). Returns a WireBatch when EVERY record parses clean and
        passes the reference's int32 price/size envelope — the hot
        case; None sends the batch through the per-record _parse path
        (whose drop/strict policy is the authority for bad input)."""
        import numpy as np

        from kme_tpu.wire import WireBatch

        with self._span("parse_batch", self._batch_ordinal + 1):
            try:
                payload = b"\n".join(
                    v if isinstance(v, bytes) else v.encode()
                    for v in (r.value for r in recs))
                wb = WireBatch.parse_buffer(payload)
            except (ValueError, OverflowError, UnicodeEncodeError,
                    AttributeError):
                return None
            if wb.n != len(recs):
                return None  # embedded newlines / empty values
            lim = 1 << 31
            if not (np.all(wb.price >= -lim) and np.all(wb.price < lim)
                    and np.all(wb.size >= -lim)
                    and np.all(wb.size < lim)):
                return None
            return wb

    def _step_pipelined(self, timeout: float = 0.5) -> int:
        """Poll once in pipelined mode: parse + plan + DISPATCH this
        batch without waiting on the device, then retire the oldest
        in-flight batch once the window exceeds `pipeline` — batch
        N+1's host work runs under batch N's device step. The fetch
        cursor runs ahead of the committed offset by the in-flight
        window; self.offset still advances only at collect time, so
        the at-least-once replay contract (H5 batch-boundary commit)
        is unchanged."""
        fetch_off = self._pipe[-1][0] if self._pipe else self.offset
        recs = self._poll(fetch_off, timeout)
        if recs is None:
            return 0
        if not recs:
            # idle input: finish the in-flight window so output
            # visibility and offsets never stall behind an empty poll
            self._drain_pipeline()
            return 0
        import time as _t

        wb = self._parse_batch(recs)
        if wb is None:
            # malformed / out-of-envelope records: drain, then run the
            # batch through the exact per-record path (drops, strict)
            self._drain_pipeline()
            return self._process_batch(recs)
        fetch_us = self.clock.time_us()
        atss = [getattr(r, "ats", None) for r in recs]
        end_off = recs[-1].offset + 1
        if (self.checkpoint_dir is not None and not self.follower
                and self._pipe
                and end_off - self._last_ckpt_offset
                >= self.checkpoint_every):
            # a due snapshot needs a drained pipeline (engine state at
            # a committed offset boundary); drain BEFORE submitting so
            # the cadenced checkpoint fires at this batch's collect
            self._drain_pipeline()
        self._batch_ordinal += 1
        if self._first_batch is None:
            self._first_batch = (self._batch_ordinal, _t.perf_counter())
        phases = self._session.phases
        p0 = dict(phases)
        with self._span("session_submit"):
            self._flow("s")
            handle = self._session.submit(wb)
        plan_d = phases.get("plan_s", 0.0) - p0.get("plan_s", 0.0)
        self._pipe.append((end_off, handle, wb,
                           [r.offset for r in recs], atss, fetch_us,
                           plan_d, self._batch_ordinal,
                           self.clock.time_us()))
        while len(self._pipe) > self.pipeline:
            self._collect_one()
        return len(recs)

    def _collect_one(self) -> None:
        """Retire the oldest in-flight batch: fetch + reconstruct its
        outputs, produce, journal, and only THEN advance the committed
        offset. Checkpoints wait for an empty pipeline: a snapshot must
        pair engine state with an offset whose every predecessor is
        visible on MatchOut."""
        import time as _t

        from kme_tpu.telemetry.journal import buffer_lines

        (end_off, handle, wb, offs, atss, fetch_us, plan_d, ordinal,
         submitted_us) = self._pipe.popleft()
        lat = self._lat
        # in flight: since session_submit returned the loop has gone
        # on to submit up to `pipeline` more batches and collect older
        # ones (fewer where a poll came back empty and drained the
        # window); one observation a batch, charged to each order in it
        lat["inflight"].observe(
            max(0, self.clock.time_us() - submitted_us) * 1e-6, wb.n)
        self._last_produce_s = 0.0
        phases = self._session.phases
        p0 = dict(phases)
        with self._span("session_collect", ordinal):
            buf, line_off, msg_lines = self._session.collect(handle)
        if self._first_batch and self._first_batch[0] == ordinal:
            self._first_batch_done(self._first_batch[1])
        reasons = self._session.last_reasons
        # device attribution under pipelining: what the batch WAITED at
        # fetch time (overlapped device work the host never sees is the
        # point of the pipeline)
        dev_d = phases.get("fetch_s", 0.0) - p0.get("fetch_s", 0.0)
        self._produce_buffer(buf, line_off, ordinal)
        done_us = self.clock.time_us()
        n = wb.n
        if plan_d > 0:
            lat["plan"].observe(plan_d, n)
        if dev_d > 0:
            lat["device"].observe(dev_d, n)
            self.telemetry.gauge(
                "device_ms_per_batch", _DEVICE_MS_HELP).set(
                round(dev_d * 1e3, 3))
        if self._last_produce_s > 0:
            lat["produce"].observe(self._last_produce_s, n)
        if self.journal is not None and n:
            with self._span("journal_record", ordinal):
                if self._journal_tamper is not None:
                    # the drill rewrites a line: this batch goes as lines
                    with self._span("journal_lines", ordinal):
                        out = buffer_lines(buf, line_off, msg_lines)
                    self.journal.record_batch(
                        self._journal_tamper(out), reasons=reasons,
                        offsets=offs, drops=[])
                else:
                    self.journal.record_buffer(buf, line_off, msg_lines,
                                               reasons=reasons,
                                               offsets=offs)
        with self._span("latency_stamp", ordinal):
            # every record of a pipelined batch parsed: one list
            self._stamp_latency(atss, atss, offs, wb.oid.tolist(),
                                wb.aid.tolist(), fetch_us, done_us,
                                plan_d, dev_d, ordinal)
        if self.watch is not None and n:
            self.watch.observe_lines(
                buffer_lines(buf, line_off, msg_lines), reasons=reasons,
                offsets=offs, drops=[], exemplars=self._slow)
        self.offset = end_off
        if not self.follower:
            faults.kill_now("serve.kill", offset=self.offset)
        if not self._pipe:
            # engine state now equals the committed offset — the only
            # point where a snapshot is coherent under pipelining
            self._maybe_checkpoint()
        self._commit_watermark()
        self._publish_batch(n, 0)

    def _drain_pipeline(self) -> None:
        """Collect every in-flight batch (idle input, a slow-path
        batch, a due checkpoint, shutdown)."""
        while self._pipe:
            self._collect_one()

    def _produce_buffer(self, buf, line_off, ordinal=None) -> None:
        """Produce a reconstructed record buffer — the collect-side
        twin of _produce_lines, with the same stamping, retry and
        flow-arrow semantics. Where the buffer may go whole
        (_buffer_call) it is handed to the broker as `session.collect`
        returned it (_produce_runs): no line is sliced out of it.
        Otherwise its lines go through _produce_records' walk."""
        import time as _t

        t0 = _t.perf_counter()
        with self._span("produce_buffer", ordinal):
            self._flow("f", ordinal)
            send = self._buffer_call()
            if send is not None:
                self._produce_runs(send, buf, line_off)
            else:
                text = buf.decode("ascii")
                lo = line_off.tolist()
                self._produce_records(
                    [text[lo[i]:lo[i + 1]] for i in range(len(lo) - 1)])
        self._last_produce_s += _t.perf_counter() - t0

    def _buffer_call(self):
        """The broker's call that takes a stamped run as one buffer, or
        None — read off what the code can see, never off a flag: the
        leader stamps, the broker has the stamped batch calls
        (InProcessBroker; one that hides `produce_stamped` is served
        record by record), and `_produce_out` is the class's own —
        where a subclass, a monkeypatch or the benchmark's broken host
        has replaced the gate, every record passes through it."""
        if (self.epoch is None
                or getattr(self._produce_out, "__func__", None)
                is not _PRODUCE_OUT
                or getattr(self.broker, "produce_stamped", None) is None):
            return None
        return getattr(self.broker, "produce_stamped_buffer", None)

    def _produce_runs(self, send, buf: bytes, line_off) -> None:
        """Send a batch's "KEY value" buffer (`line_off`: n + 1 int64
        offsets) through the broker's buffer call `send`, in order:
        whole where no Xfer mark occurs in it — one `bytes` search —
        and split at every Xfer-marked line otherwise: the lines before
        it go as one run, the line goes through _produce_xfer in its
        place, so the stamp stream is the per-record walk's. Each run
        is one call under _produce_retry's backoff: a BrokerError
        retries the whole run from the same `seq0`, and the broker's
        dedup makes that idempotent."""
        n = len(line_off) - 1
        start = 0
        mark = self._xfer_mark
        markb = None if mark is None else mark.encode("ascii")
        pos = -1 if mark is None else buf.find(markb)
        while pos >= 0:
            # the line the match lies in; the walk's own test decides
            # (a match in a key, or across two lines, marks nothing)
            li = int(line_off.searchsorted(pos, "right")) - 1
            key, _, value = buf[line_off[li]:line_off[li + 1]].decode(
                "ascii").partition(" ")
            if mark in value:
                self._send_run(send, buf, line_off, start, li)
                self._produce_xfer(key, value)
                start = li + 1
                pos = int(line_off[start]) - 1
            pos = buf.find(markb, pos + 1)
        self._send_run(send, buf, line_off, start, n)

    def _send_run(self, send, buf: bytes, line_off, lo: int,
                  hi: int) -> None:
        n = hi - lo
        if n <= 0:
            return
        self._broker_retry(send, self.topic_out, buf,
                           line_off[lo:hi + 1], self.epoch, self.out_seq)
        self._out_calls += 1
        self._out_records += n
        self._out_buffered += n
        self.out_seq += n

    def _publish_batch(self, nrecs: int, ndropped: int) -> None:
        """Per-batch service counters + a rate-limited engine refresh.
        Runs on the POLL THREAD only: the engine refresh touches device
        arrays, which the heartbeat/HTTP threads must never do — they
        read registry snapshots."""
        with self._span("publish_batch"):
            self._publish_gauges()
            now = self.clock.monotonic()
            if now - self._last_engine_pub >= 1.0:
                self._last_engine_pub = now
                self._engine_refresh()
        # (its own totals are in the NEXT batch's gauges: it closes
        # after they are set)
        with self._span("publish_spans"):
            self._publish_spans(1, nrecs, ndropped)

    def _publish_spans(self, batches: int = 0, nrecs: int = 0,
                       ndropped: int = 0) -> None:
        """The batch counters, and every span of the loop's timer and
        of the session's as two cumulative gauges, `<name>_s` and
        `<name>_n` (and `<name>_cpu_s` for the session's CPU_SPANS),
        with the loop's own wall and CPU (`serve_loop_s`,
        `serve_cpu_s`), the process's CPU and the front door's by role
        (_thread_gauges), the lane switches and the XLA compile totals
        beside them — all at ONE
        instant, after the batch's last span has closed: a difference
        of two heartbeats then holds whole batches of each (a counter
        stepped before the engine refresh and a gauge set after it
        would be a batch apart in most heartbeats)."""
        import time as _t

        t = self.telemetry
        t.counter("service_batches").inc(batches)
        t.counter("service_records").inc(nrecs)
        t.counter("service_dropped").inc(ndropped)
        gauges = self._ptimer.gauges(self.LOOP_SPANS + self.INNER_SPANS
                                     + self.BETWEEN_SPANS
                                     + self._plane_spans)
        timer = getattr(self._session, "timer", None)
        if timer is not None:
            gauges.update(timer.gauges(getattr(self._session, "SPANS", ())))
            # host-path attribution: cumulative wall seconds the serve
            # loop spent OFF the device (plan + reconstruction)
            gauges["host_path_s"] = round(
                gauges.get("plan_s", 0.0) + gauges.get("recon_s", 0.0), 6)
            # bytes the session's metrics() brought device -> host:
            # 20 a refresh (SeqSession's narrow read); only a session
            # that counts them has the gauge
            fetched = getattr(self._session, "metrics_fetch_bytes", None)
            if fetched is not None:
                gauges["metrics_fetch_bytes"] = fetched
            # the newest snapshot's size and live counts, from the
            # first one a fixed-mode SeqSession writes
            gauges.update(getattr(self._session, "snapshot_gauges", {}))
        gauges.update(self._thread_gauges())
        gauges["serve_loop_s"] = round(_t.perf_counter() - self._loop_t0, 6)
        t.counter("lane_switches",
                  "HBM book-cache lane switches the seq kernel made "
                  "(host count over each plan, by the kernel's "
                  "rule)").set(getattr(self._session, "lane_switches", 0))
        t.counter("pos_probe_tiles",
                  "tiles of the position store the seq kernel brought "
                  "in from HBM (the kernel's own count)"
                  ).set(getattr(self._session, "pos_probe_tiles", 0))
        # a seq session's fetch: whether a batch's output prefix was
        # sent on its way with its dispatch, and had arrived by the time
        # the batch was collected (0 from an engine that has no such
        # fetch)
        for name, what in _FETCH_COUNTERS.items():
            t.counter(name, what).set(getattr(self._session, name, 0))
        # the symbol lifecycle: the router's counts as of the newest
        # batch collected and the kernel's own of its barrier section
        # (0 from an engine that has no lanes to hand back)
        routed = getattr(self._session, "router_stats", {})
        for name, what in _LIFECYCLE_COUNTERS.items():
            t.counter(name, what).set(routed.get(
                name, getattr(self._session, name, 0)))
        bound = routed.get("lanes_bound", 0)
        gauges["lanes_bound"] = bound
        gauges["lanes_free"] = (self._session.cfg.lanes - bound
                                if routed else 0)
        # oid routes the router holds as of the newest batch collected
        # (it plans `pipeline` batches ahead: their routes are in)
        gauges["routes_held"] = getattr(self._session, "routes_held", 0)
        t.counter("matchout_produce_calls",
                  "broker calls made for output-stream records: one a "
                  "run on a broker with produce_stamped, one a record "
                  "elsewhere").set(self._out_calls)
        t.counter("matchout_records",
                  "output-stream records handed to the broker, by "
                  "any path").set(self._out_records)
        t.counter("matchout_records_buffered",
                  "of matchout_records, those that reached the broker "
                  "inside a buffer run (produce_stamped_buffer): no "
                  "Python object a record").set(self._out_buffered)
        journal = getattr(self, "journal", None)
        if journal is not None:
            t.counter("journal_events",
                      "events the flight recorder wrote: lifecycle, and "
                      "one latency stamp an order").set(
                getattr(journal, "events_written", 0))
            t.counter("journal_bytes",
                      "bytes the flight recorder wrote to its live "
                      "file").set(getattr(journal, "bytes_written", 0))
            t.counter("journal_native_batches",
                      "batches whose journal records were made as one "
                      "record array by the native walk over the "
                      "collected buffer (Journal.record_buffer)").set(
                getattr(journal, "native_batches", 0))
        if getattr(self, "auditor", None) is not None:
            self.auditor.publish_counts()
            gauges["audit_shadow_positions"] = len(self.auditor.positions)
        # host engines never load jax: nothing compiles
        jaxsetup = sys.modules.get("kme_tpu._jaxsetup")
        compiles = (jaxsetup.compiles if jaxsetup is not None
                    else {"n": 0, "seconds": 0.0})
        t.counter("xla_compiles",
                  "programs XLA compiled or took from the persistent "
                  "cache in this process").set(compiles["n"])
        gauges["xla_compile_s"] = round(compiles["seconds"], 6)
        t.publish_gauges(gauges)

    def _thread_gauges(self) -> dict:
        """CPU seconds by the role of the thread that spent them, all
        cumulative since `_loop_t0`: the poll thread's (`serve_cpu_s`,
        beside the wall `serve_loop_s`), the whole process's
        (`process_cpu_s`) and, where the broker is this process's own,
        what its TCP front door's handler threads booked by the role
        they serve (bridge/tcp.py: `tcp_ingress_cpu_s` — produce
        requests — and `tcp_egress_cpu_s` — fetches) and its decode totals
        (`wire_parse_s` over `wire_binary_records`: parse_ns_per_msg,
        windowed by whoever takes two heartbeats).
        What no role claims — XLA's threads, the beater, a profiler —
        is process_cpu_s less the three. A broker behind a socket
        (TcpBroker) has no such counters: those gauges are absent."""
        import threading
        import time as _t

        # CPU counts while one thread polls: what another thread ran
        # between two reads (a service built on one, run on another)
        # is in no thread_time() difference and is left out
        ident, at = self._serve_cpu_mark
        now = (threading.get_ident(), _t.thread_time())
        if now[0] == ident:
            self._serve_cpu_s += now[1] - at
        self._serve_cpu_mark = now
        out = {"serve_cpu_s": round(self._serve_cpu_s, 6),
               "process_cpu_s": round(
                   _t.process_time() - self._process_cpu0, 6)}
        books = getattr(self.broker, "tcp_cpu_books", None)
        if books is not None:
            # other threads own the books and may be adding: a sum can
            # miss a request's CPU for one reading, never count it twice
            for role in ("ingress", "egress"):
                out[f"tcp_{role}_cpu_s"] = round(
                    sum(b[role] for b in books), 6)
            out["wire_parse_s"] = round(self.broker.wire_parse_ns * 1e-9, 9)
            out["wire_binary_records"] = self.broker.wire_binary_records
        return out

    def _first_batch_done(self, t0: float) -> None:
        """Gauge first_batch_s, set once: the first batch's submit to
        its collect (trace, lowering, compile-cache read, first run)."""
        import time as _t

        self._first_batch = False
        self.telemetry.gauge("first_batch_s").set(
            round(_t.perf_counter() - t0, 3))

    def _publish_gauges(self) -> None:
        t = self.telemetry
        t.gauge("service_offset").set(self.offset)
        if faults.active():
            t.gauge("faults_injected").set(faults.fired_total())
        shed = getattr(self.broker, "overload_rejects", None)
        if shed is not None:
            t.gauge("overload_rejects").set(shed)
        nbin = getattr(self.broker, "wire_binary_records", None)
        if nbin is not None:
            # binary-wire adoption surface (kme-top shows a wire row
            # keyed on wire_binary_frac being present)
            njson = self.broker.wire_json_records
            total = nbin + njson
            t.gauge("wire_binary_frac",
                    "fraction of ingress records that arrived as "
                    "binary wire frames").set(
                round(nbin / total, 6) if total else 0.0)
            t.gauge("parse_ns_per_msg",
                    "mean wire-frame decode cost per binary "
                    "record (ns)").set(
                round(self.broker.wire_parse_ns / nbin) if nbin else 0)
        ov = getattr(self._session, "h2d_overlap_frac", None)
        if ov:
            # stage-transfer overlap surface (r14): fraction of H2D
            # staging wall hidden under in-flight device execution
            t.gauge("h2d_overlap_frac",
                    "fraction of host->device staging time "
                    "overlapped with device execution").set(ov)
        ctl = getattr(self.broker, "overload", None)
        if ctl is not None:
            # adaptive-controller surface (kme-top shows a degradation
            # row keyed on overload_state being present)
            t.gauge("overload_state",
                    "degradation state: 0 normal / 1 shedding / "
                    "2 draining").set(ctl.state)
            t.gauge("overload_backoff_ms",
                    "AIMD producer backoff hint carried on "
                    "rej_overload").set(ctl.backoff_ms)
            t.gauge("overload_transitions",
                    "degradation state-machine transitions").set(
                ctl.transitions)
            t.gauge("overload_fairness_sheds",
                    "class-2 sheds forced by the per-account "
                    "fairness cap").set(ctl.fairness_sheds)
            for cls in range(3):
                t.gauge(f"shed_by_class{cls}").set(
                    ctl.shed_by_class[cls])
                t.gauge(f"admitted_by_class{cls}").set(
                    ctl.admitted_by_class[cls])
            if self._shed_pending is not None:
                self._drain_shed_annotations()
        self._publish_eos_gauges()
        if self.journal is not None:
            t.gauge("journal_last_offset",
                    "input offset of the newest committed journal "
                    "record").set(self.journal.last_offset)
            t.gauge("journal_lag_bytes",
                    "bytes accepted by the journal but not yet "
                    "committed by its writer").set(self.journal.lag_bytes)
        if self._pipe is not None:
            t.gauge("pipeline_depth",
                    "in-flight pipelined batches").set(len(self._pipe))

    def _engine_refresh(self) -> None:
        """The once-a-second block of _publish_batch: engine counters,
        SLO, profiler, trigger capture."""
        t = self.telemetry
        with self._span("engine_refresh"):
            if self._session is not None:
                # publishes counters + gauges
                self._publish_pos_store(self._session.metrics())
                self._session.histograms()  # publishes bucket counts
            if self.slo is not None:
                # SLO degradation rides the same heartbeat channel as
                # an audit violation; the auditor's verdict wins
                self._slo_reason = self.slo.evaluate()
            if self.profiler is not None:
                self.profiler.publish(t)
            if self.capture is not None:
                # trigger-based capture: SLO burn or a p99 exemplar
                # past threshold records a bounded profile window whose
                # span ids resolve through kme-trace
                fired = self.capture.maybe_fire(self._slo_reason,
                                                t.exemplars())
                if fired:
                    print(f"kme-serve: profile capture {fired}",
                          file=sys.stderr)

    def _publish_eos_gauges(self) -> None:
        """Exactly-once observability (cheap broker-attribute reads;
        safe from the heartbeat thread too)."""
        t = self.telemetry
        for name, attr in (("dup_suppressed_total", "dup_suppressed"),
                           ("fenced_produces_total", "fenced_produces")):
            v = getattr(self.broker, attr, None)
            if v is not None:
                t.gauge(name).set(v)
        if self.epoch is not None:
            t.gauge("leader_epoch").set(self.epoch)
        if self.topic_xfer is not None:
            self._publish_group_gauges()

    def _publish_group_gauges(self) -> None:
        """Per-group scale-out surface (ISSUE 9): identity, input lag
        behind the group's own MatchIn topic, and the cross-shard
        transfer ledger. Gauges (not counters) so a resumed leader
        republishes the checkpointed totals without double counting."""
        t = self.telemetry
        gk = self.group_id
        t.gauge("group_id").set(gk)
        t.gauge("group_count").set(self.group_count)
        end = getattr(self.broker, "end_offset", None)
        if end is not None:
            from kme_tpu.bridge.broker import BrokerError

            try:
                t.gauge(f"group{gk}_lag",
                        "input records admitted to this group's "
                        "MatchIn topic but not yet applied").set(
                    max(0, end(self.topic_in) - self.offset))
            except BrokerError:
                pass    # topic not provisioned yet
        x = self._xfer
        t.gauge("cross_shard_transfers_total",
                "applied cross-shard balance-transfer legs").set(
            x["legs"])
        t.gauge("cross_shard_transfer_volume",
                "cents moved across groups (credits+debits)").set(
            x["credits"] + x["debits"])
        t.gauge("cross_shard_rejected_total").set(x["rejected"])
        t.gauge("balance_broadcasts_total").set(x["broadcasts"])

    def _produce_retry(self, topic: str, key, value,
                       stamp: bool = False) -> None:
        """Produce with bounded exponential backoff. A transport blip
        (socket reset, injected broker.produce fault) must not kill the
        serve loop mid-batch: the offset has NOT advanced yet, so a
        retry is safe — at worst the record lands twice, which the
        at-least-once contract allows and the exactly-once stamp path
        dedups broker-side. `stamp=True` marks an output-stream record:
        a leader sends it with its `(epoch, out_seq)` stamp; a follower
        only COUNTS it (the discarded produce keeps the cursor aligned
        for promotion). BrokerFenced is never retried — a newer leader
        owns the stream and this process must die so its supervisor
        restarts it under a fresh epoch."""
        stamped = stamp and self.epoch is not None
        if stamped:
            self._broker_retry(self.broker.produce, topic, key, value,
                               epoch=self.epoch, out_seq=self.out_seq)
        else:
            self._broker_retry(self.broker.produce, topic, key, value)
        if stamp:
            self._out_calls += 1
            self._out_records += 1
            if stamped or (self.follower and self.exactly_once):
                self.out_seq += 1

    def _broker_retry(self, send, topic: str, *args, **kw) -> None:
        """One broker call under _produce_retry's bounded backoff: five
        retries of a BrokerError, 0.05 s doubling to 1 s, each counted
        in `broker_retries`; BrokerFenced goes straight up."""
        from kme_tpu.bridge.broker import BrokerError, BrokerFenced

        delay = 0.05
        for attempt in range(6):
            try:
                send(topic, *args, **kw)
                return
            except BrokerFenced:
                raise
            except BrokerError as e:
                if attempt == 5:
                    raise
                self.telemetry.counter("broker_retries").inc()
                print(f"kme-serve: produce to {topic} failed ({e}); "
                      f"retry {attempt + 1}/5 in {delay:.2f}s",
                      file=sys.stderr)
                self.clock.sleep(delay)
                delay = min(delay * 2, 1.0)

    def _produce_records(self, lines) -> None:
        """Produce one batch's "KEY value" output lines in order — the
        per-record walk behind _produce_buffer and _produce_lines,
        taken wherever the batch may not go to the broker as one
        buffer (_buffer_call): every line is routed by _produce_out,
        which is the definition the buffer path is held to
        (tests/test_produce_stamped.py). How many records a broker call
        takes is read off the broker object: a stamping leader whose
        broker has `produce_stamped` (InProcessBroker) gathers each
        run of consecutive organic lines and sends it in ONE call —
        one lock hold, one log write + flush, one wake of the
        consumers; an Xfer-marked line closes the run and goes out in
        its place, so the stamp stream is the one the per-record walk
        writes. Everything else — a follower's discarding broker, a
        remote or Kafka broker, the unstamped at-least-once path —
        hands each record over as it is routed."""
        if (self.epoch is not None
                and getattr(self.broker, "produce_stamped", None)
                is not None):
            self._run = []
        try:
            for ln in lines:
                key, _, value = ln.partition(" ")
                self._produce_out(key, value)
            self._produce_run()
        finally:
            self._run = None

    def _produce_run(self) -> None:
        """Send the gathered run, if any, in one stamped batch produce
        under _produce_retry's backoff: a BrokerError retries the whole
        run from the same `seq0`, and the broker's dedup makes that
        idempotent."""
        run = self._run
        if not run:
            return
        self._broker_retry(self.broker.produce_stamped, self.topic_out,
                           run, self.epoch, self.out_seq)
        self._out_calls += 1
        self._out_records += len(run)
        self.out_seq += len(run)
        self._run = []

    def _produce_out(self, key, value) -> None:
        """Route one output line: organic records go to this group's
        MatchOut stream; front-injected cross-shard lines (the
        XFER_MARK passthrough stamp in `prev` — bridge/front.py) are
        suppressed from the merged feed and land STAMPED on the
        per-group Xfer topic instead, so every applied transfer leg
        leaves one fenced `(epoch, out_seq)` row of durable dedup
        evidence. Both paths consume the same out_seq cursor, keeping
        the stamp stream deterministic across crash-replay. Where
        _produce_records gathers a run, an organic record joins it and
        an Xfer line sends it first."""
        if self._xfer_mark is not None and self._xfer_mark in value:
            self._produce_run()
            self._produce_xfer(key, value)
        elif self._run is not None:
            self._run.append((key, value))
        else:
            self._produce_retry(self.topic_out, key, value, stamp=True)

    def _produce_xfer(self, key, value) -> None:
        import json
        import time as _t

        t0 = _t.perf_counter()
        self._produce_retry(self.topic_xfer, key, value, stamp=True)
        lat = self._lat.get("transfer")
        if lat is not None:
            lat.observe(_t.perf_counter() - t0)
        if key != "OUT":
            return      # ledger counts each leg once, on its result
        try:
            msg = json.loads(value)
            action, size = int(msg["action"]), int(msg["size"])
        except (ValueError, KeyError, TypeError):
            return
        x = self._xfer
        from kme_tpu import opcodes as op

        if action == op.TRANSFER:
            x["legs"] += 1
            if size >= 0:
                x["credits"] += size
            else:
                x["debits"] -= size
        elif action == op.CREATE_BALANCE:
            x["broadcasts"] += 1
        elif action == op.REJECT:
            x["rejected"] += 1

    def _flow(self, phase: str, ordinal: Optional[int] = None) -> None:
        """Trace flow arrow endpoint for the current batch: "s" inside
        the engine span (process_wire / session_submit), "f" inside
        the produce span (produce_lines / produce_buffer) — Perfetto draws
        the causality arrow submit -> produce across tracks. Pipelined
        collects pass their submit-time ordinal explicitly (newer
        batches may have submitted in between)."""
        from kme_tpu.telemetry import get_tracer

        tr = get_tracer()
        if tr is not None:
            tr.flow("batch", phase,
                    self._batch_ordinal if ordinal is None else ordinal,
                    track="serve")

    def _produce_lines(self, out) -> None:
        """Produce a batch's per-message line lists — the serial
        path's twin of _produce_buffer. Where a buffer may go whole
        (_buffer_call) the lines are joined into that shape — one
        `join`, one `encode`, lengths by ``map(len, ...)`` — and take
        the same call (_produce_runs); lines that are not ASCII (their
        lengths would not be byte offsets) and every other case go
        through _produce_records' walk."""
        import time as _t

        t0 = _t.perf_counter()
        with self._span("produce_lines"):
            self._flow("f")
            lines = [ln for lines in out for ln in lines]
            send = self._buffer_call() if lines else None
            text = None if send is None else "".join(lines)
            if text is not None and text.isascii():
                from kme_tpu.bridge.broker import line_offsets

                self._produce_runs(send, text.encode("ascii"),
                                   line_offsets(lines))
            else:
                self._produce_records(lines)
        # accumulates across the branch paths that produce more than
        # once per step (native partial + REJ annotations)
        self._last_produce_s += _t.perf_counter() - t0

    def _native_produce(self, msgs, flow: bool = True):
        # byte-faithful death handling: forward every completed
        # message's records, THEN die like the reference thread
        # (flow=False: the batch's arrow already started in the
        # session this batch left)
        with self._span("process_wire"):
            if flow:
                self._flow("s")
            out, exc = self._native.process_wire_partial(msgs)
        self._produce_lines(out)
        if exc is not None:
            raise exc
        return out

    def _produce_rej_annotations(self, out, reasons) -> None:
        """Opt-in per-order reject causes as ADDITIVE "REJ"-keyed
        MatchOut records (wire.rej_record_json) — the IN/OUT stream
        stays byte-identical to the reference. Engines without exact
        codes (native/oracle) get the action heuristic."""
        import json

        from kme_tpu.wire import (REJ_UNSPECIFIED, reason_for_reject,
                                  rej_record_json)

        for i, lines in enumerate(out):
            if not lines or '"action":7,' not in lines[-1]:
                continue
            m = json.loads(lines[0].partition(" ")[2])
            code = (int(reasons[i]) if reasons is not None
                    else reason_for_reject(m["action"]))
            if code == 0:
                code = REJ_UNSPECIFIED
            self._produce_retry(self.topic_out, "REJ", rej_record_json(
                m["oid"], m["aid"], code))

    def _drain_shed_annotations(self) -> None:
        """REJ rows for controller sheds. The shed never reached the
        engine (it is a produce-time refusal), so the annotation is the
        only durable trace — it carries the observed backlog, the
        active threshold, the degradation state and the backoff hint."""
        from kme_tpu.wire import REJ_OVERLOAD, rej_record_json

        q = self._shed_pending
        while True:
            try:
                d = q.popleft()
            except IndexError:
                break
            self._produce_retry(self.topic_out, "REJ", rej_record_json(
                d.get("oid", 0), d.get("aid", 0), REJ_OVERLOAD,
                detail={"backlog": d["backlog"],
                        "threshold": d["threshold"],
                        "state": d["state"],
                        "backoff_ms": d["backoff_ms"]}))

    def _degrade_to_native(self, reason: str) -> None:
        """One-way engine degradation for java-mode streams that leave
        the device surface (COMPAT.md): the seq session's state
        converts losslessly to the native engine (runtime/javasnap.py)
        and serving continues there — the full java wire surface incl.
        barriers. Checkpoints switch to native snapshots; a restart
        resumes the degraded continuation (_try_resume)."""
        from kme_tpu.native.oracle import NativeOracleEngine, \
            native_available
        from kme_tpu.runtime.javasnap import export_seqjava, \
            to_native_dump

        if not native_available():
            raise RuntimeError(
                f"java stream left the device surface ({reason}) and "
                f"the native engine is unavailable to degrade onto — "
                f"serve this stream with engine='native' or 'oracle'")
        print(f"kme-serve: java stream left the device surface "
              f"({reason}); continuing on the native engine",
              file=sys.stderr)
        eng = NativeOracleEngine("java")
        eng.load_state(to_native_dump(export_seqjava(self._session)))
        self._native = eng
        self._session = None
        self.telemetry.gauge("left_device_at_offset").set(self.offset)

    def engine_in_effect(self) -> str:
        """The engine serving RIGHT NOW: the requested one, or "native"
        once a java-mode seq stream degraded (_degrade_to_native)."""
        if self._session is not None:
            return self.engine_kind
        return "native" if self._native is not None else "oracle"

    def state_homes(self) -> dict:
        """Where the seq kernel keeps its books, as the deployment's
        depth decided it (1: in HBM, one lane at a time in VMEM; 0:
        VMEM-resident) — on the start-up line and as a gauge. The
        fixed-mode position store is in HBM at every size. {} for
        every other engine."""
        cfg = getattr(self._session, "cfg", None)
        if not hasattr(cfg, "hbm_books"):
            return {}
        return {"books_in_hbm": int(cfg.hbm_books)}

    def _publish_pos_store(self, counters: dict) -> None:
        """The seq engine's position store under its load, of the same
        read as the engine's gauges: live entries (`positions`) over
        what the configuration's store holds (SeqConfig.pos_capacity)."""
        cap = getattr(getattr(self._session, "cfg", None),
                      "pos_capacity", None)
        if cap:
            live = counters.get("positions", 0)
            self.telemetry.publish_gauges({
                "pos_live": live, "pos_capacity": cap,
                "pos_load_pct": round(100.0 * live / cap, 4)})

    def metrics(self) -> Optional[dict]:
        """On-device counters+gauges (seq engine; None for the host
        engines).
        A seq session with nothing in flight adds `stale_routes`: the
        oid routes it holds beyond the orders resting on the device."""
        if self._session is None:
            return None
        met = self._session.metrics()
        stale = getattr(self._session, "stale_routes", None)
        if stale is not None and "open_orders" in met:
            n = stale(met["open_orders"])
            if n is not None:
                met["stale_routes"] = n
                self.telemetry.gauge("stale_routes").set(n)
        return met

    def run(self, max_messages: Optional[int] = None,
            idle_exit: Optional[float] = None,
            poll_timeout: float = 0.5,
            health_file: Optional[str] = None,
            health_every: float = 1.0) -> int:
        """Serve until max_messages consumed (None = forever) or the
        input topic stays idle for `idle_exit` seconds.

        health_file: heartbeat surface for the supervisor (kme-supervise)
        — a JSON snapshot {pid, time, seen, offset} atomically replaced
        every `health_every` seconds FROM A BACKGROUND THREAD, so a
        legitimately long step (first-batch XLA compile, a large
        checkpoint write) does not read as a hang; a stale mtime means
        the PROCESS froze or died (the reference delegates liveness to
        Kafka's group-membership heartbeats, KProcessor.java:59-60 via
        the Streams library)."""
        import os
        import threading
        import time

        # fault injection (tests/test_supervise.py): when
        # KME_TEST_STALL_ONCE names a flag file that does not exist yet,
        # the loop freezes (tick stops advancing) after
        # KME_TEST_STALL_AT messages while the heartbeat THREAD stays
        # alive — the exact hang shape the supervisor's stall branch
        # exists to catch. The flag file is created before freezing so
        # the restarted incarnation runs clean (stall exactly once).
        # Armed ONLY under KME_TEST_HOOKS=1: a stray KME_TEST_STALL_ONCE
        # in a production environment must never be able to wedge a
        # real deployment.
        stall_once = (os.environ.get("KME_TEST_STALL_ONCE")
                      if os.environ.get("KME_TEST_HOOKS") == "1"
                      else None)
        stall_at = int(os.environ.get("KME_TEST_STALL_AT", "100"))

        seen = 0
        beat_stop = None
        # the beater thread also runs when only a TSDB is configured
        # (health_file=None): metrics history wants the same heartbeat
        # cadence whether or not a supervisor is watching
        if health_file is not None or self.tsdb is not None:
            beat_stop = threading.Event()
            # readers (kme-agg staleness detection) need the cadence
            # to judge "hasn't advanced in 3 intervals"
            self._hb_every = float(health_every)
            state = self

            def beater():
                while not beat_stop.wait(health_every):
                    state._write_heartbeat(health_file, seen_box[0],
                                           tick_box[0])

            seen_box = [0]
            tick_box = [0]
            self._write_heartbeat(health_file, 0, 0)
            t = threading.Thread(target=beater, daemon=True)
            t.start()
        try:
            idle_since = self.clock.monotonic()
            while max_messages is None or seen < max_messages:
                n = self.step(timeout=poll_timeout)
                if beat_stop is not None:
                    # the loop TICK advances every iteration, idle or
                    # not — a frozen tick is the supervisor's hang
                    # signal (the mtime alone only proves the beater
                    # thread lives)
                    tick_box[0] += 1
                now = self.clock.monotonic()
                if n == 0:
                    if idle_exit is not None \
                            and now - idle_since >= idle_exit:
                        break
                else:
                    idle_since = now
                    seen += n
                    if beat_stop is not None:
                        seen_box[0] = seen
                if (stall_once and seen >= stall_at
                        and not os.path.exists(stall_once)):
                    open(stall_once, "w").close()
                    while True:   # frozen tick, live heartbeat thread
                        time.sleep(0.5)
                if n and not self.follower \
                        and faults.should("serve.stuck",
                                          offset=self.offset):
                    # stuck step(): the loop tick freezes while the
                    # heartbeat thread keeps the mtime fresh — exactly
                    # the hang shape the supervisor's stall branch
                    # detects (fresh mtime + frozen tick)
                    print(f"kme-faults: serve loop stuck at offset "
                          f"{self.offset}", file=sys.stderr)
                    while True:
                        time.sleep(0.5)
        finally:
            try:
                if self._pipe:
                    # in-flight batches hold committed-but-invisible
                    # work — finish them before the final heartbeat
                    self._drain_pipeline()
                # and the snapshot being written: a loop that has
                # returned (or been fenced out) leaves no writer behind
                self._snapshot_writer_wait()
            finally:
                if beat_stop is not None:
                    # the beater may be in the middle of a beat: let it
                    # finish, so that no beat follows the closing one
                    beat_stop.set()
                    t.join(timeout=30.0)
                    self._write_heartbeat(health_file, seen,
                                          tick_box[0], closing=True)
        return seen

    def _write_heartbeat(self, path: Optional[str], seen: int,
                         tick: int = 0, closing: bool = False) -> None:
        """One heartbeat, one writer at a time: the beater thread and
        the serve loop share `<path>.tmp`, and whichever lost the
        rename used to raise (in the serve loop: exit 1 after the work
        was done)."""
        with self._hb_lock:
            self._write_heartbeat_locked(path, seen, tick, closing)

    def _write_heartbeat_locked(self, path: Optional[str], seen: int,
                                tick: int, closing: bool) -> None:
        import json
        import os

        # refresh broker-side exactly-once counters HERE, not only on
        # the batch path: the final heartbeat after run() drains must
        # capture post-batch suppressions/fences
        self._publish_eos_gauges()
        # one monotonically increasing id per heartbeat: the TSDB uses
        # it to dedup samples replayed after a crash-resume exactly the
        # way the broker dedups (epoch, out_seq); persisted via
        # checkpoint extra so a resumed service keeps counting from
        # where the snapshot left off
        seq = self.sample_seq
        self.sample_seq = seq + 1
        snap = self.telemetry.snapshot()
        if path is None:       # TSDB-only heartbeat (no supervisor)
            self._append_tsdb(snap, seq)
            return
        # additive events-log keys (COMPAT.md): the committed-bytes
        # cursor of this process's control-plane event log. kme-agg
        # reads them to flag a recorder that froze while the heartbeat
        # itself kept advancing
        ev = getattr(self, "events", None)
        evkeys = ({"events_last_offset": ev.last_offset,
                   "events_lag_bytes": ev.lag_bytes}
                  if ev is not None else {})
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            # "metrics" is ADDITIVE — the supervisor keys
            # (pid/time/seen/offset/tick) are load-bearing
            # (tests/test_supervise.py). snapshot() only takes the
            # registry lock; safe from this background thread.
            # "closing" tells the supervisor the serve loop ended on
            # purpose (idle-exit / max-messages): the tick is frozen by
            # definition, so the stall detector must stand down while
            # the final checkpoint + teardown run.
            json.dump({"pid": os.getpid(), "time": self.clock.time(),
                       "seen": seen, "offset": self.offset,
                       "tick": tick, "closing": closing,
                       "degraded": self.degraded or self._slo_reason,
                       "role": "follower" if self.follower else "leader",
                       # additive: the engine IN EFFECT (a java stream
                       # that left the device surface reads "native"),
                       # what it runs on, the pipeline depth in effect
                       "engine": self.engine_in_effect(),
                       "pipeline": self.pipeline,
                       **self.runs_on,
                       "epoch": self.epoch,
                       "sample_seq": seq,
                       "every": getattr(self, "_hb_every", 1.0),
                       **evkeys,
                       "metrics": snap}, f)
        os.replace(tmp, path)
        self._append_tsdb(snap, seq)

    def _append_tsdb(self, snap: dict, seq: int) -> None:
        if self.tsdb is None:
            return
        try:
            # (on the heartbeat's thread, under _hb_lock: one writer)
            with self._span("tsdb_append"):
                self.tsdb.append_snapshot(snap, seq)
        except OSError as e:
            # history is best-effort; the live heartbeat is not
            print(f"kme-serve: TSDB append failed: {e}",
                  file=sys.stderr)
            self.tsdb = None


# the gate as the class defines it: _buffer_call sends a buffer past it
# only while nothing has replaced it
_PRODUCE_OUT = MatchService._produce_out
