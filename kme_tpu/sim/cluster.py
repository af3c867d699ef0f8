"""The whole-cluster simulation harness: one process, one seed, one
verdict.

``run_sim(schedule, root)`` stands up the REAL production components —
``MatchService`` leaders (oracle engine, exactly-once stamps, periodic
checkpoints), ``Replica`` hot standbys tailing the leaders' durable
logs, the ``GroupRouter`` front, per-group ``FeedDeriver``s — as
cooperatively scheduled actors under one ``SimScheduler`` virtual
clock, wired through the in-memory ``SimTransport``. Nothing is
mocked below the process boundary: brokers persist real JSONL logs,
checkpoints are real fsync'd snapshots, recovery is the service's own
resume-and-replay path, and a mid-run reshard runs the real offline
``ReshardCoordinator`` over the drained generation.

Fault vocabulary (see ``schedule.py``):

- grammar clauses fire at the production call sites via ``faults.py``
  (broker errors, torn/bitflipped checkpoints, link partitions/delays/
  reorder-dups, clock skew);
- ``crash`` events model SIGKILL of a group leader by DROPPING its
  service and broker objects (the broker flushes inside every produce
  call, a record or a stamped run at a time, so the on-disk logs are
  exactly what a kill -9 leaves) and letting the supervisor actor
  restart it through the ordinary recovery path;
- ``reshard`` events drain the cluster at a stream barrier, close the
  generation, run the coordinator, and reopen services over the new
  topology with the settle-phase resume cursors.

Verdicts, all computed against first principles after the run:

- **parity** — durable MatchOut byte-equals the partitioned
  single-leader oracle (``verify_groups`` / ``verify_groups_reshard``);
- **stamps** — exactly-once: every stamped output row's ``out_seq`` is
  unique within its group's cursor domain (MatchOut + Xfer share one);
- **conservation** — cash summed over the live group engines equals a
  single oracle's replay of the full input stream (transfer legs net
  to zero; ``pending_reserve`` ledgers are reported alongside);
- **feed** — each group's derived book byte-equals the aggregate of
  its live engine's resting orders (``canonical_books``);
- **standby** — follower application stayed within the holdback bound;
- **completed** — the run drained fully inside the virtual deadline (a
  wedge is a red verdict, not a flaky timeout).

Determinism contract: same seed → byte-identical ``trace_digest`` AND
``out_digest``. Anything that would break that (wall time, hash-order
iteration, host identity) is a bug in this module.
"""

from __future__ import annotations

import hashlib
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from kme_tpu import faults
from kme_tpu.sim.sched import SimClockView, SimScheduler
from kme_tpu.sim.schedule import FaultSchedule
from kme_tpu.sim.transport import SimTransport

PLANTED_BUGS = ("stamp-reset",)


@dataclass
class SimConfig:
    """Knobs that are NOT part of the fault schedule (they shape every
    run identically and never participate in shrinking)."""
    slots: int = 64
    max_fills: int = 32
    batch: int = 16
    checkpoint_every: int = 48
    prefund: int = 8
    num_accounts: int = 12
    num_symbols: int = 6
    # grouped parity holds only inside the funded envelope (see
    # workload.spliced_stream): big enough that shadow cash never
    # depletes over a few hundred events + a storm burst
    prefund_cash: int = 50_000_000
    feed_rate: int = 4          # input lines routed per front step
    restart_delay: float = 0.25  # supervisor's virtual restart latency
    journal: bool = True


@dataclass
class SimResult:
    seed: int
    ok: bool
    verdicts: Dict[str, dict]
    trace_digest: str
    out_digest: str
    schedule: FaultSchedule
    counters: Dict[str, int]
    vtime: float
    events: List[tuple] = field(repr=False, default_factory=list)

    def red_verdicts(self) -> List[str]:
        return sorted(k for k, v in self.verdicts.items()
                      if not v.get("ok", False))


# ---------------------------------------------------------------------------
# actors


class _Leader:
    """One group leader: a real MatchService over a real persisted
    broker, with crash = drop-the-objects and recovery = the service's
    own resume path."""

    def __init__(self, cluster: "_SimCluster", g: int, n: int,
                 gdir: str) -> None:
        self.cluster = cluster
        self.g, self.n = g, n
        self.gdir = gdir
        self.view = SimClockView(cluster.sched)
        self.topic_in = f"MatchIn.g{g}"
        self.topic_out = f"MatchOut.g{g}"
        self.topic_xfer = f"Xfer.g{g}"
        self.broker = None
        self.svc = None
        self.down_at: Optional[float] = None
        self.crashes = 0
        self.stopped = False    # actor pump stop (generation retired)
        self._last_ckpt = 0
        self.open()

    def open(self) -> None:
        from kme_tpu.bridge.broker import InProcessBroker
        from kme_tpu.bridge.provision import group_topics, provision
        from kme_tpu.bridge.service import MatchService

        cfg = self.cluster.cfg
        self.broker = InProcessBroker(
            persist_dir=os.path.join(self.gdir, "broker-log"),
            clock=self.view)
        provision(self.broker, topics=group_topics(self.g))
        if (self.crashes and self.cluster.planted_bug == "stamp-reset"):
            # THE PLANTED BUG (shrinker drill): recovery "forgets" the
            # durable idempotence watermark on the output topics, so
            # the resumed leader's replayed tail APPENDS duplicate
            # stamped rows instead of being suppressed — parity, stamp
            # and feed verdicts all go red, deterministically, on any
            # schedule that contains at least one crash
            for t in (self.topic_out, self.topic_xfer):
                topic = self.broker._topics.get(t)
                if topic is not None:
                    topic.max_out_seq = -1
            self.cluster.sched.trace(f"leader{self.g}", "planted_bug",
                                     bug="stamp-reset")
        self.svc = MatchService(
            self.broker, engine="oracle", compat="fixed",
            batch=cfg.batch, slots=cfg.slots, max_fills=cfg.max_fills,
            checkpoint_dir=self.gdir,
            checkpoint_every=cfg.checkpoint_every,
            journal=(os.path.join(self.gdir, "journal.bin")
                     if cfg.journal else None),
            exactly_once=True, group=(self.g, self.n), clock=self.view)
        self._last_ckpt = self.svc._last_ckpt_offset
        self.down_at = None

    def crash(self) -> None:
        """kill -9 at the object layer: no close(), no final flush
        beyond what each produce call already did."""
        self.crashes += 1
        self.svc = None
        self.broker = None
        self.down_at = self.cluster.sched.now
        self.cluster.sched.trace(f"leader{self.g}", "crash",
                                 n=self.crashes)

    def restart(self) -> None:
        self.open()
        self.cluster.sched.trace(
            f"leader{self.g}", "restart", offset=self.svc.offset,
            epoch=self.svc.epoch, out_seq=self.svc.out_seq)
        self.cluster.transport.flush_held(self.g)

    def step(self) -> bool:
        from kme_tpu.bridge.broker import BrokerFenced

        if self.stopped or self.svc is None:
            return False
        rule = faults.fire("clock.skew", offset=self.svc.offset)
        if rule is not None:
            self.view.skew += rule.ms / 1000.0
            self.cluster.sched.trace(f"leader{self.g}", "clock_skew",
                                     ms=rule.ms)
        try:
            n = self.svc.step(timeout=0.0)
        except BrokerFenced:
            # a newer epoch owns the stream: die like kme-serve (exit
            # 75) and let the supervisor restart us under a fresh epoch
            self.cluster.sched.trace(f"leader{self.g}", "fenced")
            self.crash()
            return True
        if n:
            self.cluster.sched.trace(f"leader{self.g}", "apply",
                                     offset=self.svc.offset)
        if self.svc._last_ckpt_offset != self._last_ckpt:
            self._last_ckpt = self.svc._last_ckpt_offset
            self.cluster.sched.trace(f"leader{self.g}", "ckpt",
                                     offset=self._last_ckpt)
        return n > 0


class _Standby:
    """Hot standby: the real Replica follow machinery, stepped under
    the virtual clock. Promotion is not exercised here (crash recovery
    goes through the supervisor restart path); what this actor pins is
    bounded-lag following against a leader that crashes, stalls and
    skews underneath it."""

    def __init__(self, cluster: "_SimCluster", g: int, n: int,
                 gdir: str) -> None:
        from kme_tpu.bridge.replica import Replica

        cfg = cluster.cfg
        self.cluster = cluster
        self.g = g
        self.view = SimClockView(cluster.sched)
        self.stopped = False
        self.last_seen = 0
        self.rep = Replica(
            gdir, engine="oracle", compat="fixed", batch=cfg.batch,
            slots=cfg.slots, max_fills=cfg.max_fills,
            checkpoint_every=10 ** 9, group=(g, n), clock=self.view)

    def step(self) -> bool:
        if self.stopped:
            return False
        leader = self.cluster.leaders[self.g]
        if leader.svc is not None:
            self.last_seen = leader.svc.offset
        self.rep.follow.limit = max(
            self.rep.follow.limit,
            self.last_seen - self.rep.holdback)
        n = self.rep.svc.step(timeout=0.0)
        if n:
            self.cluster.sched.trace(f"standby{self.g}", "apply",
                                     offset=self.rep.svc.offset)
        return n > 0

    def applied(self) -> int:
        return self.rep.svc.offset


class _Feed:
    """Per-group market-data deriver tailing the durable MatchOut
    log — the consumer-side actor whose book must stay byte-pinned to
    the engine through every crash/replay."""

    def __init__(self, cluster: "_SimCluster", g: int,
                 snap_engine=None) -> None:
        from kme_tpu.feed.derive import FeedDeriver

        self.cluster = cluster
        self.g = g
        self.off = 0
        self.stopped = False
        self.fd = FeedDeriver(group=g)
        if snap_engine is not None:
            # post-reshard bootstrap: the new generation's MatchOut
            # stream starts AFTER the migrated books, so the deriver
            # adopts the offset-0 snapshot's resting store (exactly
            # FeedDeriver.from_state's reconstruction)
            from kme_tpu import opcodes as op
            from kme_tpu.feed.derive import SIDE_BUY, SIDE_SELL

            for oid in sorted(snap_engine.orders):
                o = snap_engine.orders[oid]
                side = SIDE_SELL if o.action == op.SELL else SIDE_BUY
                self.fd.resting[oid] = (o.sid, side, o.price, o.size)
                lv = self.fd.book.levels.setdefault((o.sid, side), {})
                lv[o.price] = lv.get(o.price, 0) + o.size

    def step(self) -> bool:
        from kme_tpu.bridge.broker import BrokerError
        from kme_tpu.wire import parse_order

        if self.stopped:
            return False
        leader = self.cluster.leaders[self.g]
        if leader.broker is None:
            return False
        try:
            recs = leader.broker.fetch(leader.topic_out, self.off, 64)
        except BrokerError:
            return False        # injected fetch fault: retry next pump
        for r in recs:
            msg = parse_order(r.value) if r.key == "OUT" else None
            self.fd.on_record(r.key, msg, r.epoch, r.out_seq)
        self.off += len(recs)
        return bool(recs)


class _Supervisor:
    """Restart policy: a downed leader comes back after
    ``restart_delay`` virtual seconds — unless the cluster is inside a
    reshard barrier teardown, which retires generations on purpose."""

    def __init__(self, cluster: "_SimCluster") -> None:
        self.cluster = cluster
        self.stopped = False

    def step(self) -> bool:
        c = self.cluster
        acted = False
        for leader in c.leaders:
            if (leader.svc is None and not leader.stopped
                    and leader.down_at is not None
                    and c.sched.now - leader.down_at
                    >= c.cfg.restart_delay):
                leader.restart()
                acted = True
        return acted


class _Front:
    """The input side: routes the composed stream through a real
    GroupRouter into the transport, performs schedule events at their
    stream positions, and drives the reshard drain barrier."""

    def __init__(self, cluster: "_SimCluster", lines: List[str],
                 events: List[dict]) -> None:
        from kme_tpu.bridge.front import GroupRouter

        self.cluster = cluster
        self.lines = lines
        self.pos = 0
        self.router = GroupRouter(cluster.ngroups,
                                  prefund=cluster.cfg.prefund)
        self.events = sorted(
            events, key=lambda e: (e.get("at", 0), e["kind"]))
        self.state = "feeding"      # feeding | draining | done
        self.pending_reshard: Optional[dict] = None
        self.stopped = False

    def step(self) -> bool:
        c = self.cluster
        if self.state == "done":
            return False
        if self.state == "draining":
            if c.drained():
                c.do_reshard(self.pending_reshard, split_at=self.pos)
                self.pending_reshard = None
                self.state = "feeding"
            return True
        # events scheduled at (or before) the current stream position
        while self.events and self.events[0].get("at", 0) <= self.pos:
            ev = self.events.pop(0)
            if ev["kind"] == "crash":
                g = ev.get("group", 0) % c.ngroups
                leader = c.leaders[g]
                if leader.svc is not None:
                    leader.crash()
            elif ev["kind"] == "reshard":
                self.pending_reshard = ev
                self.state = "draining"
                c.sched.trace("front", "drain_begin", at=self.pos)
                return True
            # storm events shape the input stream at composition time
            # (run_sim), not here
        if self.pos >= len(self.lines):
            self.state = "done"
            c.sched.trace("front", "done", routed=self.pos)
            return False
        n = min(self.cluster.cfg.feed_rate,
                len(self.lines) - self.pos)
        for _ in range(n):
            line = self.lines[self.pos]
            self.pos += 1
            for g, routed in self.router.route_line(line):
                c.transport.send(g, None, routed)
            # re-check events between lines so `at` is exact
            if self.events and self.events[0].get("at", 0) <= self.pos:
                break
        return True


# ---------------------------------------------------------------------------
# the cluster


class _SimCluster:
    def __init__(self, sched: SimScheduler, schedule: FaultSchedule,
                 cfg: SimConfig, root: str,
                 planted_bug: Optional[str]) -> None:
        if planted_bug is not None and planted_bug not in PLANTED_BUGS:
            raise ValueError(f"unknown planted bug {planted_bug!r} "
                             f"(known: {', '.join(PLANTED_BUGS)})")
        self.sched = sched
        self.schedule = schedule
        self.cfg = cfg
        self.root = root
        self.planted_bug = planted_bug
        self.generation = 0
        self.ngroups = schedule.ngroups
        self.leaders: List[_Leader] = []
        self.standbys: List[_Standby] = []
        self.feeds: List[_Feed] = []
        self.front: Optional[_Front] = None
        self.resharded: Optional[dict] = None
        self.pre_matchout: Optional[List[List[str]]] = None
        self.split_at: Optional[int] = None
        self.old_dup_suppressed = 0
        self.old_delivered = 0

    # -- construction ---------------------------------------------------

    def gen_root(self) -> str:
        return os.path.join(self.root, f"gen{self.generation}")

    def start(self, lines: List[str], events: List[dict]) -> None:
        os.makedirs(self.gen_root(), exist_ok=True)
        self._open_generation(snap_engines=None)
        self.front = _Front(self, lines, events)
        self.transport = SimTransport(
            self.sched, self.ngroups,
            broker_for=lambda g: self.leaders[g].broker,
            topic_for=lambda g: f"MatchIn.g{g}")
        self.sched.add_actor("front", self.front, quantum=0.002)
        self.sched.add_actor("supervisor", _Supervisor(self),
                             quantum=0.01, idle_quantum=0.02)
        self._add_group_actors()

    def _open_generation(self, snap_engines) -> None:
        self.leaders = []
        self.standbys = []
        self.feeds = []
        for g in range(self.ngroups):
            gdir = os.path.join(self.gen_root(), f"group{g}")
            os.makedirs(gdir, exist_ok=True)
            self.leaders.append(_Leader(self, g, self.ngroups, gdir))
            self.standbys.append(_Standby(self, g, self.ngroups, gdir))
            self.feeds.append(_Feed(
                self, g,
                snap_engine=(snap_engines[g] if snap_engines else None)))

    def _add_group_actors(self) -> None:
        gen = self.generation
        for g in range(self.ngroups):
            self.sched.add_actor(f"g{gen}.leader{g}", self.leaders[g],
                                 quantum=0.002)
            self.sched.add_actor(f"g{gen}.standby{g}", self.standbys[g],
                                 quantum=0.004)
            self.sched.add_actor(f"g{gen}.feed{g}", self.feeds[g],
                                 quantum=0.003)

    # -- reshard barrier ------------------------------------------------

    def drained(self) -> bool:
        """Everything routed so far is durable AND applied: transport
        empty, every leader alive and caught up with its input log."""
        if not self.transport.idle():
            return False
        for leader in self.leaders:
            if leader.svc is None or leader.broker is None:
                return False
            if (leader.svc.offset
                    < leader.broker.end_offset(leader.topic_in)):
                return False
        return True

    def do_reshard(self, ev: dict, split_at: int) -> None:
        from kme_tpu.bridge.reshard import ReshardCoordinator
        from kme_tpu.runtime import checkpoint as ck

        m = max(2, int(ev.get("to", 2)))
        n = self.ngroups
        self.sched.trace("reshard", "begin", n=n, m=m,
                         split_at=split_at)
        # close the old generation cleanly: final snapshot (the
        # coordinator needs drained oracle snapshots), then record what
        # it produced for the pre-generation parity verdict
        pre: List[List[str]] = []
        for leader in self.leaders:
            leader.svc.checkpoint()
            leader.svc.close()
            pre.append([f"{r.key} {r.value}" for r in
                        leader.broker.fetch(leader.topic_out, 0,
                                            10 ** 7)])
            self.old_dup_suppressed += leader.broker.dup_suppressed
            self.old_delivered += sum(
                link.delivered for link in self.transport.links
                if link.g == leader.g)
            leader.broker.sync()
            leader.svc = None
            leader.broker = None
            leader.stopped = True
        for st in self.standbys:
            st.stopped = True
        for fd in self.feeds:
            fd.stopped = True
        old_root = self.gen_root()
        self.generation += 1
        new_root = self.gen_root()
        # the coordinator stamps its phase events with the virtual
        # clock — the timeline verdict needs seed-stable event bytes
        coord = ReshardCoordinator(old_root, new_root, n, m,
                                   clock=SimClockView(self.sched).time)
        j = coord.run()
        cursors = j["settle"]["resume_cursors"]
        self.pre_matchout = pre
        self.split_at = split_at
        self.resharded = {"n": n, "m": m, "split_at": split_at,
                          "legs": j["settle"]["legs"]}
        self.ngroups = m
        # offset-0 snapshots seed the new feed derivers' books
        snaps = [ck.load_oracle(os.path.join(new_root, f"group{g}"))[0]
                 for g in range(m)]
        self._open_generation(snap_engines=snaps)
        self.transport.reshape(m, cursors=cursors)
        self.front.router.reshard(m)
        self._add_group_actors()
        self.sched.trace("reshard", "done", m=m,
                         legs=j["settle"]["legs"])

    # -- completion -----------------------------------------------------

    def finished(self) -> bool:
        if self.front.state != "done":
            return False
        if not self.drained():
            return False
        for fd in self.feeds:
            leader = self.leaders[fd.g]
            if fd.off < leader.broker.end_offset(leader.topic_out):
                return False
        return True

    # -- verdicts -------------------------------------------------------

    def verdicts(self, lines: List[str]) -> Dict[str, dict]:
        from kme_tpu.bridge.front import (verify_groups,
                                          verify_groups_reshard)
        from kme_tpu.feed.derive import books_from_oracle, \
            canonical_books
        from kme_tpu.oracle import OracleEngine
        from kme_tpu.wire import parse_order

        cfg = self.cfg
        out: Dict[str, dict] = {}
        completed = self.finished()
        out["completed"] = {"ok": completed, "vtime": round(
            self.sched.now, 6)}

        mo = [[f"{r.key} {r.value}" for r in
               leader.broker.fetch(leader.topic_out, 0, 10 ** 7)]
              if leader.broker is not None else []
              for leader in self.leaders]

        if self.resharded is not None:
            rep = verify_groups_reshard(
                lines, self.split_at, self.pre_matchout, mo,
                compat="fixed", book_slots=cfg.slots,
                max_fills=cfg.max_fills, prefund=cfg.prefund)
        else:
            rep = verify_groups(lines, mo, compat="fixed",
                                book_slots=cfg.slots,
                                max_fills=cfg.max_fills,
                                prefund=cfg.prefund)
        out["parity"] = {"ok": bool(rep["ok"]),
                         "mismatches": rep["mismatches"][:3],
                         "merged_lines": rep["merged_lines"]}

        # exactly-once stamps: MatchOut + Xfer share one out_seq
        # cursor per leader — the union must be duplicate-free
        dup = []
        for leader in self.leaders:
            if leader.broker is None:
                continue
            seqs: List[int] = []
            for t in (leader.topic_out, leader.topic_xfer):
                try:
                    recs = leader.broker.fetch(t, 0, 10 ** 7)
                except Exception:
                    continue
                seqs.extend(r.out_seq for r in recs
                            if r.out_seq is not None)
            if len(seqs) != len(set(seqs)):
                dup.append({"group": leader.g,
                            "rows": len(seqs),
                            "unique": len(set(seqs))})
        out["stamps"] = {"ok": not dup, "duplicates": dup}

        # conservation: group engines vs one single-leader oracle
        oracle = OracleEngine("fixed", cfg.slots, cfg.max_fills)
        for ln in lines:
            oracle.process(parse_order(ln))
        want_cash = sum(oracle.balances.values())
        got_cash = sum(
            sum(leader.svc._oracle.balances.values())
            for leader in self.leaders if leader.svc is not None)
        pending = [dict(leader.svc._xfer) for leader in self.leaders
                   if leader.svc is not None]
        out["conservation"] = {"ok": got_cash == want_cash,
                               "got": got_cash, "want": want_cash,
                               "pending_reserve": pending}

        # feed books vs the live engines
        feed_bad = []
        for fd in self.feeds:
            leader = self.leaders[fd.g]
            if leader.svc is None:
                continue
            want = canonical_books(books_from_oracle(
                leader.svc._oracle))
            got = canonical_books(fd.fd.book)
            if got != want:
                feed_bad.append(fd.g)
        out["feed"] = {"ok": not feed_bad, "mismatched": feed_bad}

        lag_bad = []
        for st in self.standbys:
            leader = self.leaders[st.g]
            if leader.svc is None:
                continue
            if st.applied() > leader.svc.offset:
                lag_bad.append({"group": st.g,
                                "applied": st.applied(),
                                "leader": leader.svc.offset})
        out["standby"] = {"ok": not lag_bad, "violations": lag_bad}
        return out

    def counters(self) -> Dict[str, int]:
        dup = self.old_dup_suppressed
        delivered = self.old_delivered
        for leader in self.leaders:
            if leader.broker is not None:
                dup += leader.broker.dup_suppressed
        delivered += sum(link.delivered
                         for link in self.transport.links)
        return {
            "routed": self.front.pos,
            "delivered": delivered,
            "dup_suppressed": dup,
            "reorder_dups": sum(link.dup_resends
                                for link in self.transport.links),
            "crashes": sum(leader.crashes for leader in self.leaders),
            "resharded": 1 if self.resharded is not None else 0,
            "faults_fired": faults.fired_total(),
        }


# ---------------------------------------------------------------------------


def _compose_lines(schedule: FaultSchedule, cfg: SimConfig) -> List[str]:
    from kme_tpu.wire import dumps_order
    from kme_tpu.workload import spliced_stream

    splices = [(ev["at"], ev["profile"], ev.get("n", 100))
               for ev in schedule.events if ev["kind"] == "storm"]
    msgs = spliced_stream(schedule.num_events, seed=schedule.seed,
                          splices=splices,
                          num_accounts=cfg.num_accounts,
                          num_symbols=cfg.num_symbols,
                          prefund_cash=cfg.prefund_cash)
    return [dumps_order(m) for m in msgs]


def run_sim(schedule: FaultSchedule, root: str,
            cfg: Optional[SimConfig] = None,
            planted_bug: Optional[str] = None,
            max_vtime: float = 600.0) -> SimResult:
    """Execute one seeded simulated run under ``root`` (a fresh
    directory per run). Returns the full verdict set plus the two
    determinism digests."""
    cfg = cfg or SimConfig()
    if schedule.ngroups < 2:
        raise ValueError("the sim cluster is grouped serving; "
                         "ngroups must be >= 2")
    sched = SimScheduler(schedule.seed)
    lines = _compose_lines(schedule, cfg)
    faults.configure(schedule.spec())
    try:
        cluster = _SimCluster(sched, schedule, cfg, root, planted_bug)
        cluster.start(lines, list(schedule.events))
        sched.run(until=cluster.finished, max_vtime=max_vtime)
        counters = cluster.counters()
        verdicts = cluster.verdicts(lines)
    finally:
        faults.clear()

    h = hashlib.sha256()
    for per in ([cluster.pre_matchout] if cluster.pre_matchout else []):
        for g, ls in enumerate(per):
            h.update(f"pre.g{g}:{len(ls)}\n".encode())
            for ln in ls:
                h.update(ln.encode("utf-8"))
                h.update(b"\n")
    for leader in cluster.leaders:
        ls = ([f"{r.key} {r.value}" for r in
               leader.broker.fetch(leader.topic_out, 0, 10 ** 7)]
              if leader.broker is not None else [])
        h.update(f"g{leader.g}:{len(ls)}\n".encode())
        for ln in ls:
            h.update(ln.encode("utf-8"))
            h.update(b"\n")

    # seventh verdict: the control-plane timeline. The embedded REAL
    # components (MatchService lease grants, the reshard coordinator's
    # phase events) wrote virtual-clock-stamped event logs under the
    # run root; merge them, verify every segment (digests, seq gaps),
    # and fold the timeline digest into trace_digest so the seed-sweep
    # byte-determinism check extends to the control plane for free
    from kme_tpu.telemetry import events as cpevents

    tl = cpevents.merge_logs([root])
    tl_digest = cpevents.timeline_digest(tl)
    bad_logs = []
    for lp in cpevents.discover_logs(root):
        rep = cpevents.verify_log(lp)
        if not rep.get("ok", False) or rep.get("seq_gaps"):
            bad_logs.append({"path": os.path.relpath(lp, root),
                             "seq_gaps": rep.get("seq_gaps", 0)})
    verdicts["timeline"] = {"ok": bool(tl) and not bad_logs,
                            "events": len(tl), "digest": tl_digest,
                            "bad_logs": bad_logs}
    trace_digest = hashlib.sha256(
        (sched.digest() + tl_digest).encode("ascii")).hexdigest()

    ok = all(v.get("ok", False) for v in verdicts.values())
    return SimResult(seed=schedule.seed, ok=ok, verdicts=verdicts,
                     trace_digest=trace_digest,
                     out_digest=h.hexdigest(), schedule=schedule,
                     counters=counters, vtime=round(sched.now, 6),
                     events=list(sched.events))
