"""Multi-chip SEQ fleet: a symbol-sharded set of sequential mega-kernels
under ONE shard_map, bit-exact vs single-chip serial replay.

The flagship seq engine (kme_tpu/engine/seq.py) is strictly serial on
one chip. Scale-out follows the reference's partition model (the topic
is partitioned and Streams instances split partitions — topic.js:18,
KProcessor.java:59-60), TPU-first: lanes (books, positions, seq
counters) are SHARDED over the 'symbol' mesh axis — each device runs
its own seq kernel over its own message subsequence — and balances are
REPLICATED with exact psum delta-merges at window boundaries.

Why this is bit-exact (the window invariant): within one window every
ACCOUNT's messages live on a single shard, so an account's balance
evolves exactly as in serial replay (balance writes are always to the
acting account: taker debit/credit, transfer, cancel release; maker
fills credit price 0 and touch only lane-local position state). The
host planner (plan_windows) closes a window whenever a message's
account is already bound to a different shard, whenever a shard's
window capacity fills, and around barriers (PAYOUT/REMOVE credit many
accounts, so each runs alone in its own window). At a window boundary
each shard contributes an int64 balance delta with at most one nonzero
contributor per account — psum is exact, including Java-long wrap.

The sticky error plane is pmax-merged (any shard's envelope error
surfaces globally; WHICH error wins when several shards fail in one
window is unspecified, unlike the serial engine's first-error rule —
the error path aborts the stream either way).

ELASTIC PLACEMENT (this round): lanes are no longer pinned to shards
by the static `global_lane // S_local` layout. A placement table
(`_perm`: global lane -> global slot; shard = slot // S_local) starts
as the identity — byte-identical to the old static layout — and a
per-lane load EWMA drives BETWEEN-BATCH migrations of hot lanes to
underloaded shards (plan_rebalance decides, _migrate permutes the
sharded lane axis of the state pytree through the engine's canonical
codec). Correctness is placement-INDEPENDENT: the engine is a
deterministic state machine, so any symbol->shard assignment that
preserves the global application order and the per-window
account-disjointness invariant above yields byte-identical MatchOut —
which is what lets the planner rebalance aggressively and the tests
gate on oracle parity WITH migrations observed
(tests/test_shard_elastic.py, shard_proof below).

PER-CHIP ASYNC DISPATCH (this round): the shard_map scan above is
LOCKSTEP — one dispatch, every shard waits for the slowest shard at
every window boundary, and per-chip walls are unmeasurable from the
host. `dispatch="async"` (the default wherever every mesh device is
locally addressable) breaks that: each shard gets its OWN submission
queue of window segments, dispatched as independent per-device scan
calls that drain at the shard's own rate. The global psum barrier is
replaced by the minimal dependency set the window invariant implies:
when an account's messages move from shard A to shard B between
windows, B's queue takes a point-to-point dependency on A — the host
fetches A's (tiny) balance planes as of that window and patches ONLY
the moved accounts into B's planes with an on-device scatter; all
other shards run ahead untouched. Barriers (PAYOUT/REMOVE credit many
accounts) and the batch-end collect are the only FULL merges: the
host selects each account's balance from the shard that last bound it
(tracked exactly by the planner), pushes the merged planes to every
shard, and output order is re-established at collect from the same
placements list the lockstep path uses — so MatchOut stays byte-exact
vs the single-chip oracle in both modes. Lockstep remains available
(`dispatch="lockstep"`) and byte-identical to the pre-async behavior;
multi-process meshes (tests/test_multihost.py) fall back to lockstep
automatically because per-device queues need locally addressable
devices.

One semantic note: the sticky error plane is per-shard in async mode
(no per-window pmax), so after an envelope error the OTHER shards keep
executing their queued windows instead of no-opping. The first errored
(window, shard) cell in collect order raises the same LaneEngineError
either way, and the error path aborts the stream, so the divergence is
unobservable through the session surface.

Executed evidence: tests/test_seqmesh.py (bit-exact at shards 1/2/8 on
a virtual mesh vs the scalar oracle and the single-chip SeqSession),
tests/test_async_dispatch.py (async-vs-lockstep byte parity under
migrations, payout storms, mid-stream checkpoints; stall-schedule
determinism), tests/test_multihost.py (the same program SPMD across
two OS processes), and __graft_entry__.dryrun_multichip (the driver's
multichip artifact).
"""

from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional

import numpy as np

import kme_tpu._jaxsetup  # noqa: F401
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from kme_tpu.engine import seq as SQ
from kme_tpu.native import sched as native_sched
from kme_tpu.runtime.seqsession import (LaneEngineError, SeqSession,
                                        make_seq_router)
from kme_tpu.telemetry import PhaseTimer, Registry
from kme_tpu.utils import pow2_bucket

AXIS = "symbol"


@functools.lru_cache(maxsize=None)
def build_mesh(shards: int) -> Mesh:
    """One Mesh per shard count per process — sessions share it, so the
    jitted sharded builders below cache across sessions."""
    devs = jax.devices()
    if len(devs) < shards:
        raise ValueError(
            f"need {shards} devices for {shards} shards, have {len(devs)}")
    return Mesh(np.array(devs[:shards]), axis_names=(AXIS,))


# per-shard per-window message capacity (windows close earlier on
# account conflicts; 128 keeps the padded input planes small)
WINDOW_CAP = 128

# rebalance when the hottest shard's EWMA load exceeds the mean by
# this factor; migrating costs a full canonical round-trip of the lane
# state, so the trigger is deliberately above measurement noise
REBALANCE_THRESHOLD = 1.25
# per-batch decay of the per-lane load estimate
LOAD_EWMA_ALPHA = 0.5
# matchable messages (BUY/SELL) sweep makers; everything else is O(1)
MATCH_WORK_WEIGHT = 2.0

# wall-feed (async only, opt-in): EWMA decay and clip for the measured
# per-shard cost rate that scales the rebalancer's lane weights
WALL_RATE_ALPHA = 0.5
WALL_RATE_MIN, WALL_RATE_MAX = 0.5, 2.0

# communication costs for the dispatch-schedule simulation, in the
# same work units as the per-message weights. The lockstep scan pays a
# full cross-shard collective EVERY window (balance psum + sticky-err
# pmax + output all_gather are baked into its scan body); async pays
# the full merge only at barriers and batch-end collect, plus one
# point-to-point fetch+scatter per dependency patch. Modeling that
# asymmetry is what makes chip_stall_frac reflect the schedules'
# actual communication structure, not just their compute.
MERGE_COST_WEIGHT = 0.5   # collective cost per participating shard
# host-side cost of one point-to-point dep fetch + scatter enqueue.
# Deliberately below one message unit: the dominant real cost of a
# patch — waiting for the source shard's earlier windows — is modeled
# separately via the prev[src] wait; this term only covers the host's
# drain/materialize + scatter enqueue of a few KB of balance planes
PATCH_COST = 0.25

_MSG_FIELDS = ("act", "aid", "price", "size", "lane",
               "oid_lo", "oid_hi")


@jax.jit
def _scatter_balances(lo, hi, u, rows, cls, vlo, vhi, vu):
    """On-device patch of forwarded account balances into a shard's
    replicated planes. Callers pad the index/value arrays by REPEATING
    the last entry, so duplicate scatter indices always carry identical
    values and the scatter is order-independent (deterministic)."""
    return (lo.at[rows, cls].set(vlo),
            hi.at[rows, cls].set(vhi),
            u.at[rows, cls].set(vu))


def make_mesh_state(local_cfg: SQ.SeqConfig, shards: int) -> dict:
    """Global state pytree: per-shard seq states stacked on the leading
    row axis for the sharded keys; balances/err replicated."""
    local = SQ.make_seq_state(local_cfg)
    out = {}
    for k, v in local.items():
        if k in ("bal_lo", "bal_hi", "bal_u", "err"):
            out[k] = v
        else:
            out[k] = jnp.tile(v, (shards, 1))
    return out


def state_specs(local_cfg: SQ.SeqConfig) -> dict:
    specs = {}
    for k in SQ.state_keys(local_cfg):
        if k in ("bal_lo", "bal_hi", "bal_u", "err"):
            specs[k] = P()
        else:
            specs[k] = P(AXIS)
    return specs


def _i64(lo, hi):
    return ((lo.astype(jnp.int64) & 0xFFFFFFFF)
            | (hi.astype(jnp.int64) << 32))


def _split64(v):
    lo = v & 0xFFFFFFFF
    lo = jnp.where(lo >= 1 << 31, lo - (1 << 32), lo).astype(jnp.int32)
    return lo, (v >> 32).astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def build_seq_mesh_scan(local_cfg: SQ.SeqConfig, shards: int, K: int):
    """Jitted (state, wins) -> (state, out_planes): a lax.scan over K
    account-disjoint windows inside ONE shard_map. Each window: the
    per-shard seq kernel runs its local sub-batch, then balance deltas
    psum-merge (exact — see module docstring) and the sticky error
    pmax-merges."""
    mesh = build_mesh(shards)
    _, raw_call = SQ.build_seq_step(local_cfg)

    def body(state, win):
        start_lo = state["bal_lo"]
        start_hi = state["bal_hi"]
        start_u = state["bal_u"]
        st2, outp = raw_call(state, win)
        old = _i64(start_lo, start_hi)
        delta = _i64(st2["bal_lo"], st2["bal_hi"]) - old
        merged = old + jax.lax.psum(delta, AXIS)
        mlo, mhi = _split64(merged)
        mu = start_u + jax.lax.psum(st2["bal_u"] - start_u, AXIS)
        err = jax.lax.pmax(st2["err"], AXIS)
        st2 = dict(st2, bal_lo=mlo, bal_hi=mhi, bal_u=mu, err=err)
        # REPLICATE the window's out planes (all_gather over ICI/DCN):
        # under multi-process meshes the host can only fetch
        # fully-addressable arrays (tests/test_multihost.py)
        return st2, jax.lax.all_gather(outp, AXIS)

    def run(state, wins):
        return jax.lax.scan(body, state, wins, length=K)

    specs = state_specs(local_cfg)
    win_specs = {f: P(None, AXIS) for f in _MSG_FIELDS}
    # NO jit-level donation: it composes badly with the kernel's
    # input_output_aliases (clobbered aliased outputs — the documented
    # hazard in build_seq_step's NOTE), at the cost of one state copy
    # per dispatch. Varying-mesh-axes checking is off: the body holds a
    # pallas_call, whose out_shapes carry no vma annotation.
    sharded = jax.shard_map(run, mesh=mesh, in_specs=(specs, win_specs),
                            out_specs=(specs, P()), check_vma=False)
    return jax.jit(sharded)   # outs: (K, shards, NROWS, 128) replicated


def plan_rebalance(lane_load, perm, shards: int,
                   threshold: float = REBALANCE_THRESHOLD,
                   max_swaps: Optional[int] = None):
    """Pure placement decision: given the per-lane load EWMA and the
    current placement table, return a new table (or None for "stay").

    Greedy slot swaps between the hottest and coldest shard, accepted
    only while each swap STRICTLY reduces that pair's peak load, so the
    loop terminates and a balanced table is a fixed point. Fully
    deterministic (argmax/argmin first-index ties, no RNG) — the
    decision is replay-safe by construction, which kme-lint's KME-D002
    replay scope pins.
    """
    S = len(perm)
    Sl = S // shards
    total = float(lane_load.sum())
    if total <= 0.0:
        return None
    shard_loads = np.bincount(perm // Sl, weights=lane_load,
                              minlength=shards).astype(float)
    mean = total / shards
    if shard_loads.max() <= threshold * mean:
        return None
    new = perm.copy()
    budget = S if max_swaps is None else max_swaps
    swapped = False
    for _ in range(budget):
        h = int(shard_loads.argmax())
        c = int(shard_loads.argmin())
        if h == c:
            break
        # best single lane swap hot<->cold: minimize the pair's peak
        best = None
        for gh in range(S):
            if new[gh] // Sl != h:
                continue
            for gc in range(S):
                if new[gc] // Sl != c:
                    continue
                d = float(lane_load[gh]) - float(lane_load[gc])
                if d <= 0.0:
                    continue
                peak = max(shard_loads[h] - d, shard_loads[c] + d)
                if peak >= shard_loads[h]:
                    continue
                if best is None or peak < best[0]:
                    best = (peak, gh, gc, d)
        if best is None:
            break
        _, gh, gc, d = best
        new[gh], new[gc] = new[gc], new[gh]
        shard_loads[h] -= d
        shard_loads[c] += d
        swapped = True
    return new if swapped else None


class SeqMeshSession(SeqSession):
    """Sharded drop-in for SeqSession (fixed mode): same process /
    process_wire / process_wire_buffer surface, state sharded over a
    `shards`-device mesh.

    `dispatch` selects the mesh execution discipline:

    - "async" (default where available): per-shard submission queues —
      independent per-device scan segments with point-to-point balance
      forwarding and full merges only at barriers and batch-end collect
      (module docstring). Needs every mesh device locally addressable.
    - "lockstep": the original single-shard_map scan with per-window
      psum merges; byte-identical to the pre-async behavior.
    - "auto": async when capable, else lockstep (multi-process SPMD).

    Both modes produce byte-identical MatchOut. `wall_feed=True`
    (async only) feeds measured per-chip walls into the rebalancer's
    lane-load EWMA as a per-shard cost rate — placement changes, bytes
    don't (correctness is placement-independent, see ELASTIC above)."""

    # replicated state keys: migration must NOT permute these
    _REPL_KEYS = ("bal_lo", "bal_hi", "bal_u", "err")

    def __init__(self, cfg: SQ.SeqConfig, shards: int, *,
                 rebalance: bool = True,
                 rebalance_threshold: float = REBALANCE_THRESHOLD,
                 dispatch: str = "auto",
                 wall_feed: bool = False,
                 ) -> None:
        if cfg.compat != "fixed":
            raise ValueError(
                "sharded seq serving is fixed-mode only (java mode is "
                "single-chip by Q11's serial semantics, COMPAT.md)")
        if cfg.hbm_books:
            raise ValueError("seq mesh uses VMEM books per shard")
        if cfg.lanes % shards:
            raise ValueError(f"lanes {cfg.lanes} not divisible by "
                             f"{shards} shards")
        self.cfg = cfg
        self.shards = shards
        self.local_cfg = SQ.SeqConfig(
            lanes=cfg.lanes // shards, slots=cfg.slots,
            accounts=cfg.accounts, max_fills=cfg.max_fills,
            batch=WINDOW_CAP, fill_cap=cfg.fill_cap)
        self.S_local = cfg.lanes // shards
        self.state = make_mesh_state(self.local_cfg, shards)
        self.router = make_seq_router(cfg.lanes, cfg.accounts)
        self._metrics = np.zeros(SQ.N_METRICS, np.int64)
        self._hist = np.zeros((SQ.N_HIST, SQ.N_HIST_BUCKETS), np.int64)
        self._recon = None
        self.telemetry = Registry()
        self.timer = PhaseTimer(track="seqmesh")
        self.phases = self.timer.totals   # cumulative across batches
        self._use_native_wire = True
        self._ghint = 8
        # position tiles the shards' kernels brought in from HBM (each
        # call's own count, added at collect as in SeqSession)
        self.pos_probe_tiles = 0
        # elastic placement: global lane -> global slot; shard of a
        # lane is perm[lane] // S_local, its kernel row perm[lane] %
        # S_local. Identity == the pre-elastic static layout.
        self.rebalance = rebalance
        self.rebalance_threshold = rebalance_threshold
        self._perm = np.arange(cfg.lanes, dtype=np.int64)
        self._lane_load = np.zeros(cfg.lanes, np.float64)
        # sticky account home: last GLOBAL LANE the account traded on
        # (tracked as a lane, not a shard, so homes follow migrations)
        self._acct_lane: Dict[int, int] = {}
        self._migrations = 0
        self._rebalances = 0
        self._occ_shard = np.zeros(shards, np.int64)
        self._hist_shard = np.zeros(
            (shards, SQ.N_HIST, SQ.N_HIST_BUCKETS), np.int64)
        # -- per-chip async dispatch --
        if dispatch not in ("auto", "async", "lockstep"):
            raise ValueError(f"unknown dispatch mode {dispatch!r}")
        capable = self._async_capable(shards)
        if dispatch == "auto":
            dispatch = "async" if capable else "lockstep"
        elif dispatch == "async" and not capable:
            raise ValueError(
                "async dispatch needs every mesh device locally "
                "addressable (single-process mesh); use "
                "dispatch='lockstep' or 'auto'")
        self.dispatch = dispatch
        self.wall_feed = wall_feed
        self._bal_shape = tuple(self.state["bal_lo"].shape)
        self._shard_rate = np.ones(shards, np.float64)
        self._shard_states: Optional[List[dict]] = None
        self._devices = None
        # deterministic stall schedule accumulators (plan_dispatch)
        self._sim_busy = np.zeros(shards, np.float64)
        self._sim_T_async = 0.0
        self._sim_T_lock = 0.0
        # measured per-chip walls + H2D overlap accounting
        self._msgs_total = 0
        self._async_wall_total = 0.0
        self._h2d_total_s = 0.0
        self._h2d_overlap_s = 0.0
        self._seg_inflight = 0
        self._t0_shard: List[Optional[float]] = [None] * shards
        if dispatch == "async":
            self._init_async_states()

    @staticmethod
    def _async_capable(shards: int) -> bool:
        """Per-shard queues dispatch to individual devices with
        jax.device_put, which needs every device addressable from this
        process — false under multi-process SPMD (test_multihost)."""
        try:
            return jax.process_count() == 1
        except Exception:  # pragma: no cover - defensive
            return False

    def _init_async_states(self) -> None:
        mesh = build_mesh(self.shards)
        self._devices = [d for d in np.asarray(mesh.devices).reshape(-1)]
        host = {k: np.asarray(v) for k, v in self.state.items()}
        self._split_state_async(host)
        self.state = None   # async truth lives in _shard_states

    def _split_state_async(self, host: dict) -> None:
        """Host stacked state dict -> per-shard device-committed local
        states (replicated planes copied to every shard)."""
        states = []
        for s in range(self.shards):
            loc = {k: (v if k in self._REPL_KEYS
                       else v.reshape(self.shards, -1, v.shape[-1])[s])
                   for k, v in host.items()}
            states.append(jax.device_put(loc, self._devices[s]))
        self._shard_states = states

    def _gather_state_async(self) -> dict:
        """Per-shard states -> host stacked dict (the lockstep layout).
        Only called at batch boundaries, where _collect_merge has left
        every shard's replicated planes identical — so the replicated
        keys legitimately come from shard 0."""
        parts = [jax.device_get(st) for st in self._shard_states]
        host = {}
        for k in parts[0]:
            if k in self._REPL_KEYS:
                host[k] = np.asarray(parts[0][k])
            else:
                host[k] = np.concatenate(
                    [np.asarray(p[k]) for p in parts], axis=0)
        return host

    # -- host planning -------------------------------------------------

    def plan_windows(self, cols):
        """Columnar routed messages -> (wins dict of (K, shards*Bw) i32,
        placements list of (window, shard, pos) per routed message,
        cnts (K, shards) int).

        A lane's shard and kernel row come from the elastic placement
        table (`_perm`, applied once per batch via
        native_sched.apply_placement), NOT the old static
        `lane // S_local` split. Laneless balance messages (CREATE/
        TRANSFER) follow the account's sticky home lane, which is the
        last lane it traded on — tracked as a LANE so a migration
        automatically re-pins the account to the lane's new shard and
        the balance-coupling window invariant survives rebalancing.

        The planner is host Python (per-message loop): fine for the
        dryrun/test scale this session targets; a measured multi-chip
        serving path would move it next to the C++ router
        (native/kme_router.cpp) like round 4 did for routing."""
        n = len(cols["act"])
        Bw = WINDOW_CAP
        acts = cols["act"]
        lanes = cols["lane"]
        aids = cols["aid"]
        _, shard_col, local_col = native_sched.apply_placement(
            self._perm, lanes, self.S_local)
        barrier = ((acts == SQ.L_PAYOUT_YES) | (acts == SQ.L_PAYOUT_NO)
                   | (acts == SQ.L_REMOVE_SYMBOL))
        laneful = ((acts == SQ.L_BUY) | (acts == SQ.L_SELL)
                   | (acts == SQ.L_CANCEL) | (acts == SQ.L_ADD_SYMBOL)
                   | barrier)
        # only balance-touching acts bind their account to a shard
        # (ADD_SYMBOL routes with aid=0 but never touches balances)
        binds = ((acts == SQ.L_BUY) | (acts == SQ.L_SELL)
                 | (acts == SQ.L_CANCEL) | (acts == SQ.L_CREATE)
                 | (acts == SQ.L_TRANSFER))
        windows: List[List[List[int]]] = []  # [w][s] -> routed indices
        placements = []
        bound: Dict[int, int] = {}
        cur = [[] for _ in range(self.shards)]

        def flush():
            nonlocal cur, bound
            if any(cur[s] for s in range(self.shards)):
                windows.append(cur)
            cur = [[] for _ in range(self.shards)]
            bound = {}

        for k in range(n):
            if barrier[k]:
                # barriers credit many accounts: run alone
                flush()
                cur[int(shard_col[k])].append(k)
                flush()
                continue
            a = int(aids[k])
            if laneful[k]:
                s = int(shard_col[k])
                if binds[k]:
                    self._acct_lane[a] = int(lanes[k])
            else:
                s = bound.get(a, self._home_shard(a))
            b = bound.get(a) if binds[k] else None
            if (b is not None and b != s) or len(cur[s]) >= Bw:
                flush()
            if binds[k]:
                bound[a] = s
            cur[s].append(k)
        flush()

        K = pow2_bucket(max(len(windows), 1), lo=1)
        wins = {f: np.zeros((K, self.shards, Bw), np.int32)
                for f in _MSG_FIELDS}
        cnts = np.zeros((K, self.shards), np.int32)
        for w, per in enumerate(windows):
            for s, idxs in enumerate(per):
                cnts[w, s] = len(idxs)
                for p, k in enumerate(idxs):
                    placements.append((k, w, s, p))
                    wins["act"][w, s, p] = cols["act"][k]
                    wins["aid"][w, s, p] = cols["aid"][k]
                    wins["price"][w, s, p] = cols["price"][k]
                    wins["size"][w, s, p] = cols["size"][k]
                    wins["lane"][w, s, p] = int(local_col[k])
                    oid = int(cols["oid"][k])
                    lo = oid & 0xFFFFFFFF
                    wins["oid_lo"][w, s, p] = np.int32(
                        lo - (1 << 32) if lo >= 1 << 31 else lo)
                    wins["oid_hi"][w, s, p] = np.int32(oid >> 32)
        wins = {f: v.reshape(K, self.shards * WINDOW_CAP)
                for f, v in wins.items()}
        placements.sort()
        return wins, placements, cnts, K

    # -- the SeqSession contract ---------------------------------------

    def _run(self, msgs):
        if self.dispatch == "async":
            return self._run_async(msgs)
        return self._run_lockstep(msgs)

    def _run_lockstep(self, msgs):
        # migrations happen BETWEEN batches only: state is quiescent
        # here, so the permutation is a pure relabeling of lane rows
        self._maybe_rebalance()

        with self.timer.phase("plan_s"):
            cols, host_rejects = self.router.route(msgs)
            self._note_load(cols)
            wins, placements, cnts, K = self.plan_windows(cols)

        with self.timer.phase("dispatch_s"):
            t_disp = time.perf_counter()
            scan = build_seq_mesh_scan(self.local_cfg, self.shards, K)
            self.state, outs = scan(self.state, wins)
            jax.block_until_ready(self.state)
            disp_wall = time.perf_counter() - t_disp

        with self.timer.phase("fetch_s"):
            outs = np.asarray(outs)   # (K, shards, NROWS, 128)
            HR = SQ.hdr_rows(self.local_cfg)
            n = len(cols["act"])
            host = {k: np.zeros(n, dt) for k, dt in
                    (("ok", bool), ("cap_reject", bool),
                     ("append", bool), ("residual", np.int64),
                     ("nfill", np.int64), ("prev_oid", np.int64))}
            groups = {}
            mets = np.zeros(SQ.N_METRICS, np.int64)
            # batch_occupancy convention (documented + tested,
            # tests/test_shard_elastic.py): per-(window, shard) kernel
            # calls are the dispatch units here, so batch_occupancy
            # observes per-shard SUB-WINDOWS — one observation per
            # non-empty (w, s) cell, valued at that cell's message
            # count cnts[w, s], NOT one blended observation per host
            # batch like the single-chip session. The same counters
            # accumulate per shard into _hist_shard and surface as
            # batch_occupancy_shard{N} (histograms()); the cumulative
            # per-shard occupancy totals (_occ_shard) feed the
            # shard_imbalance gauge = max/mean per-shard occupancy.
            hists = np.zeros((SQ.N_HIST, SQ.N_HIST_BUCKETS), np.int64)
            for w in range(K):
                for s in range(self.shards):
                    cnt = int(cnts[w, s])
                    if not cnt:
                        continue
                    res = SQ.unpack_hdr(self.local_cfg,
                                        outs[w, s][:HR], cnt)
                    if res["err"] != SQ.LERR_OK:
                        raise LaneEngineError(res["err"])
                    ft = res["fill_total"]
                    gr = outs[w, s][HR:HR + 5 * (-(-max(ft, 1) // 128))]
                    groups[(w, s)] = (res, SQ.unpack_fills(gr, ft),
                                      np.concatenate(
                                          ([0], np.cumsum(res["nfill"]))))
                    mets += res["metrics"]
                    hists += res["hist"]
                    self._hist_shard[s] += res["hist"]
                    self.pos_probe_tiles += res["pos_tiles"]
            self._metrics += mets
            self._hist += hists
            self._publish_shard_telemetry(
                disp_wall, cnts.sum(axis=0).astype(np.int64))
            fills_parts = []
            for k, w, s, p in placements:
                res, fills_ws, off = groups[(w, s)]
                for key in host:
                    host[key][k] = res[key][p]
                if res["nfill"][p]:
                    fills_parts.append(fills_ws[:, off[p]:off[p + 1]])
            fills = (np.concatenate(fills_parts, axis=1) if fills_parts
                     else np.zeros((4, 0), np.int64))
        return cols, host_rejects, host, fills

    # -- per-chip async dispatch ---------------------------------------

    def _run_async(self, msgs):
        self._maybe_rebalance()

        with self.timer.phase("plan_s"):
            cols, host_rejects = self.router.route(msgs)
            self._note_load(cols)
            wins, placements, cnts, K = self.plan_windows(cols)
            plan = self.plan_dispatch(cols, placements)

        with self.timer.phase("dispatch_s"):
            t_disp = time.perf_counter()
            out_map, walls = self._dispatch_async(wins, cnts, plan)
            disp_wall = time.perf_counter() - t_disp

        with self.timer.phase("fetch_s"):
            host, fills = self._unpack_outputs(
                cols, placements, cnts, K, out_map)
            occ = cnts.sum(axis=0).astype(np.int64)
            self._sim_busy += plan["busy"]
            self._sim_T_async += plan["T_async"]
            self._sim_T_lock += plan["T_lock"]
            self._msgs_total += int(occ.sum())
            if walls.size:
                self._async_wall_total += float(walls.max())
            if self.wall_feed:
                self._update_wall_rates(walls, plan["busy"])
            self._publish_shard_telemetry_async(walls, occ, disp_wall)
        return cols, host_rejects, host, fills

    def _owner_sel(self, loc: Dict[int, int],
                   base: Optional[int]) -> np.ndarray:
        """Per-account owner-shard selection table for a full merge:
        account a's authoritative balance copy lives on loc[a], else on
        `base` (the shard the last barrier ran on), else anywhere (all
        shards identical since the previous merge — pick 0)."""
        sel = np.zeros(self._bal_shape[0] * self._bal_shape[1],
                       np.int32)
        if base:
            sel[:] = base
        for a, s in loc.items():
            sel[a] = s
        return sel

    def plan_dispatch(self, cols, placements) -> dict:
        """Pure host planning for async dispatch (hot scope: no device
        syncs, no blocking I/O). One walk over the batch's windows in
        stream order derives:

        - `deps[(w, s)]`: the point-to-point dependency set — accounts
          bound to shard s in window w whose authoritative balance copy
          currently lives on another shard (the ONLY cross-shard waits
          the async schedule takes outside barriers);
        - `merge_sel[w]` / `final_sel`: owner-selection tables for the
          full merges at barrier windows and batch-end collect;
        - a deterministic stall schedule for BOTH dispatch modes, with
          per-message weighted costs (MATCH_WORK_WEIGHT, same as the
          rebalancer) plus communication terms (MERGE_COST_WEIGHT /
          PATCH_COST): async — per-shard clocks plus a host clock that
          blocks on the source shard (+ one patch cost) at each
          dependency fetch, a full-merge collective at barriers and
          batch-end only; lockstep — every window is a global barrier
          AND a full collective, so T += max-shard cost + S·merge per
          window. chip_stall_frac derives from this schedule, so it is
          replay-stable and backend-independent.
        """
        acts = cols["act"]
        aids = cols["aid"]
        S = self.shards
        W = placements[-1][1] + 1 if placements else 0
        barrier_acts = (SQ.L_PAYOUT_YES, SQ.L_PAYOUT_NO,
                        SQ.L_REMOVE_SYMBOL)
        bind_acts = (SQ.L_BUY, SQ.L_SELL, SQ.L_CANCEL, SQ.L_CREATE,
                     SQ.L_TRANSFER)
        cost = np.zeros((W, S))
        binds_w: List[List] = [[] for _ in range(W)]
        barriers: Dict[int, int] = {}
        for k, w, s, _ in placements:
            act = int(acts[k])
            if act in barrier_acts:
                barriers[w] = s
            cost[w, s] += (MATCH_WORK_WEIGHT
                           if act in (SQ.L_BUY, SQ.L_SELL) else 1.0)
            if act in bind_acts:
                binds_w[w].append((int(aids[k]), s))
        deps: Dict[tuple, list] = {}
        merge_sel: Dict[int, np.ndarray] = {}
        loc: Dict[int, int] = {}
        base: Optional[int] = None
        clock = np.zeros(S)
        busy = np.zeros(S)
        host_t = 0.0
        t_lock = 0.0
        m_full = MERGE_COST_WEIGHT * S   # one full-merge collective
        for w in range(W):
            # lockstep: barrier + collective (psum/pmax/all_gather in
            # the scan body) every window
            t_lock += float(cost[w].max()) + m_full
            bs = barriers.get(w)
            if bs is not None:
                # full merge: host waits for every shard, pays ONE
                # collective, then the barrier cell runs alone
                merge_sel[w] = self._owner_sel(loc, base)
                t = max(float(clock.max()), host_t) + m_full
                clock[:] = t
                host_t = t
                clock[bs] = t + float(cost[w, bs])
                busy[bs] += float(cost[w, bs])
                loc = {}
                base = bs
                continue
            cell_deps: Dict[tuple, Dict[int, int]] = {}
            for a, s in binds_w[w]:
                src = loc.get(a, base)
                if src is not None and src != s:
                    cell_deps.setdefault((w, s), {})[a] = src
            for key, d in cell_deps.items():
                deps[key] = sorted(d.items())
            # dependency fetches read the SOURCE shard as of window w-1
            # (the dispatcher patches before appending w to any queue),
            # so dep waits use the pre-window clocks: every cell starts
            # no later than the lockstep barrier max — T_async <= T_lock
            # by induction, strictly less whenever windows are imbalanced
            prev = clock.copy()
            for s in range(S):
                c = float(cost[w, s])
                if c <= 0.0:
                    continue
                dl = cell_deps.get((w, s))
                if dl:
                    for src in sorted(set(dl.values())):
                        # drain src, then one point-to-point
                        # fetch+scatter onto the destination
                        host_t = (max(host_t, float(prev[src]))
                                  + PATCH_COST)
                    start = max(float(prev[s]), host_t)
                else:
                    start = float(prev[s])
                clock[s] = start + c
                busy[s] += c
            for a, s in binds_w[w]:
                loc[a] = s
        return {
            "W": W, "deps": deps, "barriers": barriers,
            "merge_sel": merge_sel,
            "final_sel": self._owner_sel(loc, base),
            "busy": busy,
            # batch-end collect pays async's one deferred collective
            "T_async": ((max(float(clock.max()), host_t) + m_full)
                        if W else 0.0),
            "T_lock": t_lock,
        }

    def _stage_and_dispatch(self, s: int, seg: dict):
        """Enqueue one window segment on shard s's dispatch stream (hot
        scope: device_put is async, the jitted per-device scan returns
        futures — no host syncs here). H2D staging time is charged as
        overlapped when any earlier segment of this batch is still in
        flight: that is exactly the device-side double-buffering win —
        shard s's (or a peer's) compute hides the copy."""
        t0 = time.perf_counter()
        staged = jax.device_put(seg, self._devices[s])
        dt = time.perf_counter() - t0
        self._h2d_total_s += dt
        if self._seg_inflight:
            self._h2d_overlap_s += dt
        self._seg_inflight += 1
        if self._t0_shard[s] is None:
            self._t0_shard[s] = t0
        kseg = next(iter(staged.values())).shape[0]
        scan = SQ.build_seq_scan(self.local_cfg, kseg)
        st2, outs = scan(self._shard_states[s], staged)
        self._shard_states[s] = st2
        return outs

    def _patch_shard(self, s: int, rows, cls, vlo, vhi, vu) -> None:
        """Enqueue an on-device scatter of forwarded account balances
        into shard s's replicated planes (hot scope: no syncs). Arrays
        are padded by repeating the LAST entry — duplicate scatter
        indices with identical values stay deterministic — so the jit
        cache is bounded by pow2 bucket sizes."""
        n = rows.shape[0]
        npad = pow2_bucket(n, lo=8)

        def pad(a):
            out = np.empty(npad, a.dtype)
            out[:n] = a
            out[n:] = a[n - 1]
            return out

        dev = self._devices[s]
        args = [jax.device_put(pad(a), dev)
                for a in (rows, cls, vlo, vhi, vu)]
        st = self._shard_states[s]
        lo, hi, u = _scatter_balances(
            st["bal_lo"], st["bal_hi"], st["bal_u"], *args)
        self._shard_states[s] = dict(st, bal_lo=lo, bal_hi=hi, bal_u=u)

    def _collect_merge(self, sel: np.ndarray) -> None:
        """FULL merge barrier (watermark/checkpoint/produce boundary or
        barrier window): drain every shard, select each account's
        authoritative balance copy per `sel`, max-merge the sticky
        error, and push the merged replicated planes to every shard."""
        parts = []
        err = None
        for s in range(self.shards):
            st = self._shard_states[s]
            parts.append({k: np.asarray(st[k]) for k in SQ.BAL_KEYS})
            e = np.asarray(st["err"])
            err = e if err is None else np.maximum(err, e)
        merged = SQ.select_balances(parts, sel)
        merged["err"] = err
        for s in range(self.shards):
            put = jax.device_put(merged, self._devices[s])
            self._shard_states[s] = dict(self._shard_states[s], **put)
        self._seg_inflight = 0

    def _dispatch_async(self, wins, cnts, plan):
        """Walk the batch's windows in stream order, buffering each
        shard's windows into its own submission queue and flushing a
        queue only when forced: a dependency fetch (point-to-point — the
        host drains the SOURCE shard and patches just the moved accounts
        into the destination), a barrier (full merge), or batch end.
        Shards without dependencies run arbitrarily far ahead."""
        Bw = WINDOW_CAP
        S = self.shards
        pend: List[List[int]] = [[] for _ in range(S)]
        segs: List[List[tuple]] = [[] for _ in range(S)]
        fetched: Dict[int, tuple] = {}
        self._t0_shard = [None] * S
        self._seg_inflight = 0

        def flush(s):
            if not pend[s]:
                return
            win_idx, pend[s] = pend[s], []
            fetched.pop(s, None)
            seg = native_sched.slice_windows(wins, win_idx, s, S, Bw)
            segs[s].append((win_idx, self._stage_and_dispatch(s, seg)))

        def planes_of(src):
            # blocks the host until src's queue drains — THE
            # point-to-point wait. The cached fetch is only valid while
            # src has received no further windows: any pending (or
            # patched — see the explicit pops) work invalidates it.
            if pend[src] or src not in fetched:
                flush(src)
                st = self._shard_states[src]
                fetched[src] = tuple(np.asarray(st[k])
                                     for k in SQ.BAL_KEYS)
            return fetched[src]

        for w in range(plan["W"]):
            bs = plan["barriers"].get(w)
            if bs is not None:
                for s in range(S):
                    flush(s)
                self._collect_merge(plan["merge_sel"][w])
                fetched.clear()
                pend[bs].append(w)
                flush(bs)
                continue
            # pass 1 — dependency fetches + patches BEFORE window w is
            # appended to ANY queue: the source flush inside planes_of
            # therefore only covers windows <= w-1, matching the stall
            # schedule's prev-clock dep waits (and lockstep's timing
            # bound). Patch-then-append keeps the destination's on-device
            # scatter ordered after its own w-1 segment by data flow.
            for s in range(S):
                if not cnts[w, s]:
                    continue
                dl = plan["deps"].get((w, s))
                if not dl:
                    continue
                flush(s)
                by_src: Dict[int, list] = {}
                for a, src in dl:
                    by_src.setdefault(src, []).append(a)
                for src in sorted(by_src):
                    lo_p, hi_p, u_p = planes_of(src)
                    accs = np.fromiter(by_src[src], np.int64,
                                       len(by_src[src]))
                    rows = (accs >> 7).astype(np.int32)
                    cls_ = (accs & 127).astype(np.int32)
                    self._patch_shard(
                        s, rows, cls_, lo_p[rows, cls_],
                        hi_p[rows, cls_], u_p[rows, cls_])
                fetched.pop(s, None)
            # pass 2 — enqueue window w on every occupied shard
            for s in range(S):
                if cnts[w, s]:
                    pend[s].append(w)
        for s in range(S):
            flush(s)
        # drain + measure real per-chip walls (first submit -> done)
        walls = np.zeros(S, np.float64)
        for s in range(S):
            if segs[s]:
                jax.block_until_ready(segs[s][-1][1])
            if self._t0_shard[s] is not None:
                walls[s] = time.perf_counter() - self._t0_shard[s]
        self._collect_merge(plan["final_sel"])
        out_map = {}
        for s in range(S):
            for win_idx, outs in segs[s]:
                h = np.asarray(outs)   # (kseg, NROWS, 128)
                for i, w in enumerate(win_idx):
                    out_map[(w, s)] = h[i]
        return out_map, walls

    def _unpack_outputs(self, cols, placements, cnts, K, out_map):
        """Async collect: byte-identical to the lockstep fetch loop,
        reading per-(window, shard) output planes from `out_map` instead
        of the stacked all_gather array. Raises at the first errored
        cell in (w, s) order — the same error surface as lockstep
        (module docstring)."""
        HR = SQ.hdr_rows(self.local_cfg)
        n = len(cols["act"])
        host = {k: np.zeros(n, dt) for k, dt in
                (("ok", bool), ("cap_reject", bool),
                 ("append", bool), ("residual", np.int64),
                 ("nfill", np.int64), ("prev_oid", np.int64))}
        groups = {}
        mets = np.zeros(SQ.N_METRICS, np.int64)
        hists = np.zeros((SQ.N_HIST, SQ.N_HIST_BUCKETS), np.int64)
        for w in range(K):
            for s in range(self.shards):
                cnt = int(cnts[w, s])
                if not cnt:
                    continue
                cell = out_map[(w, s)]
                res = SQ.unpack_hdr(self.local_cfg, cell[:HR], cnt)
                if res["err"] != SQ.LERR_OK:
                    raise LaneEngineError(res["err"])
                ft = res["fill_total"]
                gr = cell[HR:HR + 5 * (-(-max(ft, 1) // 128))]
                groups[(w, s)] = (res, SQ.unpack_fills(gr, ft),
                                  np.concatenate(
                                      ([0], np.cumsum(res["nfill"]))))
                mets += res["metrics"]
                hists += res["hist"]
                self._hist_shard[s] += res["hist"]
                self.pos_probe_tiles += res["pos_tiles"]
        self._metrics += mets
        self._hist += hists
        fills_parts = []
        for k, w, s, p in placements:
            res, fills_ws, off = groups[(w, s)]
            for key in host:
                host[key][k] = res[key][p]
            if res["nfill"][p]:
                fills_parts.append(fills_ws[:, off[p]:off[p + 1]])
        fills = (np.concatenate(fills_parts, axis=1) if fills_parts
                 else np.zeros((4, 0), np.int64))
        return host, fills

    def _update_wall_rates(self, walls, busy) -> None:
        """Fold measured per-chip walls into the per-shard cost-rate
        EWMA (wall_feed=True): a shard whose wall exceeds its planned
        busy share is genuinely slower (thermals, host contention), so
        its lanes weigh more in the rebalancer. Bytes are unaffected —
        placement only moves work, never changes MatchOut."""
        act = (busy > 0) & (walls > 0)
        if int(act.sum()) < 2:
            return
        r = ((walls[act] / walls[act].mean())
             / (busy[act] / busy[act].mean()))
        rate = np.ones(self.shards, np.float64)
        rate[act] = np.clip(r, WALL_RATE_MIN, WALL_RATE_MAX)
        self._shard_rate = np.clip(
            WALL_RATE_ALPHA * rate
            + (1.0 - WALL_RATE_ALPHA) * self._shard_rate,
            WALL_RATE_MIN, WALL_RATE_MAX)

    def _publish_shard_telemetry_async(self, walls, occ,
                                       disp_wall: float) -> None:
        """Async-mode telemetry: REAL measured per-chip walls feed the
        device_shard{N} histograms (replacing the lockstep
        occupancy-weighted split approximation), plus the deterministic
        stall-schedule gauges and the H2D overlap fraction."""
        self._occ_shard += occ
        reg = self.telemetry
        reg.gauge("shard_count", "mesh shard count").set(self.shards)
        reg.counter("shard_migrations_total",
                    "lane slots moved by elastic placement"
                    ).set(self._migrations)
        reg.counter("shard_rebalances_total",
                    "between-batch rebalance events"
                    ).set(self._rebalances)
        tot = int(self._occ_shard.sum())
        if tot:
            reg.gauge(
                "shard_imbalance",
                "max/mean per-shard cumulative occupancy").set(
                round(float(self._occ_shard.max())
                      * self.shards / tot, 4))
        for s in range(self.shards):
            reg.gauge(f"shard{s}_occupancy",
                      "cumulative messages executed on shard").set(
                int(self._occ_shard[s]))
            if int(occ[s]) and walls[s] > 0:
                reg.latency(
                    f"device_shard{s}",
                    "measured per-chip dispatch wall").observe(
                    float(walls[s]), n=int(occ[s]))
        if self._sim_T_async > 0:
            tot_busy = float(self._sim_busy.sum())
            reg.gauge(
                "chip_stall_frac",
                "stall fraction of the async dispatch schedule "
                "(deterministic, weighted-cost)").set(round(
                    1.0 - tot_busy / (self.shards * self._sim_T_async),
                    4))
            for s in range(self.shards):
                reg.gauge(
                    f"shard{s}_stall_frac",
                    "per-chip stall fraction (async schedule)").set(
                    round(1.0 - float(self._sim_busy[s])
                          / self._sim_T_async, 4))
        if self._sim_T_lock > 0:
            reg.gauge(
                "chip_stall_frac_lockstep",
                "stall fraction the lockstep schedule would incur on "
                "the same batches").set(round(
                    1.0 - float(self._sim_busy.sum())
                    / (self.shards * self._sim_T_lock), 4))
        if self._async_wall_total > 0:
            reg.gauge(
                "chip_msgs_per_sec",
                "messages per second of measured async dispatch wall"
                ).set(round(self._msgs_total / self._async_wall_total,
                            2))
        if self._h2d_total_s > 0:
            reg.gauge(
                "h2d_overlap_frac",
                "fraction of H2D staging time overlapped under "
                "in-flight device compute").set(
                round(self._h2d_overlap_s / self._h2d_total_s, 4))

    def stall_stats(self) -> dict:
        """Bench/report surface for the deterministic stall schedule."""
        tot_busy = float(self._sim_busy.sum())
        S = self.shards
        return {
            "chip_stall_frac": (
                round(1.0 - tot_busy / (S * self._sim_T_async), 4)
                if self._sim_T_async > 0 else 0.0),
            "chip_stall_frac_lockstep": (
                round(1.0 - tot_busy / (S * self._sim_T_lock), 4)
                if self._sim_T_lock > 0 else 0.0),
            "h2d_overlap_frac": (
                round(self._h2d_overlap_s / self._h2d_total_s, 4)
                if self._h2d_total_s > 0 else 0.0),
            "chip_msgs_per_sec": (
                round(self._msgs_total / self._async_wall_total, 2)
                if self._async_wall_total > 0 else 0.0),
        }

    # -- elastic placement ---------------------------------------------

    def _home_shard(self, a: int) -> int:
        """Shard for a laneless balance message: the account's sticky
        home lane's CURRENT shard under the placement table, falling
        back to the static hash for accounts that never traded."""
        g = self._acct_lane.get(a)
        if g is None:
            return a % self.shards
        return int(self._perm[g]) // self.S_local

    def _note_load(self, cols) -> None:
        """Fold this batch's routed messages into the per-lane load
        EWMA. Matchable messages (BUY/SELL) weigh more: a taker can
        sweep up to max_fills makers, everything else is O(1)."""
        acts = cols["act"]
        laneful = ((acts == SQ.L_BUY) | (acts == SQ.L_SELL)
                   | (acts == SQ.L_CANCEL) | (acts == SQ.L_ADD_SYMBOL)
                   | (acts == SQ.L_PAYOUT_YES)
                   | (acts == SQ.L_PAYOUT_NO)
                   | (acts == SQ.L_REMOVE_SYMBOL))
        w = np.where((acts == SQ.L_BUY) | (acts == SQ.L_SELL),
                     MATCH_WORK_WEIGHT, 1.0)
        batch = np.bincount(
            cols["lane"][laneful].astype(np.int64),
            weights=w[laneful], minlength=self.cfg.lanes)
        if self.wall_feed and self.dispatch == "async":
            # measured per-chip walls feed the rebalancer: scale each
            # lane's weight by its CURRENT shard's cost rate so lanes
            # on genuinely-slow chips look hotter than their raw count
            batch = batch * self._shard_rate[
                (self._perm // self.S_local).astype(np.int64)]
        self._lane_load = (LOAD_EWMA_ALPHA * batch
                           + (1.0 - LOAD_EWMA_ALPHA) * self._lane_load)

    def _maybe_rebalance(self) -> None:
        if not self.rebalance or self.shards == 1:
            return
        new = plan_rebalance(self._lane_load, self._perm, self.shards,
                             threshold=self.rebalance_threshold)
        if new is None:
            return
        with self.timer.phase("migrate_s"):
            moved = self._migrate(new)
        if moved:
            self._rebalances += 1
            self._migrations += moved

    def _migrate(self, new_perm) -> int:
        """Permute the sharded lane axis of the state pytree to the new
        placement. Lane state moves WHOLESALE through the engine's
        canonical codec (export_canonical / import_canonical per
        shard): books, per-lane seq counters, and the lane-keyed
        position hash are re-keyed for the destination shard's local
        lane stride, while the replicated balance planes are untouched
        — so the migrated mesh state replays byte-identically.
        Returns the number of lanes that changed slot."""
        old_perm = self._perm
        moved = int((new_perm != old_perm).sum())
        if not moved:
            return 0
        # async mode: rebalancing only runs between batches, where the
        # per-shard queues are drained and every shard's replicated
        # planes are identical — gather to the stacked lockstep layout,
        # permute through the canonical codec, split back out
        async_mode = (self.dispatch == "async"
                      and self._shard_states is not None)
        if async_mode:
            self.state = self._gather_state_async()
        Sl, A = self.S_local, self.local_cfg.accounts
        host = {k: np.asarray(v) for k, v in self.state.items()}
        canons = []
        for s in range(self.shards):
            loc = {k: (v if k in self._REPL_KEYS
                       else v.reshape(self.shards, -1, v.shape[-1])[s])
                   for k, v in host.items()}
            canons.append(SQ.export_canonical(self.local_cfg, loc))
        # inverse of the NEW table: which global lane lands in slot g
        inv_new = np.empty_like(new_perm)
        inv_new[new_perm] = np.arange(len(new_perm),
                                      dtype=new_perm.dtype)
        parts = []
        for s in range(self.shards):
            src = []   # (old_shard, old_row) feeding each local row
            for r in range(Sl):
                g = int(inv_new[s * Sl + r])
                o = int(old_perm[g])
                src.append((o // Sl, o % Sl))
            tgt = dict(canons[0])   # replicated planes from shard 0
            for key in ("slot_oid", "slot_aid", "slot_price",
                        "slot_size", "slot_seq", "slot_used"):
                tgt[key] = np.stack(
                    [canons[ss][key][rr] for ss, rr in src])
            tgt["seq"] = np.stack(
                [canons[ss]["seq"][rr] for ss, rr in src])
            tgt["book_exists"] = np.stack(
                [canons[ss]["book_exists"][rr] for ss, rr in src])
            for key in ("pos_amt", "pos_avail"):
                tgt[key] = np.stack(
                    [canons[ss][key].reshape(Sl, A)[rr]
                     for ss, rr in src]).reshape(-1)
            parts.append(SQ.import_canonical(self.local_cfg, tgt))
        state = {}
        for k in host:
            if k in self._REPL_KEYS:
                state[k] = parts[0][k]
            else:
                state[k] = jnp.concatenate(
                    [parts[s][k] for s in range(self.shards)], axis=0)
        if async_mode:
            self._split_state_async(
                {k: np.asarray(v) for k, v in state.items()})
            self.state = None
        else:
            self.state = state
        self._perm = new_perm
        return moved

    # -- per-shard telemetry -------------------------------------------

    def _publish_shard_telemetry(self, disp_wall: float, occ) -> None:
        """Per-shard straggler attribution. The mesh scan is lockstep
        (one shard_map dispatch), so per-chip walls are not separately
        measurable from the host — attribution charges each shard an
        occupancy-weighted share of the batch's dispatch wall, the
        psum-mergeable convention the on-device histogram counters
        already use. shard_imbalance = cumulative max/mean per-shard
        occupancy (1.0 == perfectly balanced)."""
        self._occ_shard += occ
        reg = self.telemetry
        reg.gauge("shard_count", "mesh shard count").set(self.shards)
        reg.counter("shard_migrations_total",
                    "lane slots moved by elastic placement"
                    ).set(self._migrations)
        reg.counter("shard_rebalances_total",
                    "between-batch rebalance events"
                    ).set(self._rebalances)
        tot = int(self._occ_shard.sum())
        if tot:
            reg.gauge(
                "shard_imbalance",
                "max/mean per-shard cumulative occupancy").set(
                round(float(self._occ_shard.max())
                      * self.shards / tot, 4))
        btot = int(occ.sum())
        for s in range(self.shards):
            reg.gauge(f"shard{s}_occupancy",
                      "cumulative messages executed on shard").set(
                int(self._occ_shard[s]))
            if btot and int(occ[s]):
                reg.latency(
                    f"device_shard{s}",
                    "occupancy-weighted device wall share").observe(
                    disp_wall * float(occ[s]) / btot, n=int(occ[s]))

    def shard_stats(self) -> dict:
        """Bench/report surface: per-shard occupancy + imbalance."""
        tot = int(self._occ_shard.sum())
        return {
            "shards": self.shards,
            "occupancy": self._occ_shard.tolist(),
            "imbalance": (round(float(self._occ_shard.max())
                                * self.shards / tot, 4)
                          if tot else 0.0),
            "migrations": self._migrations,
            "rebalances": self._rebalances,
        }

    # -- the SeqSession metric surface ---------------------------------

    def histograms(self) -> Dict[str, list]:
        out = {name: self._hist[i].tolist()
               for i, name in enumerate(SQ.HIST_NAMES)}
        for s in range(self.shards):
            for i, name in enumerate(SQ.HIST_NAMES):
                out[f"{name}_shard{s}"] = self._hist_shard[s][i].tolist()
        self.telemetry.publish_histograms(out)
        return out

    def metrics(self) -> Dict[str, int]:
        counters = dict(zip(SQ.METRIC_NAMES, self._metrics.tolist()))
        counters["shard_migrations"] = self._migrations
        counters["shard_rebalances"] = self._rebalances
        tot = int(self._occ_shard.sum())
        if tot:
            counters["shard_imbalance"] = round(
                float(self._occ_shard.max()) * self.shards / tot, 4)
        self._publish(counters)
        return counters

    def export_canonical_global(self) -> dict:
        """Stitch the per-shard canonical exports back into ONE
        global-cfg canonical dict through the inverse placement table.
        Every _run fully drains before returning (async submit queues
        never span host batches), so this is always a quiescent
        drain-to-barrier snapshot — a checkpoint landing between
        batches sees exactly the serial-session state."""
        Sl, A = self.S_local, self.local_cfg.accounts
        if self.dispatch == "async":
            host = self._gather_state_async()
        else:
            host = {k: np.asarray(v) for k, v in self.state.items()}
        canons = []
        for s in range(self.shards):
            loc = {k: (v if k in self._REPL_KEYS
                       else v.reshape(self.shards, -1, v.shape[-1])[s])
                   for k, v in host.items()}
            canons.append(SQ.export_canonical(self.local_cfg, loc))
        where = []   # global lane g -> (shard, local row)
        for g in range(self.cfg.lanes):
            slot = int(self._perm[g])
            where.append((slot // Sl, slot % Sl))
        gl = {}
        for key in ("slot_oid", "slot_aid", "slot_price", "slot_size",
                    "slot_seq", "slot_used"):
            gl[key] = np.stack([canons[ss][key][rr] for ss, rr in where])
        gl["seq"] = np.stack([canons[ss]["seq"][rr] for ss, rr in where])
        gl["book_exists"] = np.stack(
            [canons[ss]["book_exists"][rr] for ss, rr in where])
        for key in ("pos_amt", "pos_avail"):
            gl[key] = np.stack(
                [canons[ss][key].reshape(Sl, A)[rr]
                 for ss, rr in where]).reshape(-1)
        # replicated planes are identical across shards at batch
        # boundaries (psum merge / _collect_merge) — take shard 0
        gl["bal"] = canons[0]["bal"]
        gl["bal_used"] = canons[0]["bal_used"]
        gl["err"] = np.int32(max(int(c["err"]) for c in canons))
        gl["metrics"] = None
        return gl

    def export_state(self):
        """Oracle-comparable host dict view, both dispatch modes: the
        stitched global canon through SeqSession's shared mapping."""
        return self._canon_to_export(self.export_canonical_global())


def shard_proof(events: int = 4000, shard_counts=(1, 2, 4)) -> dict:
    """The proof chip_smoke.py's four-chip phase runs (and
    tests/test_seqmesh.py rehearses on the virtual CPU mesh): the
    zipf-hot stream (seed 0, 8 symbols x 128 accounts x 128 slots, VMEM
    books) through SeqMeshSession at every shard count, byte-compared
    with the scalar fixed-mode oracle. It is fed in slices of 500
    because rebalancing happens between process_wire calls only, and
    above one shard migrations are REQUIRED — a placement that never
    moved proves nothing about _migrate. At the top count every
    shard's state must sit on a device of its own (async dispatch is
    parallel only then), and a lockstep control run must produce the
    same bytes. Raises AssertionError on the first check that fails."""
    from kme_tpu.oracle import OracleEngine
    from kme_tpu.workload import zipf_hot_stream

    top = max(shard_counts)
    if len(jax.devices()) < top:
        raise RuntimeError(
            f"shard_proof needs {top} devices, found "
            f"{len(jax.devices())} (a virtual CPU mesh: XLA_FLAGS="
            f"--xla_force_host_platform_device_count={top})")
    msgs = zipf_hot_stream(events, num_symbols=8, num_accounts=128,
                           seed=0)
    oracle = OracleEngine("fixed", book_slots=128, max_fills=16)
    want = [r.wire() for m in msgs for r in oracle.process(m.copy())]
    cfg = SQ.SeqConfig(lanes=8, slots=128, accounts=128, max_fills=16)

    def run(shards, dispatch):
        ses = SeqMeshSession(cfg, shards, dispatch=dispatch)
        got = []
        for lo in range(0, len(msgs), 500):
            for per in ses.process_wire(msgs[lo:lo + 500]):
                got.extend(per)
        if got != want:
            raise AssertionError(
                f"shards={shards} dispatch={ses.dispatch}: MatchOut "
                f"diverged from the single-chip oracle "
                f"({sum(a != b for a, b in zip(got, want))} lines + "
                f"{abs(len(got) - len(want))} length delta)")
        return ses

    migrations = []
    top_ses = None
    for shards in shard_counts:
        ses = run(shards, "auto")
        if ses.dispatch != "async":
            raise AssertionError(
                f"shards={shards}: dispatch resolved to "
                f"{ses.dispatch!r}, not async")
        moved = ses.shard_stats()["migrations"]
        if shards > 1 and moved <= 0:
            raise AssertionError(
                f"shards={shards}: no migrations on the skewed stream "
                f"— the elastic planner never fired")
        migrations.append(moved)
        if shards == top:
            top_ses = ses
    placed = [sorted(str(d) for d in st["err"].devices())
              for st in top_ses._shard_states]
    if (any(len(p) != 1 for p in placed)
            or len({p[0] for p in placed}) != top):
        raise AssertionError(
            f"shards={top}: per-shard states are not on {top} distinct "
            f"devices: {placed}")
    run(top, "lockstep")
    return {"backend": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "events": len(msgs), "shard_counts": list(shard_counts),
            "dispatch": top_ses.dispatch, "parity": "byte-exact",
            "migrations": migrations,
            "shard_devices": [p[0] for p in placed],
            "lockstep_parity": "byte-exact"}
