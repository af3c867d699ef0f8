"""LaneSession: the host half of the throughput engine.

Plans a message batch (runtime/sequencer.py), packs each scan segment
into COMPACT (M,) message vectors with (t, lane) schedule coordinates,
dispatches the device chunks + barrier ops fully asynchronously, then
fetches the compacted outputs once and reconstructs the byte-exact
record stream in arrival order — the same IN / fills / OUT contract the
reference forwards per message (KProcessor.java:97, 272-273, 124).

I/O design (round 2): the dense (T, S, E) grids are >95% padding, and
every separate transfer is a blocking round trip. So the session never
moves a grid: inputs are scattered to (T, S) on device, fill outputs
come back as ONE packed (4, F) buffer per segment, per-message results
as (M,) vectors, and every dispatch is queued without host sync — the
sticky error code in the device state is checked once at the end.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

import numpy as np

import kme_tpu._jaxsetup  # noqa: F401
import jax

from kme_tpu import opcodes as op
from kme_tpu.engine import lanes as L
from kme_tpu.runtime.sequencer import Schedule, make_scheduler
from kme_tpu.telemetry import PhaseTimer, Registry
from kme_tpu import wire as W
from kme_tpu.wire import OrderMsg, OutRecord

_LERR_NAMES = {
    L.LERR_FILLBUF_FULL: "session fill log exhausted (fill_buffer knob)",
}


def _device_reason(lane_act: int, cap: bool) -> int:
    """REJ_* code for a device not-ok result: the capacity flag wins,
    else classify by the internal lane act."""
    if cap:
        return W.REJ_CAPACITY
    if lane_act in (L.L_BUY, L.L_SELL):
        return W.REJ_RISK
    if lane_act == L.L_CANCEL:
        return W.REJ_CANCEL
    return W.REJ_OTHER


class LaneEngineError(RuntimeError):
    def __init__(self, code: int) -> None:
        self.code = int(code)
        super().__init__(
            f"lane engine error: {_LERR_NAMES.get(self.code, self.code)}")


from kme_tpu.utils import pow2_bucket as _bucket


@dataclasses.dataclass
class _WindowRun:
    """A dispatched window: its compact device outputs + bookkeeping.

    `idx` are placement ROW ids into the schedule's columnar arrays,
    sorted by (step-in-window, lane) — the exact order the device
    appends fills to the persistent fill log, so host fill offsets are
    the running cumsum of nfill in row order across windows in dispatch
    order."""
    idx: np.ndarray           # placement rows, sorted by (step, lane)
    outs: dict                # device arrays (fetched lazily)
    host: dict = None         # np arrays after fetch
    offs: np.ndarray = None   # (M,) absolute fill-log offsets


class LaneSession:
    """Drop-in fixed-mode engine over the vmapped lane kernel.

    With shards > 1 the lane axis is sharded over a device mesh
    (kme_tpu/parallel/mesh.py); the output stream is bit-identical for
    any shard count — the determinism contract of SURVEY.md §5."""

    def __init__(self, cfg: L.LaneConfig, shards: int = 1,
                 width: int = 16) -> None:
        """width > 0 (single-device only) enables active-lane compaction:
        the scheduler caps each scan step at `width` messages and the
        device computes (T, width) message slots instead of (T, S) lanes
        — per-step work drops from O(S·(N+A)) to O(width·N). cfg.width,
        if set, wins over the argument; the sharded path is always
        full-width (GSPMD owns the lane axis there)."""
        W = cfg.width if cfg.width > 0 else width
        # at most one message per lane per step can ever be scheduled, so
        # wider-than-S slots would be permanently dead padding
        W = min(W, cfg.lanes)
        if shards > 1 or W < 0:
            W = 0
        self.cfg = cfg = dataclasses.replace(cfg, width=0, pos_dma=False)
        # device config: compaction reserves the last lane as the padding
        # scrap row, so the device state carries one extra lane. The
        # compact path keeps positions as planar i32 rows updated in
        # place by Pallas row-DMA (engine/lanes.py pos_dma) whenever the
        # row width tiles cleanly (accounts % 64 == 0).
        use_dma = W > 0 and (2 * cfg.accounts) % 128 == 0
        self.dev_cfg = (dataclasses.replace(cfg, lanes=cfg.lanes + 1,
                                            width=W, pos_dma=use_dma)
                        if W else cfg)
        self.shards = shards
        if shards > 1:
            from kme_tpu.parallel import mesh as M

            self.mesh = M.build_mesh(shards)
            self.state = M.shard_state(L.make_lane_state(cfg), self.mesh)
            self._settle = M.build_sharded_settle_jit(cfg, shards)
        else:
            self.mesh = None
            self.state = L.make_lane_state(self.dev_cfg)
            self._settle = jax.jit(L.build_barrier_ops(self.dev_cfg),
                                   donate_argnums=(0,))
        self.scheduler = make_scheduler(cfg.lanes, cfg.accounts, width=W)
        self.telemetry = Registry()
        self.timer = PhaseTimer(track="lanes")
        # the timer owns the dict: phase totals ACCUMULATE across batches
        self.phases = self.timer.totals
        # per-message REJ_* reason codes for the last processed batch
        # (np.uint8 (nmsg,), wire.REJ_NAMES) — read by the flight
        # recorder and the opt-in REJ annotation records
        self.last_reasons = None

    # ------------------------------------------------------------------

    def _chunk_fn(self, T: int, M: int):
        if self.shards == 1:
            return L.build_lane_chunk(self.dev_cfg, T, M)
        from kme_tpu.parallel import mesh as MM

        return MM.build_sharded_chunk_jit(self.cfg, self.shards, T, M)

    def _pack_window(self, cols: Dict[str, np.ndarray], widx: np.ndarray,
                     t0: int, T: int, M: int) -> Dict[str, np.ndarray]:
        n = len(widx)
        cb = {
            "t": np.full(M, T, np.int32),     # t >= T marks padding
            "lane": np.zeros(M, np.int32),
            "slot": np.zeros(M, np.int32),
            "act": np.zeros(M, np.int32),
            "oid": np.zeros(M, np.int64),
            "aid": np.zeros(M, np.int32),
            "price": np.zeros(M, np.int32),
            "size": np.zeros(M, np.int32),
        }
        cb["t"][:n] = cols["step"][widx] - t0
        cb["lane"][:n] = cols["lane"][widx]
        cb["slot"][:n] = cols["slot"][widx]
        cb["act"][:n] = cols["act"][widx]
        cb["oid"][:n] = cols["oid"][widx]
        cb["aid"][:n] = cols["aidx"][widx]
        cb["price"][:n] = cols["price"][widx]
        cb["size"][:n] = cols["size"][widx]
        return cb

    def _dispatch(self, sched: Schedule) -> tuple:
        """Queue every dispatch window + barrier asynchronously. Long
        segments are split into windows of <= cfg.window scan steps (the
        HBM bound for the per-step output grids); nothing syncs with the
        device here. Returns (window runs in dispatch order, barrier-ok
        device scalars by msg index)."""
        cols = sched.cols
        nseg = len(sched.segment_steps)
        # rows are appended in arrival order, so `segment` is sorted
        seg_bounds = np.searchsorted(cols["segment"], np.arange(nseg + 1))

        runs: List[_WindowRun] = []
        barrier_ok: Dict[int, object] = {}
        from kme_tpu.oracle import javalong as jl

        W = self.cfg.window
        for kind, idx in sched.program:
            if kind == "scan":
                lo, hi = int(seg_bounds[idx]), int(seg_bounds[idx + 1])
                height = sched.segment_steps[idx]
                order = lo + np.lexsort((cols["lane"][lo:hi],
                                         cols["step"][lo:hi]))
                sorted_steps = cols["step"][order]
                for w in range((height + W - 1) // W):
                    a = np.searchsorted(sorted_steps, w * W, "left")
                    b = np.searchsorted(sorted_steps, (w + 1) * W, "left")
                    widx = order[a:b]
                    T = _bucket(min(height - w * W, W), lo=self.cfg.steps)
                    M = _bucket(max(len(widx), 1))
                    cb = self._pack_window(cols, widx, w * W, T, M)
                    self.state, outs = self._chunk_fn(T, M)(self.state, cb)
                    runs.append(_WindowRun(widx, outs))
            else:
                b = sched.barriers[idx]
                self.state, ok = self._settle(
                    self.state, np.int32(b.lane),
                    np.int64(jl.jlong(b.credit_size)), np.int32(b.mode))
                barrier_ok[b.msg_index] = ok
        return runs, barrier_ok

    def _fetch(self, runs: List[_WindowRun]) -> np.ndarray:
        """One sync phase: start every device->host copy asynchronously,
        then materialize; check the sticky error; slice the used prefix
        of the persistent fill log and rewind it. Returns the packed
        (4, F_used) fill log [oid, aid, price, size]."""
        from kme_tpu.utils import async_prefetch

        for run in runs:
            async_prefetch(run.outs.values())
        base = 0
        for run in runs:
            # one (8, M) packed array per window — a single transfer
            # (chunk_compaction packs all per-message outputs + the
            # err/total scalars into it)
            p = np.asarray(run.outs["packed"])
            err = int(p[6, 0])
            if err != L.LERR_OK:
                raise LaneEngineError(err)
            host = {
                "ok": p[0] != 0,
                "residual": p[1],
                "append": p[2] != 0,
                "prev_oid": p[3],
                "cap_reject": p[4] != 0,
                "nfill": p[5],
                "nfill_total": p[7, 0],
            }
            run.host = host
            run.offs = base + np.cumsum(host["nfill"]) - host["nfill"]
            base += int(host["nfill_total"])
            run.outs = None
        if base:
            fills = np.asarray(self.state["fillbuf"][:, :base])
        else:
            fills = np.zeros((4, 0), np.int64)
        self.state = L.build_fill_reset(self.dev_cfg)(self.state)
        return fills

    # ------------------------------------------------------------------

    def process(self, msgs: Sequence[OrderMsg]) -> List[List[OutRecord]]:
        with self.timer.phase("plan_s"):
            sched = self.scheduler.plan(msgs)
        with self.timer.phase("dispatch_s"):
            runs, barrier_ok_dev = self._dispatch(sched)
        with self.timer.phase("fetch_s"):
            fills = self._fetch(runs)
        with self.timer.phase("recon_s"):
            return self._reconstruct(msgs, sched, runs, barrier_ok_dev,
                                     fills)

    def process_wire(self, msgs: Sequence[OrderMsg]) -> List[List[str]]:
        """Like process(), but returns the byte-exact `<key> <json>` wire
        lines (consumer.js:19 format) directly — no per-record Python
        objects. This is the serving/bench path; equivalence with
        process() is pinned by tests/test_lanes_engine.py."""
        with self.timer.phase("plan_s"):
            sched = self.scheduler.plan(msgs)
        with self.timer.phase("dispatch_s"):
            runs, barrier_ok_dev = self._dispatch(sched)
        with self.timer.phase("fetch_s"):
            fills = self._fetch(runs)
        with self.timer.phase("recon_s"):
            return self._reconstruct_wire(msgs, sched, runs, barrier_ok_dev,
                                          fills)

    def _reconstruct_wire(self, msgs, sched, runs, barrier_ok_dev, fills):
        idx_to_aid = self.scheduler.acct_of_idx()
        lane_to_sid = self.scheduler.sid_of_lane()
        barrier_ok = {i: bool(np.asarray(okd))
                      for i, okd in barrier_ok_dev.items()}
        cols = sched.cols
        nmsg = len(msgs)
        # Per-message scalar state, extracted in BULK (tolist() — numpy
        # scalar-by-scalar extraction dominates reconstruction otherwise).
        ok_of = [False] * nmsg
        nfill_of = [0] * nmsg
        off_of = [0] * nmsg
        resid_of = [0] * nmsg
        prev_of = [0] * nmsg
        append_of = [False] * nmsg
        act_of = [0] * nmsg
        lane_of = [0] * nmsg
        cap_of = [False] * nmsg
        for run in runs:
            n = len(run.idx)
            h = run.host
            mis = cols["msg_index"][run.idx].tolist()
            for name, dst in (("ok", ok_of), ("nfill", nfill_of),
                              ("residual", resid_of), ("prev_oid", prev_of),
                              ("append", append_of),
                              ("cap_reject", cap_of)):
                vals = h[name][:n].tolist()
                for k, mi in enumerate(mis):
                    dst[mi] = vals[k]
            offs = run.offs[:n].tolist()
            acts = cols["act"][run.idx].tolist()
            lanes_l = cols["lane"][run.idx].tolist()
            for k, mi in enumerate(mis):
                off_of[mi] = offs[k]
                act_of[mi] = acts[k]
                lane_of[mi] = lanes_l[k]
        f_oid, f_aid, f_price, f_size = (fills[c].tolist() for c in range(4))
        rejects = {r.msg_index for r in sched.host_rejects}
        barriers = {b.msg_index for b in sched.barriers}

        from kme_tpu.wire import order_json

        reasons = np.zeros(nmsg, np.uint8)
        out: List[List[str]] = []
        for i, m in enumerate(msgs):
            in_body = order_json(m.action, m.oid, m.aid, m.sid, m.price,
                                 m.size, m.next, m.prev)
            lines = [f'IN {in_body}']
            if i in rejects or (i in barriers and not barrier_ok[i]):
                reasons[i] = (W.REJ_UNROUTABLE if i in rejects
                              else W.REJ_BARRIER)
                lines.append('OUT ' + order_json(
                    op.REJECT, m.oid, m.aid, m.sid, m.price, m.size,
                    m.next, m.prev))
            elif i in barriers:
                lines.append(f'OUT {in_body}')
            else:
                lane_act = act_of[i]
                ok = ok_of[i]
                is_trade = lane_act in (L.L_BUY, L.L_SELL)
                if is_trade and ok:
                    sid = lane_to_sid[lane_of[i]]
                    is_buy = lane_act == L.L_BUY
                    mk_act = op.SOLD if is_buy else op.BOUGHT
                    tk_act = op.BOUGHT if is_buy else op.SOLD
                    o0 = off_of[i]
                    for e in range(nfill_of[i]):
                        moid = f_oid[o0 + e]
                        maid = idx_to_aid[f_aid[o0 + e]]
                        mprice = f_price[o0 + e]
                        fsz = f_size[o0 + e]
                        lines.append('OUT ' + order_json(
                            mk_act, moid, maid, sid, 0, fsz))
                        lines.append('OUT ' + order_json(
                            tk_act, m.oid, m.aid, sid, m.price - mprice,
                            fsz))
                    lines.append('OUT ' + order_json(
                        m.action, m.oid, m.aid, m.sid, m.price,
                        resid_of[i], m.next,
                        prev_of[i] if append_of[i] else m.prev))
                else:
                    if not ok:
                        reasons[i] = _device_reason(lane_act, cap_of[i])
                    lines.append('OUT ' + order_json(
                        m.action if ok else op.REJECT, m.oid, m.aid,
                        m.sid, m.price, m.size, m.next, m.prev))
            out.append(lines)
        self.last_reasons = reasons
        return out

    def _reconstruct(self, msgs, sched, runs, barrier_ok_dev, fills):
        idx_to_aid = self.scheduler.acct_of_idx()
        lane_to_sid = self.scheduler.sid_of_lane()
        barrier_ok = {i: bool(np.asarray(okd))
                      for i, okd in barrier_ok_dev.items()}

        # run + m-position of each device message within its window run
        cols = sched.cols
        run_of_msg = np.full(len(msgs), -1, np.int64)
        m_of_msg = np.zeros(len(msgs), np.int64)
        for ri, run in enumerate(runs):
            mi = cols["msg_index"][run.idx]
            run_of_msg[mi] = ri
            m_of_msg[mi] = np.arange(len(run.idx))
        rejects = {r.msg_index for r in sched.host_rejects}
        barriers_by_msg = {b.msg_index: b for b in sched.barriers}

        reasons = np.zeros(len(msgs), np.uint8)
        out: List[List[OutRecord]] = []
        for i, m in enumerate(msgs):
            recs = [OutRecord("IN", m.copy())]
            if i in rejects:
                reasons[i] = W.REJ_UNROUTABLE
                echo = m.copy()
                echo.action = op.REJECT
                recs.append(OutRecord("OUT", echo))
            elif i in barriers_by_msg:
                echo = m.copy()
                if not barrier_ok[i]:
                    reasons[i] = W.REJ_BARRIER
                    echo.action = op.REJECT
                recs.append(OutRecord("OUT", echo))
            else:
                run = runs[run_of_msg[i]]
                mm = int(m_of_msg[i])
                h = run.host
                row = run.idx[mm]
                lane_act = int(cols["act"][row])
                ok = bool(h["ok"][mm])
                is_trade = lane_act in (L.L_BUY, L.L_SELL)
                if is_trade and ok:
                    sid = lane_to_sid[int(cols["lane"][row])]
                    is_buy = lane_act == L.L_BUY
                    o0 = int(run.offs[mm])
                    for e in range(int(h["nfill"][mm])):
                        moid = int(fills[0, o0 + e])
                        maid = idx_to_aid[int(fills[1, o0 + e])]
                        mprice = int(fills[2, o0 + e])
                        fsz = int(fills[3, o0 + e])
                        recs.append(OutRecord("OUT", OrderMsg(
                            action=op.SOLD if is_buy else op.BOUGHT,
                            oid=moid, aid=maid, sid=sid, price=0, size=fsz)))
                        recs.append(OutRecord("OUT", OrderMsg(
                            action=op.BOUGHT if is_buy else op.SOLD,
                            oid=m.oid, aid=m.aid, sid=sid,
                            price=m.price - mprice, size=fsz)))
                echo = m.copy()
                if not ok:
                    reasons[i] = _device_reason(
                        lane_act, bool(h["cap_reject"][mm]))
                    echo.action = op.REJECT
                if is_trade and ok:
                    echo.size = int(h["residual"][mm])
                    if bool(h["append"][mm]):
                        echo.prev = int(h["prev_oid"][mm])
                recs.append(OutRecord("OUT", echo))
            out.append(recs)
        self.last_reasons = reasons
        return out

    # ------------------------------------------------------------------

    def metrics(self) -> Dict[str, int]:
        """On-device observability: cumulative counters (accumulated in
        the scan carry, psum-merged under sharding) + point-in-time
        gauges. One tiny device reduce per call — never per message."""
        m = self.state["metrics"]
        if isinstance(m, tuple):  # compact-mode scalar-tuple carry:
            m = jax.numpy.stack(m)  # stack on device, ONE transfer
        counters = dict(zip(L.METRIC_NAMES, np.asarray(m).tolist()))
        gauges = L.build_gauges(self.dev_cfg)(self.state)
        counters.update({k: int(np.asarray(v)) for k, v in gauges.items()})
        self._publish(counters)
        return counters

    def histograms(self) -> Dict[str, list]:
        """In-kernel distribution histograms (power-of-two buckets), read
        back with the same one-transfer discipline as metrics()."""
        h = self.state["hist"]
        if isinstance(h, tuple):  # compact-mode per-hist rows
            h = jax.numpy.stack(h)  # stack on device, ONE transfer
        rows = np.asarray(h)
        out = {name: rows[i].tolist() for i, name in enumerate(L.HIST_NAMES)}
        self.telemetry.publish_histograms(out)
        return out

    def _publish(self, counters: Dict[str, int]) -> None:
        self.telemetry.publish_counters(
            {k: counters[k] for k in L.METRIC_NAMES})
        self.telemetry.publish_gauges(
            {k: v for k, v in counters.items()
             if k not in L.METRIC_NAMES})

    def export_state(self) -> Dict[str, dict]:
        """Host dict view comparable to the oracle's stores (fixed mode)."""
        s = jax.tree.map(np.asarray, self.state)
        idx_to_aid = self.scheduler.acct_of_idx()
        lane_to_sid = self.scheduler.sid_of_lane()
        balances = {idx_to_aid[i]: int(s["bal"][i])
                    for i in range(len(idx_to_aid)) if s["bal_used"][i]}
        positions = {}
        orders = {}
        S, _, N = s["slot_oid"].shape
        for k in ("pos_amt", "pos_avail"):
            if self.dev_cfg.pos_dma:  # planar lo/hi i32 rows -> s64
                from kme_tpu.ops.rowdma import unpack64_np

                s[k] = unpack64_np(s[k], S)
            else:
                s[k] = s[k].reshape(S, -1)  # flat (S*A,) device layout
        # a position exists iff amt != 0 (no-used-flag invariant)
        s["pos_used"] = s["pos_amt"] != 0
        for lane in range(S):
            sid = lane_to_sid.get(lane)
            if sid is None:
                continue
            for a in range(len(idx_to_aid)):
                if s["pos_used"][lane, a]:
                    positions[(idx_to_aid[a], sid)] = (
                        int(s["pos_amt"][lane, a]), int(s["pos_avail"][lane, a]))
            for side in range(2):
                for n in range(N):
                    if s["slot_used"][lane, side, n]:
                        orders[int(s["slot_oid"][lane, side, n])] = {
                            "aid": idx_to_aid[int(s["slot_aid"][lane, side, n])],
                            "sid": sid,
                            "price": int(s["slot_price"][lane, side, n]),
                            "size": int(s["slot_size"][lane, side, n]),
                            "is_buy": side == 0,
                        }
        books = {sid: True for sid, lane in self.scheduler.sid_lane.items()
                 if s["book_exists"][lane]}
        return {"balances": balances, "positions": positions,
                "orders": orders, "books": books}
