"""SeqSession: host half of the sequential mega-kernel engine.

There is no scheduler: the kernel processes messages strictly
sequentially (engine/seq.py), so planning reduces to ID ROUTING — dense
aid/sid maps, oid -> lane routing for cancels, and host-resolved rejects
for messages the device cannot act on (unknown-oid cancels,
negative-sid ADD_SYMBOL, unmapped payout/remove), state-free in the
reference too.
Barriers (PAYOUT / REMOVE_SYMBOL) are ordinary device messages here
(act codes 7/8/9), not separate settle calls.

I/O design: ONE packed (rows, 128) i32 output plane per kernel call,
all calls dispatched before any fetch, fetches started concurrently —
every separate np.asarray is a blocking device->host round trip.
"""

from __future__ import annotations

import heapq
from time import perf_counter_ns
from typing import Dict, List

import numpy as np

import kme_tpu._jaxsetup  # noqa: F401

from kme_tpu import opcodes as op
from kme_tpu.engine import seq as SQ
from kme_tpu.telemetry import PhaseTimer, Registry
from kme_tpu.wire import (EnvelopeError, OrderMsg, OutRecord, WireBatch,
                          order_json, reject_reason_codes)

# what LaneEngineError says for each sticky code of engine/seq.py
_LERR_NAMES = {
    SQ.LERR_FILLBUF_FULL: "a call's fill log exhausted (SeqConfig.fill_cap)",
    SQ.LERR_HASH_FULL:
        "java-mode position hash exhausted (SeqConfig.pos_cap)",
    SQ.LERR_JAVA_DOMAIN:
        "java mode: price/size outside the device domain (the reference "
        "runs unvalidated fields; this stream needs the native engine)",
    SQ.LERR_JAVA_CAP:
        "java mode: device capacity exceeded (reference stores are "
        "unbounded -- raise slots/max_fills or use the native engine)",
}


class LaneEngineError(RuntimeError):
    def __init__(self, code: int) -> None:
        self.code = int(code)
        super().__init__(
            f"lane engine error: {_LERR_NAMES.get(self.code, self.code)}")


class CapacityError(RuntimeError):
    """The workload exceeds a static device capacity (symbols, accounts)."""


def sorted_routes(keys: np.ndarray, vals: np.ndarray):
    """The oid -> sid routes a snapshot carries (runtime/checkpoint.py):
    two int64 arrays, keys ascending and values in the keys' order — a
    map's own order is not reproducible, and two snapshots of one state
    must carry one digest."""
    order = np.argsort(keys)
    return keys[order], vals[order]


def _dict_routes(d: Dict[int, int]):
    return sorted_routes(np.fromiter(d.keys(), np.int64, len(d)),
                         np.fromiter(d.values(), np.int64, len(d)))


class DictRoutes:
    """The snapshot's view of a Python router's `oid_sid` dict (the
    native twin answers the same calls from its C++ map)."""

    oid_sid: Dict[int, int]

    def routes_arrays(self):
        """`oid_sid` as a snapshot carries it (sorted_routes)."""
        return _dict_routes(self.oid_sid)

    def routes_capture(self):
        """routes_arrays() in two halves: a copy of `oid_sid` as it
        stands, made here, and -> a call that makes the two arrays of
        that copy, for any thread at any later time (a snapshot's
        writer, while the router routes on)."""
        d = dict(self.oid_sid)
        return lambda: _dict_routes(d)

    def import_routes(self, keys, vals) -> None:
        self.oid_sid = dict(zip(np.asarray(keys).tolist(),
                                np.asarray(vals).tolist()))


_TRADE_ACTS = {op.BUY: SQ.L_BUY, op.SELL: SQ.L_SELL}


# java mode: the kernel's `valid` gate on a trade (engine/seq.py, TRADE
# section): 0 <= price < 126 and size > 0
_JAVA_PRICE_END = 126


class UnsupportedJavaOp(RuntimeError):
    """The java-compat DEVICE surface excludes barriers and negative-sid
    symbols (dead or broken reference paths — Q3-Q6 and the ±sid book
    cross-coupling); streams containing them belong on the native/oracle
    engines (COMPAT.md)."""


# the routers' cumulative counts as a plan returns, in the order both
# export them (SeqRouter.stats / kme_router_stats); `lanes_bound` is a
# level, the rest only grow. `route_purge_ns` / `route_purge_n`: time
# spent dropping a wiped symbol's oid routes, and how many such purges;
# `routes_made`: trades that wrote a route; `cancels_routed` /
# `cancels_host_rejected`: cancels that found a route and went to the
# device / found none; `plans`: route() calls, the stamp a route made
# by this plan carries (SeqRouter.drop)
ROUTER_STATS = ("symbols_listed", "symbols_settled", "lanes_released",
                "lanes_reused", "unlisted_rejects", "route_purge_ns",
                "route_purge_n", "routes_made", "cancels_routed",
                "cancels_host_rejected", "plans", "lanes_bound")


class SeqRouter(DictRoutes):
    """Arrival-order ID routing (no conflict analysis): the id spaces
    and the host-reject edge semantics. compat='java' additionally
    emits the raw Java-long aid/sid columns and the Q1 merged-book flag
    the kernel needs, and REFUSES the opcodes outside the java device
    surface.

    Fixed mode, the symbol lifecycle: a lane is bound to a symbol id by
    the first routed ADD_SYMBOL of it and goes back to the pool when an
    accepted PAYOUT has emptied it (the kernel wipes the books, clears
    `bex` and zeroes the lane's positions), so `S` is how many symbols
    are listed AT A TIME. Whether the device will accept a barrier is
    known here, at route time: it accepts one exactly where the book
    exists, and every operation that makes or unmakes a book passes
    through this router in order — `delisted` holds the bound ids whose
    book a REMOVE_SYMBOL took away (their positions stay, so the lane
    does). A new id takes the LOWEST free lane, a function of
    `sid_lane` alone, so a replay and a restore choose as the first run
    did. A trade, cancel or barrier naming an id that holds no lane is
    host-rejected and takes none (the reference rejects each).

    Fixed mode, an order's route: written when its trade is routed,
    gone when the order is known to have left the book (`drop`, called
    by the session as it collects a batch) or when its symbol is wiped
    (`_purge`). The router plans ahead of the collect, so a route
    carries the ordinal of the plan that wrote it (`oid_plan`; a route
    that was imported carries none and reads 0): a drop learned from
    plan k leaves alone what a later plan wrote for the same oid. java
    mode keeps every route and no stamps."""

    def __init__(self, num_lanes: int, num_accounts: int,
                 compat: str = "fixed") -> None:
        self.S = num_lanes
        self.A = num_accounts
        self.compat = compat
        self.aid_idx: Dict[int, int] = {}
        self.oid_sid = {}
        self.delisted: set = set()
        self.counts = dict.fromkeys(ROUTER_STATS[:-1], 0)
        self.routes_dropped = 0
        self.sid_lane = {}

    @property
    def oid_sid(self) -> Dict[int, int]:
        return self._oid_sid

    @oid_sid.setter
    def oid_sid(self, d: Dict[int, int]) -> None:
        """A wholesale import (construction, checkpoint restore): no
        plan is in flight, so the routes carry no stamp."""
        self._oid_sid = d
        self.oid_plan: Dict[int, int] = {}

    @property
    def sid_lane(self) -> Dict[int, int]:
        return self._sid_lane

    @sid_lane.setter
    def sid_lane(self, d: Dict[int, int]) -> None:
        """A wholesale import (construction, checkpoint restore): the
        pool of free lanes is rebuilt from the map — every lane below
        the highest bound one that no id holds, and all above it."""
        self._sid_lane = dict(d)
        self.delisted = set()
        self._hw = max(self._sid_lane.values(), default=-1) + 1
        self._free = sorted(set(range(self._hw))
                            - set(self._sid_lane.values()))

    def capture(self):
        """The router as a seq snapshot carries it, in two halves:
        copies of its three id maps as they stand, made here, and -> a
        call that gives (`aid_idx`'s items sorted, `sid_lane`'s items
        sorted, *routes_arrays()) of those copies, for any thread at
        any later time."""
        acc, sym = dict(self.aid_idx), dict(self.sid_lane)
        routes = self.routes_capture()
        return lambda: (sorted(acc.items()), sorted(sym.items()),
                        *routes())

    def set_listed(self, book_exists) -> None:
        """After an import of `sid_lane`: the bound ids whose lane holds
        no book (`book_exists[lane]` of the restored device state) are
        the delisted ones."""
        self.delisted = {sid for sid, lane in self.sid_lane.items()
                         if not book_exists[lane]}

    def stats(self) -> tuple:
        """ROUTER_STATS, cumulative."""
        return (*self.counts.values(), len(self._sid_lane))

    def _acct(self, aid: int) -> int:
        idx = self.aid_idx.get(aid)
        if idx is None:
            if len(self.aid_idx) >= self.A:
                raise CapacityError(
                    f"account capacity {self.A} exhausted (aid={aid})")
            idx = len(self.aid_idx)
            self.aid_idx[aid] = idx
        return idx

    def _lane(self, sid: int) -> int:
        """The lane of `sid`, binding the lowest free one to a new id."""
        lane = self._sid_lane.get(sid)
        if lane is None:
            if len(self._sid_lane) >= self.S:
                raise CapacityError(
                    f"symbol capacity {self.S} exhausted (sid={sid})")
            if self._free:
                lane = heapq.heappop(self._free)
                self.counts["lanes_reused"] += 1
            else:
                lane = self._hw
                self._hw += 1
            self._sid_lane[sid] = lane
        return lane

    def _purge(self, sid: int) -> None:
        """Resting-oid routes die with the wipe."""
        t0 = perf_counter_ns()
        dead = [o for o, s2 in self.oid_sid.items() if s2 == sid]
        for o in dead:
            del self.oid_sid[o]
            self.oid_plan.pop(o, None)
        self.counts["route_purge_ns"] += perf_counter_ns() - t0
        self.counts["route_purge_n"] += 1

    def n_routes(self) -> int:
        return len(self.oid_sid)

    def drop(self, oids, alive, plan: int) -> tuple:
        """What the collect of plan `plan` learned, in message order:
        `oids[j]` rests on the book after its event (`alive[j]`: a
        trade that rested) or has left it (a trade refused or filled
        at once, an accepted cancel, a maker a sweep emptied). The
        last event of an oid decides; an oid that ends dead loses its
        route unless a plan after `plan` wrote it.
        -> (routes dropped so far, routes held)."""
        dead = set()
        for o, a in zip(np.asarray(oids).tolist(),
                        np.asarray(alive).tolist()):
            if a:
                dead.discard(o)
            else:
                dead.add(o)
        for o in dead:
            if o in self.oid_sid and self.oid_plan.get(o, 0) <= plan:
                del self.oid_sid[o]
                self.oid_plan.pop(o, None)
                self.routes_dropped += 1
        return self.routes_dropped, self.n_routes()

    def drop_batch(self, cols, host, fills, plan: int) -> tuple:
        """drop(), of what one fetched batch says (route_events)."""
        return self.drop(*route_events(cols, host, fills), plan)

    def acct_of_idx(self) -> List[int]:
        out = [0] * len(self.aid_idx)
        for aid, idx in self.aid_idx.items():
            out[idx] = aid
        return out

    def sid_of_lane(self) -> Dict[int, int]:
        return {lane: sid for sid, lane in self.sid_lane.items()}

    def route(self, msgs):
        """-> (cols dict incl. msg_index, host_reject msg indices)."""
        from kme_tpu.oracle import javalong as jl

        if isinstance(msgs, WireBatch):
            msgs = msgs.msgs()
        java = self.compat == "java"
        cols = {k: [] for k in ("msg_index", "act", "aid", "price",
                                "size", "lane", "oid", "aid_raw",
                                "sid_raw", "flags")}
        host_rejects = set()

        def emit(i, act, aidx, lane, m, oid, aid=0, sid=0):
            cols["msg_index"].append(i)
            cols["act"].append(act)
            cols["aid"].append(aidx)
            cols["price"].append(m.price)
            cols["size"].append(m.size)
            cols["lane"].append(lane)
            cols["oid"].append(oid)
            if java:
                cols["aid_raw"].append(aid)
                cols["sid_raw"].append(sid)
                cols["flags"].append(1 if sid == 0 else 0)

        def unlisted(i):
            host_rejects.add(i)
            self.counts["unlisted_rejects"] += 1

        # envelope-check the WHOLE batch up front so an EnvelopeError
        # leaves the id maps untouched (the native router's contract:
        # native/sched.py plan_batch / collect_plan)
        for i, m in enumerate(msgs):
            if not (-2**31 <= m.price < 2**31 and -2**31 <= m.size < 2**31):
                raise EnvelopeError(
                    f"message {i}: price/size outside int32 "
                    f"(price={m.price}, size={m.size})")
            if (java and m.action in _TRADE_ACTS
                    and not (0 <= m.price < _JAVA_PRICE_END and m.size > 0)):
                # the reference runs unvalidated fields and the stock
                # harness draws floor(N(50, 10)): about one trade in a
                # million is zero or negative. On the device that is a
                # sticky LERR_JAVA_DOMAIN AFTER the state was touched
                # (fatal); seen here, before anything is, the stream
                # leaves the device surface like a barrier does
                raise UnsupportedJavaOp(
                    f"message {i}: trade outside the java device domain "
                    f"(price={m.price}, size={m.size}); use the native "
                    f"engine")
        self.counts["plans"] += 1
        plan = self.counts["plans"]
        for i, m in enumerate(msgs):
            a = m.action
            aid, sid, oid = jl.jlong(m.aid), jl.jlong(m.sid), jl.jlong(m.oid)
            if a in _TRADE_ACTS:
                if java and sid < 0:
                    raise UnsupportedJavaOp(
                        f"message {i}: negative-sid trade (sid={sid}) — "
                        f"java ±sid book coupling is outside the device "
                        f"surface; use the native engine")
                # mutation order (lane, oid_sid, acct) is the authority
                # contract: the native router replicates it exactly so
                # partial map state after a CapacityError is identical
                if java:
                    lane = self._lane(sid)
                else:
                    lane = self._sid_lane.get(sid)
                    if lane is None:
                        unlisted(i)
                        continue
                self.oid_sid[oid] = sid
                if not java:
                    self.oid_plan[oid] = plan
                self.counts["routes_made"] += 1
                emit(i, _TRADE_ACTS[a], self._acct(aid), lane, m, oid,
                     aid, sid)
            elif a == op.CANCEL:
                rsid = self.oid_sid.get(oid)
                if rsid is None:
                    host_rejects.add(i)
                    self.counts["cancels_host_rejected"] += 1
                    continue
                self.counts["cancels_routed"] += 1
                if java:
                    emit(i, SQ.L_CANCEL, self._acct(aid),
                         self._lane(rsid), m, oid, aid, rsid)
                    continue
                lane = self._sid_lane.get(rsid)
                if lane is None:    # only an imported map can say so
                    unlisted(i)
                    continue
                emit(i, SQ.L_CANCEL, self._acct(aid), lane, m, oid)
            elif a == op.CREATE_BALANCE:
                emit(i, SQ.L_CREATE, self._acct(aid), 0, m, oid, aid, 0)
            elif a == op.TRANSFER:
                emit(i, SQ.L_TRANSFER, self._acct(aid), 0, m, oid,
                     aid, 0)
            elif a == op.ADD_SYMBOL:
                if java and sid < 0:
                    raise UnsupportedJavaOp(
                        f"message {i}: negative-sid ADD_SYMBOL "
                        f"(sid={sid}) — outside the java device surface")
                if sid < 0:
                    host_rejects.add(i)
                    continue
                fresh = sid not in self._sid_lane
                lane = self._lane(sid)
                if fresh or sid in self.delisted:
                    # the device accepts it: the book does not exist
                    self.delisted.discard(sid)
                    self.counts["symbols_listed"] += 1
                emit(i, SQ.L_ADD_SYMBOL, 0, lane, m, oid, aid, sid)
            elif a in (op.REMOVE_SYMBOL, op.PAYOUT):
                if java:
                    raise UnsupportedJavaOp(
                        f"message {i}: {'REMOVE_SYMBOL' if a == 1 else 'PAYOUT'} "
                        f"in java mode — Q3-Q6 barrier paths are outside "
                        f"the device surface; use the native engine")
                s = abs(sid)
                lane = self._sid_lane.get(s)
                if lane is None:
                    unlisted(i)
                    continue
                if a == op.REMOVE_SYMBOL:
                    act = SQ.L_REMOVE_SYMBOL
                else:
                    act = SQ.L_PAYOUT_YES if sid >= 0 else SQ.L_PAYOUT_NO
                emit(i, act, 0, lane, m, oid)
                self._purge(s)
                if s in self.delisted:
                    continue        # no book: the device rejects it
                if a == op.REMOVE_SYMBOL:
                    self.delisted.add(s)    # its positions stay
                else:
                    # books wiped, positions zeroed: the lane is empty
                    del self._sid_lane[s]
                    heapq.heappush(self._free, lane)
                    self.counts["symbols_settled"] += 1
                    self.counts["lanes_released"] += 1
            else:
                host_rejects.add(i)
        out = {
            "msg_index": np.array(cols["msg_index"], np.int64),
            "act": np.array(cols["act"], np.int32),
            "aid": np.array(cols["aid"], np.int32),
            "price": np.array(cols["price"], np.int32),
            "size": np.array(cols["size"], np.int32),
            "lane": np.array(cols["lane"], np.int32),
            "oid": np.array(cols["oid"], np.int64),
        }
        if java:
            out["aid_raw"] = np.array(cols["aid_raw"], np.int64)
            out["sid_raw"] = np.array(cols["sid_raw"], np.int64)
            out["flags"] = np.array(cols["flags"], np.int32)
        return out, host_rejects


class NativeSeqRouter:
    """C++ twin of SeqRouter (native/kme_router.cpp): identical routing
    over columnar int64 arrays. The id maps live in C++; the dict
    properties export/import them for the checkpoint contract. A CALL
    whose fields overflow int64 routes through a temporary Python
    router (maps synced both ways); subsequent calls are native
    again."""

    def __init__(self, num_lanes: int, num_accounts: int, lib) -> None:
        import weakref

        self.S = num_lanes
        self.A = num_accounts
        self._lib = lib
        self._h = lib.kme_router_new(num_lanes, num_accounts)
        self._fin = weakref.finalize(self, lib.kme_router_free, self._h)
        # bumped on every wholesale map import (checkpoint restore):
        # SeqSession's recon-LUT cache keys on (map size, epoch), and
        # the size alone can collide across an import
        self._map_epoch = 0

    # -- map views (checkpoint save/load reads+writes these) -----------
    def _export_arrays(self, nfn, efn, vdt):
        """(keys, values) of one C++ map, in the map's own order."""
        import ctypes

        n = nfn(self._h)
        keys = np.empty(n, np.int64)
        vals = np.empty(n, vdt)
        P64 = ctypes.POINTER(ctypes.c_int64)
        PV = ctypes.POINTER(
            ctypes.c_int32 if vdt == np.int32 else ctypes.c_int64)
        efn(self._h, keys.ctypes.data_as(P64), vals.ctypes.data_as(PV))
        return keys, vals

    def _export(self, nfn, efn, vdt):
        keys, vals = self._export_arrays(nfn, efn, vdt)
        return dict(zip(keys.tolist(), vals.tolist()))

    def _import_arrays(self, ifn, keys, vals, vdt):
        import ctypes

        self._map_epoch += 1
        keys = np.ascontiguousarray(keys, np.int64)
        vals = np.ascontiguousarray(vals, vdt)
        if keys.shape != vals.shape or keys.ndim != 1:
            raise ValueError(f"id map: {keys.shape} keys for "
                             f"{vals.shape} values")
        P64 = ctypes.POINTER(ctypes.c_int64)
        PV = ctypes.POINTER(
            ctypes.c_int32 if vdt == np.int32 else ctypes.c_int64)
        ifn(self._h, len(keys), keys.ctypes.data_as(P64),
            vals.ctypes.data_as(PV))

    def _import(self, ifn, d, vdt):
        self._import_arrays(ifn, np.fromiter(d.keys(), np.int64, len(d)),
                            np.fromiter(d.values(), vdt, len(d)), vdt)

    @property
    def aid_idx(self):
        lib = self._lib
        return self._export(lib.kme_router_n_accounts,
                            lib.kme_router_export_accounts, np.int32)

    @aid_idx.setter
    def aid_idx(self, d):
        self._import(self._lib.kme_router_import_accounts, d, np.int32)

    @property
    def sid_lane(self):
        lib = self._lib
        return self._export(lib.kme_router_n_symbols,
                            lib.kme_router_export_symbols, np.int32)

    @sid_lane.setter
    def sid_lane(self, d):
        self._import(self._lib.kme_router_import_symbols, d, np.int32)

    @property
    def delisted(self) -> set:
        import ctypes

        lib = self._lib
        keys = np.empty(lib.kme_router_n_delisted(self._h), np.int64)
        lib.kme_router_export_delisted(
            self._h, keys.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        return set(keys.tolist())

    @delisted.setter
    def delisted(self, d) -> None:
        import ctypes

        keys = np.fromiter(d, np.int64, len(d))
        self._lib.kme_router_import_delisted(
            self._h, len(d),
            keys.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))

    set_listed = SeqRouter.set_listed

    def stats(self, add=None) -> tuple:
        """ROUTER_STATS, cumulative (`add`: counts to fold in first)."""
        import ctypes

        P64 = ctypes.POINTER(ctypes.c_int64)
        out = np.empty(len(ROUTER_STATS), np.int64)
        if add is not None:
            add = np.asarray(add, np.int64)
        self._lib.kme_router_stats(
            self._h, None if add is None else add.ctypes.data_as(P64),
            out.ctypes.data_as(P64))
        return tuple(out.tolist())

    @property
    def oid_sid(self):
        lib = self._lib
        return self._export(lib.kme_router_n_routes,
                            lib.kme_router_export_routes, np.int64)

    @oid_sid.setter
    def oid_sid(self, d):
        self._import(self._lib.kme_router_import_routes, d, np.int64)

    def routes_capture(self):
        """routes_arrays() in two halves: the C++ map's export, made
        here, and -> a call that sorts it, for any thread at any later
        time. No dict in between."""
        lib = self._lib
        raw = self._export_arrays(lib.kme_router_n_routes,
                                  lib.kme_router_export_routes, np.int64)
        return lambda: sorted_routes(*raw)

    def routes_arrays(self):
        """`oid_sid` as a snapshot carries it (sorted_routes)."""
        return self.routes_capture()()

    def capture(self):
        """SeqRouter.capture's two halves from the C++ maps: their
        exports here, the lists and the sorting in the call."""
        lib = self._lib
        acc = self._export_arrays(lib.kme_router_n_accounts,
                                  lib.kme_router_export_accounts, np.int32)
        sym = self._export_arrays(lib.kme_router_n_symbols,
                                  lib.kme_router_export_symbols, np.int32)
        routes = self.routes_capture()

        def items(keys, vals):
            return sorted(zip(keys.tolist(), vals.tolist()))

        return lambda: (items(*acc), items(*sym), *routes())

    def import_routes(self, keys, vals) -> None:
        self._import_arrays(self._lib.kme_router_import_routes, keys,
                            vals, np.int64)

    def n_routes(self) -> int:
        """Routes held, without exporting the map."""
        return int(self._lib.kme_router_n_routes(self._h))

    def drop_batch(self, cols, host, fills, plan: int) -> tuple:
        """SeqRouter.drop_batch in one native call: route_events' walk
        and the drop, on the C++ map."""
        from kme_tpu.native import BoundaryError

        nr, nf = len(cols["act"]), fills.shape[1]
        # the dtypes the walk reads, made so here; it reads the six
        # per-row arrays to nr and the fills' oids to nf
        arrs = [np.ascontiguousarray(a, dt) for a, dt in (
            (cols["act"], np.int32), (cols["oid"], np.int64),
            (host["ok"], np.uint8), (host["residual"], np.int32),
            (host["nfill"], np.int32), (host["last_emptied"], np.uint8),
            (fills[0], np.int64))]
        for a, n in zip(arrs, (nr,) * 6 + (nf,)):
            if a.ndim != 1 or len(a) < n:
                raise BoundaryError(
                    f"drop_batch: shape {a.shape}, the walk reads {n}")
        out = np.empty(2, np.int64)
        rc = self._lib.kme_router_drop_batch(
            self._h, nr, *(a.ctypes.data for a in arrs[:6]), nf,
            arrs[6].ctypes.data, int(plan), out.ctypes.data)
        if rc != 0:
            raise BoundaryError("drop_batch: nfill runs past the fills")
        return int(out[0]), int(out[1])

    def acct_of_idx(self) -> List[int]:
        m = self.aid_idx
        out = [0] * len(m)
        for aid, idx in m.items():
            out[idx] = aid
        return out

    def sid_of_lane(self) -> Dict[int, int]:
        return {lane: sid for sid, lane in self.sid_lane.items()}

    def route(self, msgs):
        import ctypes

        n = len(msgs)
        try:
            if isinstance(msgs, WireBatch):
                # columnar fast path: zero per-message Python work
                raw = {f: np.ascontiguousarray(getattr(msgs, f))
                       for f in ("action", "oid", "aid", "sid",
                                 "price", "size")}
            else:
                raw = {
                    "action": np.fromiter((m.action for m in msgs),
                                          np.int64, n),
                    "oid": np.fromiter((m.oid for m in msgs),
                                       np.int64, n),
                    "aid": np.fromiter((m.aid for m in msgs),
                                       np.int64, n),
                    "sid": np.fromiter((m.sid for m in msgs),
                                       np.int64, n),
                    "price": np.fromiter((m.price for m in msgs),
                                         np.int64, n),
                    "size": np.fromiter((m.size for m in msgs),
                                        np.int64, n),
                }
        except OverflowError:
            # a field beyond int64: the columnar path cannot carry it
            py = SeqRouter(self.S, self.A)
            py.aid_idx = self.aid_idx
            py.sid_lane, py.delisted = self.sid_lane, self.delisted
            py.oid_sid = self.oid_sid
            try:
                return py.route(msgs)
            finally:    # whatever it left, a CapacityError's too
                self.aid_idx = py.aid_idx
                self.sid_lane, self.delisted = py.sid_lane, py.delisted
                self.oid_sid = py.oid_sid
                self.stats(add=py.stats()[:-1])
        bad = ((raw["price"] < -(2**31)) | (raw["price"] >= 2**31)
               | (raw["size"] < -(2**31)) | (raw["size"] >= 2**31))
        if bad.any():
            i = int(np.argmax(bad))
            raise EnvelopeError(
                f"message {i}: price/size outside int32 "
                f"(price={int(raw['price'][i])}, "
                f"size={int(raw['size'][i])})")
        lib = self._lib
        P64 = ctypes.POINTER(ctypes.c_int64)
        rc = lib.kme_router_route(
            self._h, n, *(raw[f].ctypes.data_as(P64)
                          for f in ("action", "oid", "aid", "sid",
                                    "price", "size")))
        if rc != 0:
            raise CapacityError(
                f"{'account' if rc == 1 else 'symbol'} capacity "
                f"exhausted (id={lib.kme_router_err_value(self._h)})")
        nr = lib.kme_router_n_routed(self._h)
        nj = lib.kme_router_n_rejects(self._h)

        from kme_tpu.native.sched import _arr

        arr = lambda fn, dt, cnt: _arr(fn(self._h), cnt, dt)

        cols = {
            "msg_index": arr(lib.kme_router_o_msg, np.int64, nr),
            "act": arr(lib.kme_router_o_act, np.int32, nr),
            "aid": arr(lib.kme_router_o_aidx, np.int32, nr),
            "price": arr(lib.kme_router_o_price, np.int32, nr),
            "size": arr(lib.kme_router_o_size, np.int32, nr),
            "lane": arr(lib.kme_router_o_lane, np.int32, nr),
            "oid": arr(lib.kme_router_o_oid, np.int64, nr),
        }
        rejects = set(arr(lib.kme_router_o_rej, np.int64, nj).tolist())
        return cols, rejects


def make_seq_router(num_lanes: int, num_accounts: int,
                    compat: str = "fixed"):
    """The native router, or under an explicit KME_NATIVE=0 the Python
    implementation — identical routing either way
    (tests/test_seq_engine.py). An unbuildable library raises
    (native.require_library). java mode always uses the Python router
    (it carries the raw-id/flag columns)."""
    if compat == "java":
        return SeqRouter(num_lanes, num_accounts, compat="java")
    from kme_tpu.native import require_library

    lib = require_library()
    if lib is None:
        return SeqRouter(num_lanes, num_accounts)
    return NativeSeqRouter(num_lanes, num_accounts, lib)


# acts that touch a book: the kernel's `needs_books` (engine/seq.py,
# is_trade | is_cancel | is_barrier)
_BOOK_ACTS = (SQ.L_BUY, SQ.L_SELL, SQ.L_CANCEL, SQ.L_PAYOUT_YES,
              SQ.L_PAYOUT_NO, SQ.L_REMOVE_SYMBOL)


def count_lane_switches(cfg: SQ.SeqConfig, stacked: dict) -> int:
    """HBM lane switches the kernel will make over one dispatch's
    (K, B) `act` / `lane` planes, by its own rule: within a kernel call
    (one row) a book-touching message whose lane differs from the lane
    cached before it loads that lane's books (and flushes the cached
    one); nothing is cached at the start of a call. 0 where the books
    live in VMEM."""
    if not cfg.hbm_books:
        return 0
    needs = np.isin(stacked["act"], _BOOK_ACTS)
    call = np.nonzero(needs)[0]         # row-major: the kernel's order
    if not len(call):
        return 0
    lane = stacked["lane"][needs]
    return 1 + int(np.count_nonzero((call[1:] != call[:-1])
                                    | (lane[1:] != lane[:-1])))


def route_events(cols: dict, host: dict, fills: np.ndarray):
    """What one fetched batch says of its orders' places on the book,
    in message order, for SeqRouter.drop: -> (oids, alive). A trade is
    one event (alive where it was accepted and a residual rested), an
    accepted cancel one (dead), and every maker a sweep emptied one
    (dead, before its taker's own event): all of a taker's makers but
    the last are emptied, and of the last the kernel says so
    (`last_emptied`)."""
    act, ok, nfill = cols["act"], host["ok"], host["nfill"]
    trade = (act == SQ.L_BUY) | (act == SQ.L_SELL)
    rested = trade & ok & (host["residual"] > 0)
    own = np.nonzero(trade | ((act == SQ.L_CANCEL) & ok))[0]
    gone = np.ones(fills.shape[1], bool)
    swept = np.nonzero(nfill > 0)[0]
    gone[np.cumsum(nfill)[swept] - 1] = host["last_emptied"][swept]
    taker = np.repeat(np.arange(len(nfill)), nfill)[gone]
    order = np.argsort(np.concatenate([2 * taker, 2 * own + 1]),
                       kind="stable")
    oids = np.concatenate([fills[0][gone], cols["oid"][own]])[order]
    alive = np.concatenate([np.zeros(len(taker), bool),
                            rested[own]])[order]
    return oids, alive


class SeqSession:
    """The device engine's host half, over the sequential mega-kernel:
    process / process_wire / submit + collect, metrics / histograms,
    export_state. Single-device (parallel/seqmesh.py's SeqMeshSession
    is the sharded subclass)."""

    # every span this session records (PhaseTimer names): the serve
    # loop registers each as a heartbeat gauge pair before the first
    # heartbeat, so that a reader of two snapshots finds it in both
    SPANS = ("plan_s", "stage_s", "dispatch_s", "fetch_s", "recon_s",
             "route_drop", "session_metrics", "metrics_export",
             "metrics_count", "snapshot_export", "snapshot_meta",
             "snapshot_write")
    # of them, those whose thread CPU is read beside their wall (gauge
    # `<name>_cpu_s`): the ones a metric reads, no more, since the
    # thread's CPU clock is a system call on the chip's host. Pure
    # host work (plan_s, recon_s) is off-CPU only for the interpreter
    # lock and the scheduler; fetch_s and snapshot_export wait for the
    # device and the transfer; dispatch_s is the fixed cost of a call
    CPU_SPANS = ("plan_s", "dispatch_s", "fetch_s", "recon_s",
                 "snapshot_export")

    def __init__(self, cfg: SQ.SeqConfig) -> None:
        self.cfg = cfg
        self.state = SQ.make_seq_state(cfg)
        self.router = make_seq_router(cfg.lanes, cfg.accounts,
                                      compat=cfg.compat)
        self._metrics = np.zeros(SQ.N_METRICS, np.int64)
        self._hist = np.zeros((SQ.N_HIST, SQ.N_HIST_BUCKETS), np.int64)
        self._recon = None          # native reconstructor handle
        self.telemetry = Registry()
        self.timer = PhaseTimer(track="seq", cpu=self.CPU_SPANS)
        # CUMULATIVE wall time per phase across every batch (the timer's
        # totals dict IS this attribute; snapshot/reset via self.timer)
        self.phases = self.timer.totals
        self._use_native_wire = True
        # adaptive fill-slice hint (fill groups per call fetched in the
        # single-round fetch; grows to the observed high-water mark)
        self._ghint = 8
        # per-message REJ_* reason codes for the last processed batch
        # (np.uint8 (nmsg,), wire.REJ_NAMES) — the flight recorder and
        # the REJ annotation records read this after each batch
        self.last_reasons = None
        # ("submit"|"collect", pipeline-batch-idx, t0, t1) wall windows
        # from the pipelined path, for measured-overlap reporting
        self.windows: List[tuple] = []
        self._n_submit = 0
        self._n_collect = 0
        # H2D overlap accounting: staging time spent while a previous
        # submit was still in flight (device busy) counts as overlapped
        self._h2d_total_s = 0.0
        self._h2d_overlap_s = 0.0
        # HBM book-cache lane switches the kernel made (the host's
        # count over each plan, count_lane_switches); the serve loop
        # publishes it as counter `lane_switches`
        self.lane_switches = 0
        # tiles of the position store the kernel brought in from HBM
        # (its own count, in each call's scalar row; added as a batch is
        # fetched; java mode has no such store: 0); the serve loop
        # publishes it as counter `pos_probe_tiles`
        self.pos_probe_tiles = 0
        # the barrier section's work, likewise the kernel's own counts:
        # resting orders `wipe_side` took off (one loop turn each) and
        # positions a YES payout credited
        self.barrier_wiped_orders = 0
        self.barrier_credited_positions = 0
        # the fetch's first round (_start_fetch / _finish_fetch):
        # dispatches whose prefix slice was launched with them, late
        # halves that found that slice already run, and late halves
        # that needed the overflow slices; the serve loop publishes
        # them as counters of the same names
        self.fetch_early = 0
        self.fetch_ready = 0
        self.fetch_second_rounds = 0
        # the router's ROUTER_STATS as of the newest batch COLLECTED
        # (the router itself runs `pipeline` batches ahead); the serve
        # loop publishes them as counters, `lanes_bound` and what is
        # left of cfg.lanes as gauges, the purge as span `route_purge`
        self.router_stats = dict.fromkeys(ROUTER_STATS, 0)
        # fixed mode: routes dropped because their order left the book
        # (cumulative) and routes the router holds, both as of the
        # newest drop (_drop_routes); the serve loop publishes them as
        # counter `routes_dropped` and gauge `routes_held`
        self.routes_dropped = 0
        self.routes_held = 0
        # metrics()' narrow read, compiled here and not at the first
        # refresh: a compile inside a served batch is a stall
        self._occupancy = SQ.build_seq_occupancy(cfg)
        self._occupancy(self.state)
        # likewise the two programs a fixed-mode snapshot fetches the
        # books' live rows and the positions' live entries by
        # (engine/seq.py:export_snapshot takes them from the same caches)
        if cfg.compat == "fixed":
            SQ.live_rows_call(cfg, self.state)
            SQ.live_positions_call(cfg, self.state)
        # bytes metrics() has brought device -> host (cumulative; the
        # serve loop publishes it as gauge `metrics_fetch_bytes`)
        self.metrics_fetch_bytes = 0
        # what the newest fixed-mode snapshot held (set by
        # runtime/checkpoint.py:save_seq_session; the serve loop
        # publishes them as gauges): `snapshot_bytes` of the file,
        # `snapshot_live_slots` / `snapshot_live_positions` in it,
        # `snapshot_sparse_sections` (0-2) written by their live
        # entries, and of its device -> host half `snapshot_fetch_bytes`
        # (all that crossed), `snapshot_live_rows` (rows of a book plane
        # that hold an order), `snapshot_fetch_calls` (calls of the
        # live-row program that brought the books; 0: they crossed
        # whole), `snapshot_pos_fetch_bytes` (the positions' share of
        # what crossed) and `snapshot_pos_calls` (calls of the
        # live-entry program that brought them; 0: they crossed whole)
        self.snapshot_gauges: Dict[str, int] = {}

    # ------------------------------------------------------------------

    def _plan(self, msgs):
        """Route + pack: columnar router output -> the stacked (K, B)
        i32 input planes of one scan dispatch. Returns
        (cols, host_rejects, stacked, cnts, K). Fixed-mode WireBatches
        take the single-call native path (kme_plan_batch) when the
        library is built; the numpy pack below is the byte-exact
        fallback (and the only path for java mode, whose extra
        aidr/sidr/flags planes ride the Python router)."""
        from kme_tpu.utils import pow2_bucket

        if (isinstance(msgs, WireBatch)
                and isinstance(self.router, NativeSeqRouter)):
            from kme_tpu.native.sched import plan_batch

            r = plan_batch(self.router, msgs, self.cfg.batch)
            if r is not None:
                return r
        cols, host_rejects = self.router.route(msgs)
        n = len(cols["act"])
        B = self.cfg.batch
        nk = max(-(-n // B), 1)
        K = pow2_bucket(nk, lo=1)
        total = K * B

        # vectorized pack over ALL chunks at once (pack_msgs per chunk
        # was a measurable slice of the plan phase at 100k+ messages);
        # zero padding is L_NOP by construction
        def pad32(src):
            a = np.zeros(total, np.int32)
            a[:n] = src[:n]
            return a.reshape(K, B)

        def split64(name, src):
            v = np.zeros(total, np.int64)
            v[:n] = src[:n]
            return {f"{name}_lo": (v & 0xFFFFFFFF).astype(np.uint32)
                    .astype(np.int32).reshape(K, B),
                    f"{name}_hi": (v >> 32).astype(np.int32).reshape(K, B)}

        stacked = {f: pad32(cols[f])
                   for f in ("act", "aid", "price", "size", "lane")}
        stacked.update(split64("oid", cols["oid"]))
        if self.cfg.compat == "java":
            stacked.update(split64("aidr", cols["aid_raw"]))
            stacked.update(split64("sidr", cols["sid_raw"]))
            stacked["flags"] = pad32(cols["flags"])
        cnts = [max(min(B, n - ci * B), 0) for ci in range(K)]
        return cols, host_rejects, stacked, cnts, K

    def _run(self, msgs):
        """Plan (route + pack) + dispatch (ONE lax.scan jit call over
        all chunks, its output prefix's slice and copy launched behind
        it), then fetch in one concurrent round (headers + adaptive
        fill prefix; rare overflow slices in a second round).
        Phase wall times ACCUMULATE in self.phases (the bench and the
        service read them; reset via self.timer.reset()).
        Returns (cols, host_rejects, host dict, fills (4, F))."""
        with self.timer.phase("plan_s"):
            cols, host_rejects, stacked, cnts, K = self._plan(msgs)
        self.lane_switches += count_lane_switches(self.cfg, stacked)
        self._note_router(self.router.stats())
        with self.timer.phase("dispatch_s"):
            self.state, outp = SQ.build_seq_scan(self.cfg, K)(
                self.state, stacked)
            prefix = self._start_fetch(outp)
            import jax as _jax
            _jax.block_until_ready(self.state)
        with self.timer.phase("fetch_s"):
            host, fills = self._finish_fetch(outp, prefix, cnts, K)
        with self.timer.phase("recon_s"):
            self._drop_routes(cols, host, fills)
        return cols, host_rejects, host, fills

    def _start_fetch(self, outp):
        """The early half of a dispatch's fetch, called right behind
        the scan's own dispatch (inside `dispatch_s`): slice the output
        planes' prefix (headers + the adaptive fill-group hint's worth
        of fill rows per call) and start its copy to the host. The
        device runs programs in launch order, so the slice stands
        directly behind the scan whose output it reads and ahead of
        every scan dispatched later: launched at the fetch it would
        wait out whichever scan was dispatched in between. Returns
        (the prefix on the device, the hint it was cut by)."""
        from kme_tpu.utils import async_prefetch, pow2_bucket

        ghint = min(pow2_bucket(self._ghint, lo=1),
                    self.cfg.fill_cap // 128)
        fdev = outp[:, :SQ.hdr_rows(self.cfg) + 5 * ghint, :]
        async_prefetch([fdev])
        self.fetch_early += 1
        return fdev, ghint

    def _finish_fetch(self, outp, prefix, cnts, K):
        """The late half: take `_start_fetch`'s prefix to the host and
        unpack it. ONE fetch round in the common case; calls whose
        fill_total overflows the hint THE PREFIX WAS CUT BY (the
        session's may have grown since) get a rare second-round slice,
        launched here, behind whatever was dispatched since."""
        from kme_tpu.utils import async_prefetch, pow2_bucket

        HR = SQ.hdr_rows(self.cfg)
        fdev, ghint = prefix
        self.fetch_ready += fdev.is_ready()
        fetched = np.asarray(fdev)
        host = {k: [] for k in ("ok", "cap_reject", "append",
                                "last_emptied", "residual", "nfill",
                                "prev_oid")}
        results = []
        mets = np.zeros(SQ.N_METRICS, np.int64)
        hists = np.zeros((SQ.N_HIST, SQ.N_HIST_BUCKETS), np.int64)
        for ci in range(K):
            res = SQ.unpack_hdr(self.cfg, fetched[ci][:HR], cnts[ci])
            if res["err"] != SQ.LERR_OK:
                raise LaneEngineError(res["err"])
            results.append(res)
            mets += res["metrics"]
            hists += res["hist"]
            self.pos_probe_tiles += res["pos_tiles"]
            self.barrier_wiped_orders += res["wiped"]
            self.barrier_credited_positions += res["credited"]
        gneed = [-(-max(r["fill_total"], 1) // 128) for r in results]
        self._ghint = max(self._ghint, *gneed)
        over = [ci for ci in range(K) if gneed[ci] > ghint]
        extra = {}
        if over:
            self.fetch_second_rounds += 1
            slices = [outp[ci, HR:HR + 5 * pow2_bucket(gneed[ci], lo=1)]
                      for ci in over]
            async_prefetch(slices)
            extra = {ci: np.asarray(s) for ci, s in zip(over, slices)}
        fills = []
        for ci, res in enumerate(results):
            if ci in extra:
                groups = extra[ci][:5 * gneed[ci]]
            else:
                groups = fetched[ci][HR:HR + 5 * gneed[ci]]
            fills.append(SQ.unpack_fills(groups, res["fill_total"]))
        for res in results:
            for k in host:
                host[k].append(res[k])
        self._metrics += mets
        self._hist += hists
        host = {k: np.concatenate(v) if v else np.zeros(0)
                for k, v in host.items()}
        fills = (np.concatenate(fills, axis=1) if fills
                 else np.zeros((4, 0), np.int64))
        return host, fills

    # -- pipelined serving (H5): dispatch batch N+1 before fetching N --

    def submit(self, msgs):
        """Route + pack + DISPATCH a micro-batch without fetching its
        outputs; returns an opaque handle for collect(). Multiple
        handles may be in flight — state threads through dispatch
        order, so collect order must match submit order. This is the
        double-buffered serving shape (SURVEY.md §7 H5): the device
        executes batch N+1 while the host fetches and reconstructs
        batch N."""
        from time import perf_counter

        t0 = perf_counter()
        if not isinstance(msgs, WireBatch):
            try:
                msgs = WireBatch.from_msgs(msgs)
            except OverflowError:
                raise ValueError(
                    "pipelined serving requires int64-range ids — "
                    "route beyond-int64 streams through process_wire")
        with self.timer.phase("plan_s"):
            cols, host_rejects, stacked, cnts, K = self._plan(msgs)
        # counted here (the planes are a rotating native buffer) and
        # added at collect(), with the batch's other counters: a reader
        # of two heartbeats then finds the same batches in each
        switches = count_lane_switches(self.cfg, stacked)
        routed = self.router.stats()
        with self.timer.phase("stage_s"):
            # explicit async H2D staging: device_put enqueues the copy
            # of batch N+1's input planes while the device still runs
            # batch N's scan — the jit call below then consumes
            # already-on-device buffers instead of paying a sync
            # transfer at dispatch time. (State donation is NOT an
            # option here: it clobbers the kernel's
            # input_output_aliases — see build_seq_scan.)
            import jax as _jax

            t_st = perf_counter()
            stacked = _jax.device_put(stacked)
            dt_st = perf_counter() - t_st
        # the copy is overlapped exactly when an earlier submit is
        # still un-collected: the device runs batch N's scan while
        # batch N+1's planes stream in (the device-side half of the
        # PR 6 double buffer)
        self._h2d_total_s += dt_st
        if self._n_submit > self._n_collect:
            self._h2d_overlap_s += dt_st
        # advisory gauges (pure wall time, never enforced):
        # cumulative host cost of the async staging enqueues + the
        # fraction of it hidden under in-flight device compute
        self.telemetry.publish_gauges(
            {"h2d_stage_s": round(self.phases.get("stage_s", 0.0), 6),
             "h2d_overlap_frac": self.h2d_overlap_frac})
        with self.timer.phase("dispatch_s"):
            # async enqueue: NO block_until_ready here — the device
            # runs this batch while the host plans/collects others
            self.state, outp = SQ.build_seq_scan(self.cfg, K)(
                self.state, stacked)
            prefix = self._start_fetch(outp)
        self.windows.append(("submit", self._n_submit, t0,
                             perf_counter()))
        self._n_submit += 1
        return (msgs, cols, host_rejects, outp, prefix, cnts, K, switches,
                routed)

    def _note_router(self, stats: tuple) -> None:
        """Take up the router's cumulative counts as of one batch, and
        fold what its purges took since the last into the timer as span
        `route_purge` (timed inside the router: in C++ on the native
        path)."""
        new = dict(zip(ROUTER_STATS, stats))
        old, self.router_stats = self.router_stats, new
        # (a wall clock, `steady_clock` in C++)
        self.timer.add(
            "route_purge",
            1e-9 * (new["route_purge_ns"] - old["route_purge_ns"]),
            n=new["route_purge_n"] - old["route_purge_n"])

    def _drop_routes(self, cols, host, fills) -> None:
        """Routes die with their orders: tell the router which orders
        of the batch just fetched have left the book (span
        `route_drop`, inside `recon_s`). The batch is the one whose
        router counts `_note_router` took up last, so its plan's
        ordinal is theirs. java mode keeps every route (its fills carry
        Q2 ghosts and merged books: COMPAT.md)."""
        if self.cfg.compat == "java":
            self.routes_held = self.router.n_routes()
            return
        with self.timer.phase("route_drop"):
            self.routes_dropped, self.routes_held = self.router.drop_batch(
                cols, host, fills, self.router_stats["plans"])

    @property
    def h2d_overlap_frac(self) -> float:
        """Fraction of H2D staging wall hidden under in-flight device
        compute. Serial process() paths report 0.0; a depth-N pipeline
        approaches (N-1)/N and the gate expects >= 0.5 at depth 2."""
        if self._h2d_total_s <= 0.0:
            return 0.0
        return round(self._h2d_overlap_s / self._h2d_total_s, 4)

    def collect(self, handle):
        """Complete a submit(): fetch + reconstruct the byte stream.
        Returns (buf, line_off, msg_lines) like process_wire_buffer
        (requires the native reconstructor and a WireBatch handle)."""
        from time import perf_counter

        t0 = perf_counter()
        (batch, cols, host_rejects, outp, prefix, cnts, K, switches,
         routed) = handle
        self.lane_switches += switches
        self._note_router(routed)
        with self.timer.phase("fetch_s"):
            host, fills = self._finish_fetch(outp, prefix, cnts, K)
        with self.timer.phase("recon_s"):
            self._drop_routes(cols, host, fills)
            r = self._recon_buffer(batch, cols, host_rejects, host,
                                   fills)
        self.windows.append(("collect", self._n_collect, t0,
                             perf_counter()))
        self._n_collect += 1
        return r

    # ------------------------------------------------------------------

    def process_wire_buffer(self, msgs):
        """Serving/bench fast path: the full byte-exact record stream as
        ONE utf-8 buffer + line offsets + per-message line counts, built
        by the native C++ reconstructor (kme_tpu/native/kme_wire.cpp).
        `msgs` may be a WireBatch (zero per-message Python work — the
        1M/s-class local path) or an OrderMsg sequence (columnarized
        here, one attribute walk). Returns (buf: bytes, line_off:
        (L+1,) np.int64 incl. end sentinel, msg_lines: (nmsg,)
        np.int32), or None when the native library is unavailable or a
        field exceeds int64 (callers fall back to process_wire)."""
        import ctypes

        from kme_tpu.native import load_library

        lib = load_library()
        if lib is None:
            return None
        if not len(msgs):
            return b"", np.zeros(1, np.int64), np.zeros(0, np.int32)
        if isinstance(msgs, WireBatch):
            batch = msgs
        else:
            try:
                batch = WireBatch.from_msgs(msgs)
            except OverflowError:
                return None  # beyond-int64 ids ride the Python path
        cols, host_rejects, host, fills = self._run(batch)
        with self.timer.phase("recon_s"):
            r = self._recon_buffer(batch, cols, host_rejects, host,
                                   fills)
        return r

    def _idx2aid(self):
        """account-idx -> aid LUT for reconstruction, cached against
        the router's account-map size: that map only grows, and
        exporting it was O(accounts) dict traffic per batch on the hot
        path. Wholesale imports (checkpoint restore) bump _map_epoch,
        so same-size-different-content restores can never serve a stale
        cache; Python routers are uncached (their dicts mutate without
        a hook). (There is no lane -> symbol table beside it: a lane
        names one id after another, and the router runs ahead of the
        batch being collected, so a record's id is its message's own.)"""
        r = self.router
        key = None
        if isinstance(r, NativeSeqRouter):
            key = (int(r._lib.kme_router_n_accounts(r._h)), r._map_epoch)
            cached = getattr(self, "_lut_cache", None)
            if cached is not None and cached[0] == key:
                return cached[1]
        idx2aid = np.array(r.acct_of_idx() or [0], np.int64)
        if key is not None:
            self._lut_cache = (key, idx2aid)
        return idx2aid

    def _recon_buffer(self, batch, cols, host_rejects, host, fills):
        """Columnar inputs + device results -> the byte-exact record
        stream via the native C++ reconstructor (kme_wire.cpp:
        kme_recon_batch, a single merge walk, no numpy scatter)."""
        from kme_tpu.native import load_library
        from kme_tpu.native.sched import recon_batch

        lib = load_library()
        if lib is None:
            raise RuntimeError(
                "the native reconstructor (kme_wire.cpp) is required "
                "for the pipelined/buffer serving path — use "
                "process_wire on hosts without the native toolchain")
        nmsg = batch.n
        self.last_reasons = reject_reason_codes(
            nmsg, cols["msg_index"], cols["act"], host["ok"],
            host["cap_reject"], host_rejects)
        if self._recon is None:
            import weakref

            self._recon = lib.kme_recon_new()
            # release the native buffer with the session (no __del__:
            # a finalizer survives interpreter-shutdown ordering)
            self._recon_fin = weakref.finalize(
                self, lib.kme_recon_free, self._recon)
        return recon_batch(lib, self._recon, batch, cols, host, fills,
                           self._idx2aid())

    def process_wire(self, msgs) -> List[List[str]]:
        if getattr(self, "_use_native_wire", True):
            r = self.process_wire_buffer(msgs)
            if r is not None:
                buf, line_off, msg_lines = r
                text = buf.decode("ascii")
                out = []
                li = 0
                for nl in msg_lines.tolist():
                    out.append([text[line_off[li + k]:line_off[li + k + 1]]
                                for k in range(nl)])
                    li += nl
                return out
        if isinstance(msgs, WireBatch):
            msgs = msgs.msgs()
        cols, host_rejects, host, fills = self._run(msgs)
        from kme_tpu.oracle.javalong import jlong

        idx_to_aid = self.router.acct_of_idx()

        nmsg = len(msgs)
        self.last_reasons = reject_reason_codes(
            nmsg, cols["msg_index"], cols["act"], host["ok"],
            host["cap_reject"], host_rejects)
        ok_of = [False] * nmsg
        nfill_of = [0] * nmsg
        off_of = [0] * nmsg
        resid_of = [0] * nmsg
        prev_of = [0] * nmsg
        append_of = [False] * nmsg
        act_of = [0] * nmsg
        mis = cols["msg_index"].tolist()
        offs = (np.cumsum(host["nfill"]) - host["nfill"]).tolist() \
            if len(mis) else []
        for arr, dst in ((host["ok"], ok_of), (host["nfill"], nfill_of),
                         (host["residual"], resid_of),
                         (host["prev_oid"], prev_of),
                         (host["append"], append_of)):
            vals = arr.tolist()
            for k, mi in enumerate(mis):
                dst[mi] = vals[k]
        acts = cols["act"].tolist()
        for k, mi in enumerate(mis):
            off_of[mi] = offs[k]
            act_of[mi] = acts[k]
        f_oid, f_aid, f_price, f_size = (fills[c].tolist() for c in range(4))

        out: List[List[str]] = []
        for i, m in enumerate(msgs):
            in_body = order_json(m.action, m.oid, m.aid, m.sid, m.price,
                                 m.size, m.next, m.prev)
            lines = [f'IN {in_body}']
            if i in host_rejects or not ok_of[i]:
                lines.append('OUT ' + order_json(
                    op.REJECT, m.oid, m.aid, m.sid, m.price, m.size,
                    m.next, m.prev))
            else:
                lane_act = act_of[i]
                is_trade = lane_act in (SQ.L_BUY, SQ.L_SELL)
                if is_trade:
                    # a fill is in the taker's book: the id the
                    # message was routed under (the router's map may
                    # name another for its lane by now)
                    sid = jlong(m.sid)
                    is_buy = lane_act == SQ.L_BUY
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
                        int(prev_of[i]) if append_of[i] else m.prev))
                else:
                    lines.append(f'OUT {in_body}')
            out.append(lines)
        return out

    def process(self, msgs) -> List[List[OutRecord]]:
        if isinstance(msgs, WireBatch):
            msgs = msgs.msgs()
        from kme_tpu.oracle.javalong import jlong

        cols, host_rejects, host, fills = self._run(msgs)
        idx_to_aid = self.router.acct_of_idx()
        nmsg = len(msgs)
        self.last_reasons = reject_reason_codes(
            nmsg, cols["msg_index"], cols["act"], host["ok"],
            host["cap_reject"], host_rejects)
        dev = {}
        offs = np.cumsum(host["nfill"]) - host["nfill"] \
            if len(cols["msg_index"]) else np.zeros(0)
        for k, mi in enumerate(cols["msg_index"].tolist()):
            dev[mi] = k

        out: List[List[OutRecord]] = []
        for i, m in enumerate(msgs):
            recs = [OutRecord("IN", m.copy())]
            if i in host_rejects:
                echo = m.copy()
                echo.action = op.REJECT
                recs.append(OutRecord("OUT", echo))
            else:
                k = dev[i]
                ok = bool(host["ok"][k])
                lane_act = int(cols["act"][k])
                is_trade = lane_act in (SQ.L_BUY, SQ.L_SELL)
                if is_trade and ok:
                    sid = jlong(m.sid)
                    is_buy = lane_act == SQ.L_BUY
                    o0 = int(offs[k])
                    for e in range(int(host["nfill"][k])):
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
                    echo.action = op.REJECT
                if is_trade and ok:
                    echo.size = int(host["residual"][k])
                    if bool(host["append"][k]):
                        echo.prev = int(host["prev_oid"][k])
                recs.append(OutRecord("OUT", echo))
            out.append(recs)
        return out

    # ------------------------------------------------------------------

    def metrics(self) -> Dict[str, int]:
        with self.timer.phase("session_metrics"):
            counters = dict(zip(SQ.METRIC_NAMES, self._metrics.tolist()))
            counters.update(
                self._count_for_metrics(self._export_for_metrics()))
            self._publish(counters)
        return counters

    def stale_routes(self, open_orders: int):
        """Routes the router holds beyond the `open_orders` resting on
        the device (metrics()' count): 0 since routes die with their
        orders; a restored older snapshot's stale routes show here
        until their symbols are wiped. None where the two cannot be
        set side by side: java mode keeps every route, and while a
        batch is in flight the router is ahead of the device."""
        if (self.cfg.compat == "java"
                or self._n_submit != self._n_collect):
            return None
        return self.router.n_routes() - open_orders

    def _export_for_metrics(self) -> np.ndarray:
        """The device -> host fetch of metrics(): SQ.OCCUPANCY_NAMES'
        five integers, reduced on the device. The state is not donated
        to the scan, so this reads it behind whatever is in flight.
        (This method and _count_for_metrics keep their names: each is a
        span target of benchmark/spans/.)"""
        with self.timer.phase("metrics_export"):
            five = np.asarray(self._occupancy(self.state))
            self.metrics_fetch_bytes += five.nbytes
            return five

    def _count_for_metrics(self, five: np.ndarray) -> Dict[str, int]:
        """Names what _export_for_metrics fetched."""
        with self.timer.phase("metrics_count"):
            return dict(zip(SQ.OCCUPANCY_NAMES, five.tolist()))

    def histograms(self) -> Dict[str, list]:
        """Device-accumulated distribution histograms (HIST_NAMES ->
        16 power-of-two bucket counts); published into the registry.
        book_depth stays empty in java mode (Q1 merged books have no
        per-lane occupancy plane)."""
        h = {name: self._hist[i].tolist()
             for i, name in enumerate(SQ.HIST_NAMES)}
        self.telemetry.publish_histograms(h)
        return h

    def _publish(self, counters: Dict[str, int]) -> None:
        self.telemetry.publish_counters(
            {k: counters[k] for k in SQ.METRIC_NAMES})
        self.telemetry.publish_gauges(
            {k: v for k, v in counters.items()
             if k not in SQ.METRIC_NAMES})

    def export_state(self) -> Dict[str, dict]:
        """Oracle-comparable host dict view."""
        if self.cfg.compat == "java":
            return self._export_state_java()
        canon = SQ.export_canonical(self.cfg, self.state)
        return self._canon_to_export(canon)

    def export_live(self, canon: dict, layout: dict) -> Dict[str, dict]:
        """export_state()'s four stores (fixed mode) from what a
        snapshot just fetched — SQ.export_snapshot's `canon` and
        `layout` — so that its reader (the auditor's snapshot-cadence
        compare) makes no fetch of its own and walks no dead slot: the
        books' live slots and the positions' live entries, each section
        sparse or dense as it crossed, named through the router's maps
        as _canon_to_export names the dense planes. The same keys and
        values but for an order's record, which is the tuple (aid, sid,
        is_buy, price, size) the auditor's shadow keeps and not a dict:
        no Python object is made an entry beyond what the stores hold.
        Held equal to export_state() by
        tests/test_audited_deployment.py."""
        S, _, N = layout["slot_shape"]
        A = self.cfg.accounts
        aid_of = np.asarray(self.router.acct_of_idx(), np.int64)
        n = len(aid_of)
        sid_at, bound = np.zeros(S, np.int64), np.zeros(S, bool)
        for sid, lane in self.router.sid_lane.items():
            sid_at[lane], bound[lane] = sid, True
        used = canon["bal_used"][:n]
        balances = dict(zip(aid_of[used].tolist(),
                            canon["bal"][:n][used].tolist()))

        def live(section, flag):
            # (flat index, values...) of a section's live entries
            idx_key, *keys = SQ.SPARSE_SECTIONS[section]
            if section in layout["sparse"]:
                return [np.asarray(canon[k]) for k in (idx_key, *keys)]
            idx = np.flatnonzero(canon[flag])
            return [idx] + [canon[k].reshape(-1)[idx] for k in keys]

        idx, amt, avail = live("positions", "pos_amt")
        lane, a = np.divmod(idx, A)
        # a position counts by its amount, as in _canon_to_export (the
        # snapshot keeps an amount of 0 with an available balance too)
        keep = (amt != 0) & bound[lane] & (a < n)
        positions = dict(zip(
            zip(aid_of[a[keep]].tolist(), sid_at[lane[keep]].tolist()),
            zip(amt[keep].tolist(), avail[keep].tolist())))
        idx, oid, aidx, price, size, _seq = live("books", "slot_used")
        lane = idx // (2 * N)
        keep = bound[lane]
        # an order as the auditor's shadow keeps it
        orders = dict(zip(oid[keep].tolist(), zip(
            aid_of[aidx[keep]].tolist(), sid_at[lane[keep]].tolist(),
            ((idx[keep] // N) % 2 == 0).tolist(), price[keep].tolist(),
            size[keep].tolist())))
        books = {sid: True for sid, lane in self.router.sid_lane.items()
                 if canon["book_exists"][lane]}
        return {"balances": balances, "positions": positions,
                "orders": orders, "books": books}

    def _canon_to_export(self, canon: dict) -> Dict[str, dict]:
        """Canonical engine export -> oracle-comparable dict view.
        Shared with SeqMeshSession, whose canon is stitched from
        per-shard exports through the placement table."""
        idx_to_aid = self.router.acct_of_idx()
        lane_to_sid = self.router.sid_of_lane()
        A = self.cfg.accounts
        balances = {idx_to_aid[i]: int(canon["bal"][i])
                    for i in range(len(idx_to_aid)) if canon["bal_used"][i]}
        positions = {}
        pos_amt = canon["pos_amt"].reshape(self.cfg.lanes, A)
        pos_avail = canon["pos_avail"].reshape(self.cfg.lanes, A)
        orders = {}
        S, _, N = canon["slot_oid"].shape
        for lane in range(S):
            sid = lane_to_sid.get(lane)
            if sid is None:
                continue
            for a in range(len(idx_to_aid)):
                if pos_amt[lane, a] != 0:
                    positions[(idx_to_aid[a], sid)] = (
                        int(pos_amt[lane, a]), int(pos_avail[lane, a]))
            for side in range(2):
                for nn in range(N):
                    if canon["slot_used"][lane, side, nn]:
                        orders[int(canon["slot_oid"][lane, side, nn])] = {
                            "aid": idx_to_aid[int(
                                canon["slot_aid"][lane, side, nn])],
                            "sid": sid,
                            "price": int(canon["slot_price"][lane, side, nn]),
                            "size": int(canon["slot_size"][lane, side, nn]),
                            "is_buy": side == 0,
                        }
        books = {sid: True for sid, lane in self.router.sid_lane.items()
                 if canon["book_exists"][lane]}
        return {"balances": balances, "positions": positions,
                "orders": orders, "books": books}

    def _export_state_java(self) -> Dict[str, dict]:
        """Java-mode stores, oracle-comparable: positions keyed by the
        raw 128-bit pairs (real AND Q11 garbage keys), orders with the
        original direction from the ba tag bit."""
        j = SQ.export_java(self.cfg, self.state)
        idx_to_aid = self.router.acct_of_idx()
        lane_to_sid = self.router.sid_of_lane()
        balances = {idx_to_aid[i]: int(j["bal"][i])
                    for i in range(len(idx_to_aid)) if j["bal_used"][i]}
        orders = {}
        S, _, N = j["slot_oid"].shape
        AM = (1 << 30) - 1
        for lane in range(S):
            sid = lane_to_sid.get(lane)
            if sid is None:
                continue
            for side in range(2):
                for nn in range(N):
                    if j["slot_size"][lane, side, nn] > 0:
                        ba = int(j["slot_ba"][lane, side, nn])
                        orders[int(j["slot_oid"][lane, side, nn])] = {
                            "aid": idx_to_aid[ba & AM],
                            "sid": sid,
                            "price": int(j["slot_price"][lane, side, nn]),
                            "size": int(j["slot_size"][lane, side, nn]),
                            "is_buy": (ba >> 30) & 1 == 1,
                        }
        books = {sid: True for sid, lane in self.router.sid_lane.items()
                 if j["book_exists"][lane]}
        return {"balances": balances, "positions": j["positions"],
                "orders": orders, "books": books}
