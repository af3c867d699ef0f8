"""The served host path's native calls (kme_host.cpp, kme_wire.cpp)
behind Python functions: one C++ call per stage of a batch — plan
(envelope check + route + H2D staging pack) on the way in, output
arrays -> byte-stream reconstruction on the way out — and the seqmesh
planner's two staging helpers. Each validates every buffer before it
crosses the ctypes boundary (native.check_buffer); the numpy forms in
runtime/seqsession.py remain the semantics authority
(tests/test_host_path.py pins the parity).
"""

from __future__ import annotations

import ctypes

import numpy as np

from kme_tpu.native import BoundaryError, check_buffer, load_library
from kme_tpu.wire import EnvelopeError


def _arr(ptr, n, dtype):
    if n == 0:
        return np.zeros(0, dtype)
    return np.ctypeslib.as_array(ptr, shape=(n,)).astype(dtype, copy=True)


def apply_placement(perm, lanes, s_local: int):
    """Apply the mesh planner's elastic placement table to a routed
    lane column in one vectorized pass: global lane -> global slot
    (`perm[lane]`), then (shard, local_row) = divmod(slot, s_local).

    This is the host-path mirror of SeqMeshSession.plan_windows'
    placement application (parallel/seqmesh.py); like plan_batch /
    recon_batch below, its eventual native home is kme_host.cpp —
    the numpy fancy-index form here is the semantics authority and is
    already allocation-light enough for the planner's hot scope.
    Returns (slot, shard, local_row), each shaped like `lanes`."""
    lanes64 = lanes.astype(np.int64, copy=False)
    slot = perm[lanes64]
    return slot, slot // s_local, slot % s_local


def slice_windows(wins: dict, win_idx, shard: int, shards: int,
                  bw: int) -> dict:
    """Slice ONE shard's rows for a set of windows out of the stacked
    (K, shards*bw) i32 scan-input planes into dense (kpad, bw) per-field
    segment planes, zero-padded to a pow2 window count (padding rows
    are all-zero NOP windows, a no-op through the kernel). This is the
    per-shard submission-queue staging step of the seqmesh async
    dispatcher — hot scope: one native call per field (kme_shard_slice,
    kme_host.cpp) with a byte-exact numpy-view fallback, no implicit
    host syncs or allocations beyond the output planes."""
    from kme_tpu.utils import pow2_bucket

    n = len(win_idx)
    kpad = pow2_bucket(max(n, 1), lo=1)
    idx = np.fromiter(win_idx, np.int64, n)
    out = {}
    lib = load_library()
    if lib is not None and hasattr(lib, "kme_shard_slice"):
        P32 = ctypes.POINTER(ctypes.c_int32)
        P64 = ctypes.POINTER(ctypes.c_int64)
        iptr = idx.ctypes.data_as(P64)
        for f, v in wins.items():
            src = check_buffer(f"slice_windows.{f}", v.reshape(-1),
                               np.int32, v.shape[0] * shards * bw)
            dst = np.zeros((kpad, bw), np.int32)
            lib.kme_shard_slice(
                src.ctypes.data_as(P32), v.shape[0], shards, bw,
                shard, iptr, n, kpad, dst.ctypes.data_as(P32))
            out[f] = dst
        return out
    for f, v in wins.items():
        dst = np.zeros((kpad, bw), np.int32)
        if n:
            dst[:n] = v.reshape(v.shape[0], shards, bw)[idx, shard]
        out[f] = dst
    return out


# -- batch host-path entry points (one C++ call per stage) ----------------
#
# kme_plan_batch / kme_recon_batch. plan_batch returns None when the
# loaded library predates its entry point, so its caller falls back to
# the numpy pack.


def plan_batch(router, batch, B: int):
    """Envelope-check + route + pack one WireBatch into the stacked
    (K, B) i32 scan-input planes in a single native call. `router` must
    be a NativeSeqRouter (the caller checks); returns
    (cols, host_rejects, stacked, cnts, K) with SeqSession._plan's
    exact contract, or None when unavailable. The stacked planes are
    zero-copy views into a rotating native buffer (4 deep): each is
    consumed by the very next jit dispatch, and double-buffered serving
    keeps at most two packed batches in flight."""
    lib = router._lib
    if not hasattr(lib, "kme_plan_batch"):
        return None
    pack = ensure_pack(router)
    # kme_plan_batch reads batch.n int64s from every column with no
    # native-side length check: pin the dtype at conversion and verify
    # the element count BEFORE handing out pointers
    raw = {f: check_buffer(
               f"plan_batch.{f}",
               np.ascontiguousarray(getattr(batch, f), np.int64),
               np.int64, batch.n)
           for f in ("action", "oid", "aid", "sid", "price", "size")}
    P64 = ctypes.POINTER(ctypes.c_int64)
    K = int(lib.kme_plan_batch(
        pack, router._h, batch.n,
        *(raw[f].ctypes.data_as(P64)
          for f in ("action", "oid", "aid", "sid", "price", "size")),
        B))
    return collect_plan(lib, router, pack, K, B, raw["price"],
                        raw["size"])


def ensure_pack(router):
    """The router's cached native pack handle (kme_pack_new), created
    on first use and freed with the router. Shared by plan_batch and
    the front-door acceptor (bridge/front.py accept_frames), which
    chains kme_plan_batch inside its single kme_front_accept call."""
    lib = router._lib
    pack = getattr(router, "_pack", None)
    if pack is None:
        import weakref

        pack = lib.kme_pack_new()
        router._pack = pack
        router._pack_fin = weakref.finalize(router, lib.kme_pack_free,
                                            pack)
    return pack


def collect_plan(lib, router, pack, K, B, price, size):
    """Shared tail of the native plan: map the result code K to the
    EnvelopeError/CapacityError contract and read back routed columns +
    packed planes. `price`/`size` are the int64 input columns,
    consulted only for the envelope error message."""
    if K == -3:
        i = int(lib.kme_pack_err_index(pack))
        raise EnvelopeError(
            f"message {i}: price/size outside int32 "
            f"(price={int(price[i])}, "
            f"size={int(size[i])})")
    if K < 0:
        # the routers' error, defined beside them (a lower layer
        # raises what its caller catches)
        from kme_tpu.runtime.seqsession import CapacityError

        raise CapacityError(
            f"{'account' if K == -1 else 'symbol'} capacity "
            f"exhausted (id={lib.kme_router_err_value(router._h)})")
    h = router._h
    nr = int(lib.kme_router_n_routed(h))
    nj = int(lib.kme_router_n_rejects(h))
    cols = {
        "msg_index": _arr(lib.kme_router_o_msg(h), nr, np.int64),
        "act": _arr(lib.kme_router_o_act(h), nr, np.int32),
        "aid": _arr(lib.kme_router_o_aidx(h), nr, np.int32),
        "price": _arr(lib.kme_router_o_price(h), nr, np.int32),
        "size": _arr(lib.kme_router_o_size(h), nr, np.int32),
        "lane": _arr(lib.kme_router_o_lane(h), nr, np.int32),
        "oid": _arr(lib.kme_router_o_oid(h), nr, np.int64),
    }
    host_rejects = set(_arr(lib.kme_router_o_rej(h), nj,
                            np.int64).tolist())
    planes = np.ctypeslib.as_array(lib.kme_pack_planes(pack),
                                   shape=(7, K, B))
    stacked = {name: planes[j] for j, name in enumerate(
        ("act", "aid", "price", "size", "lane", "oid_lo", "oid_hi"))}
    cnts = [max(min(B, nr - ci * B), 0) for ci in range(K)]
    return cols, host_rejects, stacked, cnts, K


def recon_batch(lib, handle, batch, cols, host, fills, idx2aid):
    """One-pass native reconstruction (kme_recon_batch): batch columns
    + routed rows + device results -> the byte-exact record stream,
    in one merge walk, no per-message numpy scatter. Returns (buf,
    line_off, msg_lines) like SeqSession.process_wire_buffer."""
    c = ctypes
    P64 = c.POINTER(c.c_int64)
    P32 = c.POINTER(c.c_int32)
    PU8 = c.POINTER(c.c_uint8)
    pp = lambda a, t: a.ctypes.data_as(t)
    i64 = lambda a: np.ascontiguousarray(a, np.int64)
    nmsg = batch.n
    nr = len(cols["msg_index"])
    # kme_recon_batch reads the m_* columns to nmsg and the r_*/h_*
    # rows to nr unconditionally (kme_wire.cpp): every pointer below is
    # validated for dtype/contiguity/length first, so a short or
    # mis-typed buffer raises here instead of overreading native-side
    for f in ("action", "oid", "aid", "sid", "price", "size", "next",
              "prev"):
        check_buffer(f"recon_batch.{f}", getattr(batch, f),
                     np.int64, nmsg)
    for f in ("hnext", "hprev"):
        check_buffer(f"recon_batch.{f}", getattr(batch, f),
                     np.uint8, nmsg)
    r_msg = i64(cols["msg_index"])
    r_act = np.ascontiguousarray(cols["act"], np.int32)
    h_ok = np.ascontiguousarray(host["ok"], np.uint8)
    h_append = np.ascontiguousarray(host["append"], np.uint8)
    h_nfill, h_resid, h_prev = (i64(host[k]) for k in
                                ("nfill", "residual", "prev_oid"))
    check_buffer("recon_batch.cols.act", r_act, np.int32, nr)
    for nm, a in (("host.ok", h_ok), ("host.append", h_append)):
        check_buffer(f"recon_batch.{nm}", a, np.uint8, nr)
    for nm, a in (("host.nfill", h_nfill), ("host.residual", h_resid),
                  ("host.prev_oid", h_prev)):
        check_buffer(f"recon_batch.{nm}", a, np.int64, nr)
    check_buffer("recon_batch.idx2aid", idx2aid, np.int64)
    if fills.ndim != 2 or fills.shape[0] != 4:
        raise BoundaryError(
            f"recon_batch.fills: expected shape (4, F), got "
            f"{fills.shape}")
    f_oid, f_aidx, f_price, f_size = (
        check_buffer(f"recon_batch.fills[{j}]", i64(fills[j]),
                     np.int64, fills.shape[1]) for j in range(4))
    rc = lib.kme_recon_batch(
        nmsg, pp(batch.action, P64), pp(batch.oid, P64),
        pp(batch.aid, P64), pp(batch.sid, P64), pp(batch.price, P64),
        pp(batch.size, P64), pp(batch.next, P64),
        pp(batch.hnext, PU8), pp(batch.prev, P64),
        pp(batch.hprev, PU8),
        nr, pp(r_msg, P64), pp(r_act, P32),
        pp(h_ok, PU8), pp(h_nfill, P64), pp(h_resid, P64),
        pp(h_prev, P64), pp(h_append, PU8),
        len(idx2aid), pp(idx2aid, P64),
        fills.shape[1], pp(f_oid, P64), pp(f_aidx, P64),
        pp(f_price, P64), pp(f_size, P64), handle)
    if rc != 0:
        raise RuntimeError(f"kme_recon_batch failed rc={rc}")
    blen = lib.kme_recon_len(handle)
    nlines = lib.kme_recon_n_lines(handle)
    buf = c.string_at(lib.kme_recon_buf(handle), blen)
    line_off = np.empty(nlines + 1, np.int64)
    line_off[:nlines] = np.ctypeslib.as_array(
        lib.kme_recon_line_off(handle), (nlines,))
    line_off[nlines] = blen
    msg_lines = np.ctypeslib.as_array(
        lib.kme_recon_msg_lines(handle), (nmsg,)).copy()
    return buf, line_off, msg_lines
