"""Native host-runtime bindings: build-on-demand C++ via ctypes.

The C++ sources compile once per source hash with the system toolchain
(g++) into a cached shared object next to the package. `KME_NATIVE=0`
disables the native path outright (the pure-Python twins are the test
references). `load_library()` also returns None when no compiler is
available; the served seq path does not accept that silently — it
calls `require_library()`.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import tempfile
from typing import Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRCS = (os.path.join(_HERE, "kme_host.cpp"),
         os.path.join(_HERE, "kme_oracle.cpp"),
         os.path.join(_HERE, "kme_wire.cpp"),
         os.path.join(_HERE, "kme_router.cpp"),
         os.path.join(_HERE, "kme_front.cpp"))

_lib = None
_lib_tried = False


class BoundaryError(ValueError):
    """A buffer about to cross the ctypes boundary is the wrong shape,
    dtype, length, or layout. The C side reads exactly the lengths it
    is told (kme_wire.cpp reads m_* to nmsg and r_*/h_* to nr with no
    way to check), so a short or mis-typed buffer is a native-side
    overread — this is raised Python-side instead."""


def check_buffer(name, arr, dtype, n=None):
    """Validate one array for a native call: exact dtype, C-contiguous,
    1-D, and (when given) at least `n` elements. Returns the array so
    call sites can validate inline."""
    import numpy as np

    if not isinstance(arr, np.ndarray):
        raise BoundaryError(
            f"{name}: expected ndarray, got {type(arr).__name__}")
    if arr.dtype != np.dtype(dtype):
        raise BoundaryError(
            f"{name}: dtype {arr.dtype} != required {np.dtype(dtype)}")
    if arr.ndim != 1:
        raise BoundaryError(f"{name}: expected 1-D, got shape "
                            f"{arr.shape}")
    if not arr.flags["C_CONTIGUOUS"]:
        raise BoundaryError(f"{name}: buffer is not C-contiguous")
    if n is not None and arr.shape[0] < n:
        raise BoundaryError(
            f"{name}: {arr.shape[0]} element(s), native call reads "
            f"{n} — short buffer would be an overread")
    return arr


def _build(srcs, out: str) -> bool:
    cmd = (["g++", "-O3", "-shared", "-fPIC", "-std=c++17"] + list(srcs)
           + ["-o", out])
    try:
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        print(f"kme_tpu.native: build failed ({e}); using the pure-Python "
              f"fallback", file=sys.stderr)
        return False
    if r.returncode != 0:
        print(f"kme_tpu.native: g++ failed:\n{r.stderr[:2000]}\n"
              f"using the pure-Python fallback", file=sys.stderr)
        return False
    return True


def load_library() -> Optional[ctypes.CDLL]:
    """The compiled host-runtime library, building it if needed.
    None when disabled or unbuildable (callers fall back to Python)."""
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    if os.environ.get("KME_NATIVE", "1") == "0":
        return None
    override = os.environ.get("KME_NATIVE_SO")
    if override:
        # explicit prebuilt library (sanitizer runs: scripts/
        # build_native.py --sanitize emits an ASan/UBSan .so whose tag
        # can't live in the normal cache); missing/unloadable is an
        # ERROR, not a fallback — a sanitizer run that silently used
        # the plain build would prove nothing
        try:
            _lib = _bind(ctypes.CDLL(override))
        except OSError as e:
            raise OSError(
                f"KME_NATIVE_SO={override} could not be loaded: {e}")
        return _lib
    try:
        h = hashlib.sha256()
        for src in _SRCS:
            with open(src, "rb") as f:
                h.update(f.read())
        tag = h.hexdigest()[:16]
    except OSError as e:
        print(f"kme_tpu.native: source unreadable ({e}); the native "
              f"runtime is DISABLED — using the pure-Python fallbacks",
              file=sys.stderr)
        return None
    build_dir = os.path.join(_HERE, "_build")
    so_path = os.path.join(build_dir, f"kme_host_{tag}.so")
    if not os.path.exists(so_path):
        try:
            os.makedirs(build_dir, exist_ok=True)
            # build into a temp name then rename: concurrent processes
            # race benignly (os.replace is atomic)
            fd, tmp = tempfile.mkstemp(suffix=".so", dir=build_dir)
            os.close(fd)
            built = _build(_SRCS, tmp)
            if built:
                os.replace(tmp, so_path)
            else:
                os.unlink(tmp)
                return None
        except OSError as e:  # read-only install dir etc.
            print(f"kme_tpu.native: cannot build ({e}); using the "
                  f"pure-Python fallback", file=sys.stderr)
            return None
    try:
        _lib = _bind(ctypes.CDLL(so_path))
    except OSError as e:
        print(f"kme_tpu.native: dlopen failed ({e}); using the pure-Python "
              f"fallback", file=sys.stderr)
        _lib = None
    return _lib


def require_library() -> Optional[ctypes.CDLL]:
    """load_library() for the served path: None ONLY under an explicit
    KME_NATIVE=0. A library that could not be built or loaded raises —
    kme-serve must not drop to the Python twins (several times slower,
    and no pipelined serving) behind the operator's back."""
    lib = load_library()
    if lib is None and os.environ.get("KME_NATIVE", "1") != "0":
        raise RuntimeError(
            "kme_tpu.native: the host runtime library could not be built "
            "or loaded (g++ output above); set KME_NATIVE=0 to run the "
            "pure-Python twins on purpose")
    return lib


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    P64, P32 = c.POINTER(c.c_int64), c.POINTER(c.c_int32)
    sigs = {
        # native quirk-exact engine (kme_oracle.cpp)
        "kme_oracle_new": ([c.c_int32, c.c_int32, c.c_int64, c.c_int32,
                            c.c_int64], c.c_void_p),
        "kme_oracle_free": ([c.c_void_p], None),
        "kme_oracle_process": ([c.c_void_p, c.c_int64] + [P64] * 6
                               + [P64, c.POINTER(c.c_uint8),
                                  P64, c.POINTER(c.c_uint8)], c.c_int32),
        "kme_oracle_err_index": ([c.c_void_p], c.c_int64),
        "kme_oracle_err_msg": ([c.c_void_p], c.c_char_p),
        "kme_oracle_out_buf": ([c.c_void_p], c.c_void_p),
        "kme_oracle_out_len": ([c.c_void_p], c.c_int64),
        "kme_oracle_line_counts": ([c.c_void_p], P64),
        "kme_oracle_n_processed": ([c.c_void_p], c.c_int64),
        "kme_oracle_dump_state": ([c.c_void_p], c.c_char_p),
        "kme_oracle_load_state": ([c.c_void_p, c.c_char_p], c.c_int32),
        # native seq router (kme_router.cpp)
        "kme_router_new": ([c.c_int64, c.c_int64], c.c_void_p),
        "kme_router_free": ([c.c_void_p], None),
        "kme_router_route": ([c.c_void_p, c.c_int64] + [P64] * 6,
                             c.c_int32),
        "kme_router_n_routed": ([c.c_void_p], c.c_int64),
        "kme_router_n_rejects": ([c.c_void_p], c.c_int64),
        "kme_router_err_value": ([c.c_void_p], c.c_int64),
        "kme_router_o_msg": ([c.c_void_p], P64),
        "kme_router_o_oid": ([c.c_void_p], P64),
        "kme_router_o_act": ([c.c_void_p], P32),
        "kme_router_o_aidx": ([c.c_void_p], P32),
        "kme_router_o_price": ([c.c_void_p], P32),
        "kme_router_o_size": ([c.c_void_p], P32),
        "kme_router_o_lane": ([c.c_void_p], P32),
        "kme_router_o_rej": ([c.c_void_p], P64),
        "kme_router_n_accounts": ([c.c_void_p], c.c_int64),
        "kme_router_n_symbols": ([c.c_void_p], c.c_int64),
        "kme_router_n_routes": ([c.c_void_p], c.c_int64),
        "kme_router_export_accounts": ([c.c_void_p, P64, P32], None),
        "kme_router_export_symbols": ([c.c_void_p, P64, P32], None),
        "kme_router_export_routes": ([c.c_void_p, P64, P64], None),
        "kme_router_import_accounts": ([c.c_void_p, c.c_int64, P64, P32],
                                       None),
        "kme_router_import_symbols": ([c.c_void_p, c.c_int64, P64, P32],
                                      None),
        "kme_router_import_routes": ([c.c_void_p, c.c_int64, P64, P64],
                                     None),
        # the symbol lifecycle (SeqRouter's): counts and delisted ids
        "kme_router_stats": ([c.c_void_p, P64, P64], None),
        # an order's route leaves when the order left the book
        # (its seven array pointers go as plain addresses: the call is
        # made once a batch on the serve loop, and building seven typed
        # pointer objects cost more than the walk itself)
        "kme_router_drop_batch": (
            [c.c_void_p, c.c_int64] + [c.c_void_p] * 6
            + [c.c_int64, c.c_void_p, c.c_int64, c.c_void_p], c.c_int32),
        "kme_router_n_delisted": ([c.c_void_p], c.c_int64),
        "kme_router_export_delisted": ([c.c_void_p, P64], None),
        "kme_router_import_delisted": ([c.c_void_p, c.c_int64, P64],
                                       None),
        # consistent-hash group assignment (kme_router.cpp, stateless)
        "kme_group_assign": ([c.c_int64, P64, c.c_int32, c.c_int64,
                              P32], None),
        # native wire reconstruction (kme_wire.cpp)
        "kme_recon_new": ([], c.c_void_p),
        "kme_recon_free": ([c.c_void_p], None),
        "kme_recon_buf": ([c.c_void_p], c.c_void_p),
        "kme_recon_len": ([c.c_void_p], c.c_int64),
        "kme_recon_n_lines": ([c.c_void_p], c.c_int64),
        "kme_recon_line_off": ([c.c_void_p], P64),
        "kme_recon_msg_lines": ([c.c_void_p], P32),
        # native batch plan + H2D pack (kme_host.cpp kme_pack_*)
        "kme_pack_new": ([], c.c_void_p),
        "kme_pack_free": ([c.c_void_p], None),
        "kme_plan_batch": ([c.c_void_p, c.c_void_p, c.c_int64]
                           + [P64] * 6 + [c.c_int32], c.c_int64),
        "kme_pack_planes": ([c.c_void_p], P32),
        "kme_pack_err_index": ([c.c_void_p], c.c_int64),
        # per-shard async-dispatch window slicing (kme_host.cpp)
        "kme_shard_slice": ([P32] + [c.c_int64] * 4 + [P64]
                            + [c.c_int64] * 2 + [P32], None),
        # native one-pass batch reconstruction (kme_wire.cpp)
        "kme_recon_batch": ([c.c_int64] + [P64] * 6
                            + [P64, c.POINTER(c.c_uint8)] * 2
                            + [c.c_int64, P64, P32]
                            + [c.POINTER(c.c_uint8), P64, P64, P64,
                               c.POINTER(c.c_uint8)]
                            + [c.c_int64, P64]
                            + [c.c_int64] + [P64] * 4 + [c.c_void_p],
                            c.c_int32),
        # native wire parsing (kme_wire.cpp kme_parse_*)
        "kme_parse_new": ([], c.c_void_p),
        "kme_parse_free": ([c.c_void_p], None),
        "kme_parse_lines": ([c.c_void_p, c.c_char_p, c.c_int64],
                            c.c_int64),
        "kme_parse_col": ([c.c_void_p, c.c_int32], P64),
        "kme_parse_hnext": ([c.c_void_p], c.POINTER(c.c_uint8)),
        "kme_parse_hprev": ([c.c_void_p], c.POINTER(c.c_uint8)),
        "kme_parse_tid": ([c.c_void_p], P64),
        "kme_parse_htid": ([c.c_void_p], c.POINTER(c.c_uint8)),
        # binary order frames + canonical-JSON emission (kme_wire.cpp)
        "kme_parse_frames": ([c.c_void_p, c.c_char_p, c.c_int64],
                             c.c_int64),
        "kme_parse_err_off": ([c.c_void_p], c.c_int64),
        "kme_parse_emit": ([c.c_void_p], c.c_int64),
        "kme_parse_emit_buf": ([c.c_void_p], c.c_void_p),
        "kme_parse_emit_off": ([c.c_void_p], P64),
        # a stamped run of output records as one buffer (kme_wire.cpp
        # kme_run_*): the broker's durable rows and fetch_bin's reply
        # rows, each into a buffer of the calling thread. Array
        # pointers go as plain addresses, as kme_router_drop_batch's do
        "kme_run_split": ([c.c_char_p, c.c_void_p, c.c_int64,
                           c.c_void_p], None),
        "kme_run_rows": ([c.c_char_p, c.c_void_p, c.c_void_p]
                         + [c.c_int64] * 4, c.c_int64),
        "kme_run_pack": ([c.c_char_p, c.c_void_p, c.c_void_p]
                         + [c.c_int64] * 6, c.c_int64),
        "kme_run_out": ([], c.c_void_p),
        # a collected batch's buffer -> the flight recorder's 96-byte
        # records (kme_wire.cpp kme_journal_rows); addresses as above
        "kme_journal_rows": ([c.c_char_p, c.c_int64, c.c_void_p,
                              c.c_int64] + [c.c_void_p] * 3
                             + [c.c_int64] * 2 + [c.c_int32] * 2
                             + [c.c_void_p], c.c_int64),
        # native front-door acceptor (kme_front.cpp): validate + route
        # + plan in one call per batch
        "kme_front_new": ([], c.c_void_p),
        "kme_front_free": ([c.c_void_p], None),
        "kme_front_accept": ([c.c_void_p, c.c_char_p, c.c_int64,
                              c.c_int32, c.c_int64, c.c_int64,
                              c.c_void_p, c.c_void_p, c.c_int32],
                             c.c_int64),
        "kme_front_groups": ([c.c_void_p], P32),
        "kme_front_plan_k": ([c.c_void_p], c.c_int64),
        "kme_front_err_off": ([c.c_void_p], c.c_int64),
        "kme_front_col": ([c.c_void_p, c.c_int32], P64),
        "kme_front_hnext": ([c.c_void_p], c.POINTER(c.c_uint8)),
        "kme_front_hprev": ([c.c_void_p], c.POINTER(c.c_uint8)),
        "kme_front_tid": ([c.c_void_p], P64),
        "kme_front_htid": ([c.c_void_p], c.POINTER(c.c_uint8)),
        "kme_front_json": ([c.c_void_p], c.c_int64),
        "kme_front_json_buf": ([c.c_void_p], c.c_void_p),
        "kme_front_json_off": ([c.c_void_p], P64),
    }
    for name, (argtypes, restype) in sigs.items():
        fn = getattr(lib, name)
        fn.argtypes = argtypes
        fn.restype = restype
    return lib
