"""Native C++ router vs the Python semantics authority.

NativeSeqRouter (native/kme_router.cpp) must route IDENTICALLY to
SeqRouter (runtime/seqsession.py) — every column, every host reject,
every id map and counter — on representative workloads, raise the same
capacity/envelope errors, and round-trip its id-space state through
the checkpoint surface. (tests/test_seq_engine.py::
test_native_router_matches_python holds the edge-case stream.)"""

import numpy as np
import pytest

import kme_tpu.opcodes as op
from kme_tpu.native import load_library
from kme_tpu.runtime.seqsession import (ROUTER_STATS, CapacityError,
                                        EnvelopeError, NativeSeqRouter,
                                        SeqRouter)
from kme_tpu.wire import OrderMsg
from kme_tpu.workload import (cancel_heavy_stream, harness_stream,
                              zipf_symbol_stream)

if load_library() is None:
    import os
    import shutil

    if os.environ.get("KME_NATIVE") == "0":
        # deliberate disable (the fallback tier-1 leg), not a build
        # failure — these tests compare native vs Python, so there is
        # nothing to test
        pytest.skip("native explicitly disabled (KME_NATIVE=0)",
                    allow_module_level=True)
    if shutil.which("g++"):
        pytest.fail("g++ is available but the native library failed to "
                    "build — a real regression, not a missing toolchain "
                    "(rerun with the kme_tpu.native build stderr)")
    pytest.skip("native library unavailable (no toolchain)",
                allow_module_level=True)

# the routers' clocks differ; every other count is a function of the
# stream
_COUNTED = [i for i, k in enumerate(ROUTER_STATS) if k != "route_purge_ns"]


def _pair(lanes, accounts):
    return SeqRouter(lanes, accounts), \
        NativeSeqRouter(lanes, accounts, load_library())


def assert_same_maps(py, cc):
    assert py.aid_idx == cc.aid_idx
    assert py.sid_lane == cc.sid_lane
    assert py.delisted == cc.delisted
    assert py.oid_sid == cc.oid_sid
    sp, sc = py.stats(), cc.stats()
    assert [sp[i] for i in _COUNTED] == [sc[i] for i in _COUNTED]


def assert_same_routes(msgs, lanes, accounts, chunk=None, routers=None):
    py, cc = routers or _pair(lanes, accounts)
    chunk = chunk or len(msgs)
    for lo in range(0, len(msgs), chunk):   # id maps persist across plans
        cp, rp = py.route(msgs[lo:lo + chunk])
        cn, rn = cc.route(msgs[lo:lo + chunk])
        assert cp.keys() == cn.keys()
        for k in cp:
            assert cp[k].dtype == cn[k].dtype
            assert np.array_equal(cp[k], cn[k]), f"col {k}@{lo} differs"
        assert rp == rn
    assert_same_maps(py, cc)
    return py, cc


def test_routes_identical_harness():
    msgs = harness_stream(1500, seed=3, num_symbols=4, num_accounts=8,
                          payout_opcode_bug=False, validate=True)
    py, _ = assert_same_routes(msgs, 8, 16)
    assert len(py.oid_sid) > 0 and py.stats()[0] > 0


def test_routes_identical_zipf_with_barriers():
    msgs = zipf_symbol_stream(2000, num_symbols=16, num_accounts=32, seed=9,
                              zipf_a=1.1, payout_per_mille=5)
    py, _ = assert_same_routes(msgs, 16, 64)
    settled = py.stats()[ROUTER_STATS.index("symbols_settled")]
    assert settled > 0      # lanes went back to the pool and out again
    assert py.stats()[ROUTER_STATS.index("lanes_reused")] > 0


def test_routes_identical_cancel_heavy_multi_batch():
    msgs = cancel_heavy_stream(1500, num_symbols=8, num_accounts=16, seed=4)
    py, _ = assert_same_routes(msgs, 8, 32, chunk=400)
    assert py.stats()[ROUTER_STATS.index("cancels_routed")] > 100


@pytest.mark.parametrize("router", ["python", "native"])
def test_router_errors_match(router):
    make = lambda lanes, accounts: _pair(lanes, accounts)[router == "native"]
    with pytest.raises(CapacityError, match="symbol capacity"):
        make(2, 2).route([OrderMsg(action=op.ADD_SYMBOL, sid=s)
                          for s in range(3)])
    with pytest.raises(CapacityError, match="account capacity"):
        make(8, 1).route([OrderMsg(action=op.CREATE_BALANCE, aid=a)
                          for a in range(2)])
    r = make(8, 8)
    with pytest.raises(EnvelopeError, match="message 1: price/size"):
        r.route([OrderMsg(action=op.CREATE_BALANCE, aid=1),
                 OrderMsg(action=op.BUY, oid=1, aid=1, sid=0,
                          price=2**31, size=1)])
    # the envelope is checked for the whole batch up front: the id maps
    # are untouched
    assert r.aid_idx == {}


def test_routes_identical_extreme_ids():
    """Java-long id wrapping at the router boundary: out-of-int64
    aids/sids/oids and INT64_MIN payout targets route identically (the
    native router hands such a call to a Python router and takes its
    maps back)."""
    big = 2**63
    msgs = [
        OrderMsg(action=op.CREATE_BALANCE, aid=big),       # wraps to -2^63
        OrderMsg(action=op.CREATE_BALANCE, aid=-big),      # same account
        OrderMsg(action=op.TRANSFER, aid=big, size=1000),
        OrderMsg(action=op.ADD_SYMBOL, sid=2**63 - 1),
        OrderMsg(action=op.BUY, oid=2**64 + 7, aid=big, sid=2**63 - 1,
                 price=50, size=2),
        OrderMsg(action=op.CANCEL, oid=7, aid=big),        # wrapped route
        OrderMsg(action=op.PAYOUT, sid=-big, size=97),     # abs(INT64_MIN)
        OrderMsg(action=2**70, aid=1),                     # unknown opcode
    ]
    py, cc = assert_same_routes(msgs, 4, 4)
    assert py.aid_idx == {-big: 0} and py.oid_sid == {7: 2**63 - 1}
    # and the next call is native again, from the maps it took back
    more = [OrderMsg(action=op.CANCEL, oid=7, aid=-big),
            OrderMsg(action=op.PAYOUT, sid=2**63 - 1, size=97)]
    assert_same_routes(more, 4, 4, routers=(py, cc))
    assert py.oid_sid == {} and py.sid_lane == {}


def test_native_state_roundtrip():
    """The checkpoint surface: export the id maps, import into a fresh
    native router, and routing continues identically."""
    msgs = harness_stream(800, seed=7, num_symbols=4, num_accounts=8,
                          payout_opcode_bug=False, validate=True)
    py, cc = assert_same_routes(msgs[:500], 8, 16)
    state = (cc.aid_idx, cc.sid_lane, cc.delisted, cc.routes_arrays())

    _, cc2 = _pair(8, 16)
    cc2.aid_idx, cc2.sid_lane, cc2.delisted = state[:3]
    cc2.import_routes(*state[3])
    cp, rp = py.route(msgs[500:])
    cn, rn = cc2.route(msgs[500:])
    for k in cp:
        assert np.array_equal(cp[k], cn[k]), f"col {k} differs"
    assert rp == rn
    assert (py.aid_idx, py.sid_lane, py.delisted, py.oid_sid) \
        == (cc2.aid_idx, cc2.sid_lane, cc2.delisted, cc2.oid_sid)
