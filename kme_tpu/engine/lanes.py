"""Throughput engine: vmapped per-symbol order-book lanes.

This is the TPU-first redesign of the matching core (SURVEY.md §7 design
stance): the reference's KV-stores + intrusive linked lists
(KProcessor.java:30-49, 448-475) dissolve into dense per-lane arrays, and
the per-message match loop (KProcessor.java:237-258) becomes a
sort + prefix-sum *sweep* — no data-dependent loop, constant work per
step, everything vectorized over S symbol lanes.

Semantics: compat='fixed' exactly (the corrected reference semantics the
scalar oracle defines — kme_tpu/oracle/engine.py docstring). Java-quirk
parity is the serial parity engine's job; this engine is the performance
path. The one observable java-era behavior kept is the Q9 prev-echo leak
(appending to a non-empty price bucket stamps the bucket tail's oid into
the echoed order), which `compat=fixed` preserves.

Exact-parallelism model (SURVEY.md §7 H1). A key structural fact of the
reference: maker fills carry price 0 (KProcessor.java:268-271), so
`fillOrder` credits `size * 0 == 0` to maker balances — balances are
mutated ONLY by their own account's messages (margin reserve/release,
taker credit, transfers) plus the rare PAYOUT. Therefore a parallel step
that (a) keeps per-symbol arrival order within its lane, (b) never
schedules two messages from the same account, and (c) isolates
PAYOUT/REMOVE_SYMBOL as barrier steps, is *bit-exact* with serial replay.
The host sequencer (kme_tpu/runtime/sequencer.py) enforces (a)-(c).

Data layout per lane (S = lanes, N = slots/side, A = dense accounts):
- book slots (S, 2, N): oid i64, aid-index i32, price i32, size i32,
  seqno i32 (FIFO arrival stamp), used bool. Price-time priority is the
  scalar key `price * 2^32 + seqno` (ask side; bid side uses 125-price),
  so "best maker" is one masked argsort — the bitmap+bucket+linked-list
  machinery (KProcessor.java:359-416) has no equivalent here.
- positions (S, A): amount i64, available i64, used bool — dense by
  (lane, account), so maker-position scatter needs no associative probe.
- balances (A,) + used (A,): replicated across shards; per-step deltas
  are scattered densely and (under shard_map) psum-merged — disjointness
  is guaranteed by the scheduler, so the merge is exact.

Fills are emitted as compact per-step arrays (maker oid/aid/price + fill
size, in priority order); the host reconstructs the byte-exact
IN/fill/OUT record stream (maker event before taker event per trade,
KProcessor.java:265-274).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import kme_tpu._jaxsetup  # noqa: F401
import jax
import jax.numpy as jnp


_I64 = jnp.int64
_I32 = jnp.int32

# dense lane op codes (host-side sequencer packs these)
L_NOP = 0
L_BUY = 1
L_SELL = 2
L_CANCEL = 3
L_CREATE = 4
L_TRANSFER = 5
L_ADD_SYMBOL = 6

# lane error codes (sticky, per batch). Book/fill CAPACITY overflow is
# NOT an error: it is a per-message REJECT (the H2/H3 envelope policy —
# the offending order is refused as a unit, surfaced as an OUT REJECT in
# the wire stream, and the batch continues). Only the host-side fill-log
# sizing knob remains a sticky error, since it is a session buffer bound,
# not an engine-semantics bound.
LERR_OK = 0
LERR_FILLBUF_FULL = 3  # session fill log exhausted (fill_buffer knob)

# on-device metrics counters (state["metrics"], int64, accumulated in
# the scan carry and psum-merged under sharding — SURVEY.md §5's
# replacement for the reference's untouched JMX metrics)
MET_MSGS = 0            # device-executed messages (non-NOP)
MET_TRADES_OK = 1       # accepted BUY/SELL
MET_FILLS = 2           # fill events (maker count)
MET_CONTRACTS = 3       # contracts traded (sum of fill sizes)
MET_REJ_CAPACITY = 4    # H2/H3 envelope rejects
MET_REJ_RISK = 5        # margin/validation rejects
MET_RESTED = 6          # orders appended to a book
MET_CANCELS_OK = 7
MET_REJ_CANCEL = 8
MET_TRANSFERS_OK = 9
MET_REJ_OTHER = 10      # failed create/transfer/add_symbol
MET_BARRIERS = 11       # payout/remove settles executed
N_METRICS = 12

METRIC_NAMES = ("msgs", "trades_ok", "fills", "contracts", "rej_capacity",
                "rej_risk", "rested", "cancels_ok", "rej_cancel",
                "transfers_ok", "rej_other", "barriers")

# on-device distribution histograms (state["hist"]): power-of-two
# buckets accumulated next to the metrics counters and fetched in the
# same device transfer (no extra round-trips). Bucket index for value
# v is #{k in 0..14 : v >= 2^k}: v <= 0 -> bucket 0, v == 1 -> 1,
# v in [2^(i-1), 2^i) -> i, v >= 2^14 -> 15.
HIST_FILLS = 0        # makers swept per ACCEPTED trade (0 = pure rest)
HIST_DEPTH = 1        # resting orders (both sides) in the touched book
#                       after each accepted trade/cancel
HIST_OCCUPANCY = 2    # non-NOP messages per dispatch unit (scan step /
#                       seq kernel call); empty units are unobserved
N_HIST = 3
N_HIST_BUCKETS = 16

HIST_NAMES = ("fills_per_order", "book_depth", "batch_occupancy")

_HIST_THRESH = tuple(1 << k for k in range(N_HIST_BUCKETS - 1))


def hist_bucket(v):
    """Power-of-two bucket index (vectorized, any int shape)."""
    thr = jnp.asarray(_HIST_THRESH, _I32)
    return jnp.sum(v[..., None] >= thr, axis=-1).astype(_I32)


@dataclasses.dataclass(frozen=True)
class LaneConfig:
    """Static shapes; one XLA program per distinct value."""

    lanes: int = 8            # S — symbols (sharded axis)
    slots: int = 128          # N — resting orders per book side
    accounts: int = 256      # A — dense account capacity
    max_fills: int = 16       # E — makers swept per taker (H3 bound)
    steps: int = 64           # T bucket granularity of a dispatch window
    window: int = 1024        # max scan steps per dispatch (HBM bound)
    fill_buffer: int = 1 << 20  # device fill ring capacity (H3 envelope)
    # width > 0 enables ACTIVE-LANE COMPACTION: each scan step computes
    # at width W (the at-most-W lanes the scheduler placed in the step)
    # instead of full S — book rows are gathered/scattered by lane id
    # and position ops use flat lane*A+acc indices, so per-step work is
    # O(W·N + W·E) instead of O(S·(N+A)). Profiled on v5e: the full-
    # width step spends >85% of its time on (S,2E)->(S,A) scatters and
    # (S,N) gathers for lanes that are pure padding. The LAST device
    # lane is reserved as the padding scrap row (LaneSession sizes the
    # device state to lanes+1). Single-device only; the sharded path
    # ignores width.
    width: int = 0            # W — max active lanes per scan step
    # scan-body unroll factor: amortizes XLA loop overhead and lets the
    # compiler fuse across adjacent steps; shapes are unchanged
    unroll: int = 1
    # pos_dma (compact mode only): positions live as PLANAR lo/hi int32
    # rows (S, 2A/128, 128) updated IN PLACE by Pallas row-DMA kernels
    # (ops/rowdma.py) instead of flat (S*A,) int64 arrays rewritten
    # whole by XLA scatter (~24us/step at the bench shapes vs ~2.7us
    # for the DMA round trip — measured, scripts/exp_pallas_rowdma.py).
    # Requires accounts % 64 == 0 (128-lane row tiles). LaneSession
    # enables it automatically; snapshots stay canonical (flat s64).
    pos_dma: bool = False


def _fill_slack(cfg: LaneConfig) -> int:
    """Slack columns past the fill-log overflow watermark (see the
    fillbuf note in make_lane_state). Compact mode's block append can
    write up to one full (M*E,) window block starting at the watermark;
    M is bucketed to a power of two over at most window*width slots."""
    if cfg.width <= 0:
        return 1
    from kme_tpu.utils import pow2_bucket

    return pow2_bucket(cfg.window * cfg.width) * cfg.max_fills


def make_lane_state(cfg: LaneConfig):
    S, N, A = cfg.lanes, cfg.slots, cfg.accounts
    if cfg.pos_dma:
        from kme_tpu.ops import rowdma

        sub, ln = rowdma.row_shape(2 * A)
        pos = {"pos_amt": jnp.zeros((S, sub, ln), _I32),
               "pos_avail": jnp.zeros((S, sub, ln), _I32)}
    else:
        pos = {"pos_amt": jnp.zeros((S * A,), _I64),
               "pos_avail": jnp.zeros((S * A,), _I64)}
    return {
        "slot_oid": jnp.zeros((S, 2, N), _I64),
        "slot_aid": jnp.zeros((S, 2, N), _I32),
        "slot_price": jnp.zeros((S, 2, N), _I32),
        "slot_size": jnp.zeros((S, 2, N), _I32),
        "slot_seq": jnp.zeros((S, 2, N), _I32),
        "slot_used": jnp.zeros((S, 2, N), bool),
        "seq": jnp.zeros((S,), _I32),
        "book_exists": jnp.zeros((S,), bool),
        # positions (non-pos_dma): kept FLAT (S*A,) — lane-major, index
        # lane*A+acc.
        # A 2-D (S, A) layout costs a physical re-tiling copy per scan
        # step on TPU for the reshape to flat scatter indices (profiled:
        # ~100us/step in reshape copies + un-aliased scatters); flat
        # arrays scatter with far less traffic, though XLA:TPU scatter
        # still rewrites the array (~1us/MB — the dominant per-step HBM
        # term, see the bench's modeled_hbm_gbps model). A per-lane (S, P)
        # associative table was evaluated and rejected: hot-symbol
        # holder counts approach A on skewed workloads, so P cannot
        # shrink below O(A) without spuriously capacity-rejecting them.
        # There is no `used` flag: in fixed mode a position exists iff
        # amt != 0 (delete-at-zero, KProcessor.java:281-284 corrected),
        # and the engine maintains avail == 0 whenever amt == 0.
        **pos,
        "bal": jnp.zeros((A,), _I64),
        "bal_used": jnp.zeros((A,), bool),
        "err": jnp.zeros((), _I32),
        # compact mode keeps the counters as a TUPLE of scalars: the
        # (12,) array form costs a serialized 12-way concatenate per
        # scan step (~8us/step profiled, x64 pairs); scalar carries are
        # free. Snapshots canonicalize to the (12,) array either way.
        "metrics": (tuple(jnp.zeros((), _I64) for _ in range(N_METRICS))
                    if cfg.width > 0 else jnp.zeros((N_METRICS,), _I64)),
        # distribution histograms (HIST_NAMES rows): same tuple-vs-array
        # split as the counters; rows stay replicated under sharding
        # (psum-merged deltas), canonicalized to (N_HIST, B) in snapshots
        "hist": (tuple(jnp.zeros((N_HIST_BUCKETS,), _I64)
                       for _ in range(N_HIST))
                 if cfg.width > 0
                 else jnp.zeros((N_HIST, N_HIST_BUCKETS), _I64)),
        # persistent fill log: rows oid/aid/price/size; filloff = next
        # free position. Only the used prefix ever crosses to the host
        # (ONE sliced fetch per batch — see chunk_compaction). Compact mode appends whole sorted (M*E,)
        # blocks with one dynamic_update_slice, so the log carries a
        # full block of slack past the overflow watermark; the
        # full-width path's per-entry scatter needs one clamp slot.
        "fillbuf": jnp.zeros((4, cfg.fill_buffer + _fill_slack(cfg)), _I64),
        "filloff": jnp.zeros((1,), _I64),
    }


def _priority_key(side, price, seqno):
    """Scalar price-time key, ascending = better maker. side is the
    MAKER side: 1 (asks) -> low price first; 0 (bids) -> high first."""
    p = jnp.where(side == 1, price, 125 - price).astype(_I64)
    return (p << 32) | seqno.astype(_I64)


_ROW_KEYS = ("slot_oid", "slot_aid", "slot_price", "slot_size",
             "slot_seq", "slot_used")


@functools.lru_cache(maxsize=None)
def build_lane_step(cfg: LaneConfig, axis_name: Optional[str] = None):
    """The pure scan-step batch function: (state, batch) -> (state, outs).

    batch: dict of (T, X) arrays (act, oid, aid, price, size) where X is
    the step width — S in full-width mode, cfg.width under active-lane
    compaction, which adds a (T, X) "lane" array mapping each step slot
    to its device lane (padding slots carry the scrap lane S-1 with
    act=NOP, so their writes are identity by construction).
    outs per (t, slot): ok, residual, append prev info, fill arrays,
    plus the sticky error code.
    When axis_name is set the balance-delta merge is psum'd over that
    mesh axis (shard_map embedding; full-width only)."""
    S, N, A, E = cfg.lanes, cfg.slots, cfg.accounts, cfg.max_fills
    compact = cfg.width > 0
    X = cfg.width if compact else S
    assert not (compact and axis_name), \
        "active-lane compaction is single-device only"
    assert not (cfg.pos_dma and not compact), \
        "pos_dma requires active-lane compaction"
    if cfg.pos_dma:
        from kme_tpu.ops import rowdma

    # TPU-friendly indexed access: multi-dim advanced indexing like
    # a[lane_ids, side, idx] lowers to a generic (slow, ~ms) gather /
    # scatter; take_along_axis / one-hot selects lower to vectorized VPU
    # work (~20µs at S=1024). Measured on v5e — use ONLY these forms in
    # the per-step path.
    def _ta1(a, idx):
        """a: (X, K), idx: (X,) -> (X,) — batched axis-1 gather."""
        return jnp.take_along_axis(a, idx[:, None].astype(_I32), axis=1)[:, 0]

    def _pa1(a, idx, vals):
        """a: (X, K), idx: (X,) -> a with a[x, idx[x]] = vals[x]."""
        return jnp.put_along_axis(a, idx[:, None].astype(_I32),
                                  vals[:, None].astype(a.dtype), axis=1,
                                  inplace=False)

    def one_step(st, msg):
        act, oid, aid = msg["act"], msg["oid"], msg["aid"]
        price, size = msg["price"], msg["size"]

        if compact:
            lanes = msg["lane"].astype(_I32)        # (W,) device lanes
            sl = {k: st[k][lanes] for k in _ROW_KEYS}   # (W, 2, N) rows
            seq_v = st["seq"][lanes]
            be_v = st["book_exists"][lanes]
        else:
            lanes = jnp.arange(S, dtype=_I32)
            sl = {k: st[k] for k in _ROW_KEYS}
            seq_v = st["seq"]
            be_v = st["book_exists"]

        if cfg.pos_dma:
            # row-DMA the W active lanes' position rows into small
            # (X, A) s64 blocks; every read/write below is block-local
            # (each step slot owns its lane row — scheduler invariant),
            # and the updated rows DMA back IN PLACE at the end of the
            # step. The 16MB flat arrays are never scattered.
            pa_f = rowdma.join_rows(
                rowdma.gather_lane_rows(st["pos_amt"], lanes))
            pv_f = rowdma.join_rows(
                rowdma.gather_lane_rows(st["pos_avail"], lanes))

            def pos_read(blk, accs):                # accs: (X,) | (X, K)
                i = (accs if accs.ndim == 2 else accs[:, None]).astype(_I32)
                v = jnp.take_along_axis(blk, i, axis=1)
                return v if accs.ndim == 2 else v[:, 0]

            acc_iota = jnp.arange(A, dtype=_I32)

            def pos_write(blk, accs, vals):
                # one-hot masked merge, NOT scatter: XLA:TPU serializes
                # scatter updates (~11us for a (W,2E)->(W,A) put_along,
                # profiled), while the (X, K, A) one-hot reduction is
                # pure vectorized VPU work. Duplicate accounts within a
                # row carry IDENTICAL values by construction (the engine
                # computes each account's final value for every entry),
                # so a max-select over contributors is exact.
                i = (accs if accs.ndim == 2 else accs[:, None]).astype(_I32)
                v = (vals if vals.ndim == 2 else vals[:, None]).astype(blk.dtype)
                oh = i[:, :, None] == acc_iota                  # (X, K, A)
                hit = jnp.any(oh, axis=1)                       # (X, A)
                BOT = jnp.asarray(-(1 << 62), blk.dtype)
                merged = jnp.max(jnp.where(oh, v[:, :, None], BOT), axis=1)
                return jnp.where(hit, merged, blk)
        else:
            # positions via flat lane*A+acc indices — the state arrays
            # are flat (make_lane_state); XLA scatter rewrites the whole
            # array per step (the pos_dma path avoids this)
            pbase = lanes * A                       # (X,) int32; S*A < 2^31
            pa_f = st["pos_amt"]
            pv_f = st["pos_avail"]

            def pos_read(arr_f, accs):              # accs: (X,) | (X, K)
                idx = pbase[:, None] + accs if accs.ndim == 2 else pbase + accs
                return arr_f[idx]

            def pos_write(arr_f, accs, vals):
                idx = pbase[:, None] + accs if accs.ndim == 2 else pbase + accs
                return arr_f.at[idx].set(vals.astype(arr_f.dtype))

        is_trade = (act == L_BUY) | (act == L_SELL)
        is_buy = act == L_BUY
        side = jnp.where(is_buy, 0, 1).astype(_I32)     # own (rest) side
        opp = (1 - side).astype(_I32)
        opp_is0 = (opp == 0)[:, None]                   # (X, 1) side select
        side_oh = (side[:, None] == jnp.arange(2, dtype=_I32))[:, :, None]
        opp_oh = (opp[:, None] == jnp.arange(2, dtype=_I32))[:, :, None]

        def pick_side(a, is0):
            return jnp.where(is0, a[:, 0], a[:, 1])

        def set_side(a, oh, new):
            """a: (X,2,N); oh: (X,2,1) one-hot; new: (X,N) side image."""
            return jnp.where(oh, new[:, None, :], a)

        bal_g = st["bal"][aid]              # (X,) pre-step actor balances
        bal_ok = st["bal_used"][aid]

        # ------------------------------------------------- CREATE_BALANCE
        create_ok = (act == L_CREATE) & ~bal_ok

        # ------------------------------------------------------- TRANSFER
        size64 = size.astype(_I64)
        # `-order.size` is Java int negation: wraps at int32 (INT_MIN stays
        # INT_MIN) before the long comparison — mirrors oracle._transfer
        neg_size64 = (-size).astype(_I64)
        transfer_ok = (act == L_TRANSFER) & bal_ok & ~(bal_g < neg_size64)

        # ----------------------------------------------------- ADD_SYMBOL
        addsym_ok = (act == L_ADD_SYMBOL) & ~be_v
        book_exists = be_v | addsym_ok

        # ------------------------------------------------- TRADE: margin
        # checkBalance (KProcessor.java:167-182), fixed-domain: price in
        # [0,126), size > 0 (validated), so no int32 wrap can occur.
        valid = (price >= 0) & (price < 126) & (size > 0)
        signed = jnp.where(is_buy, size, -size).astype(_I32)
        signed64 = signed.astype(_I64)
        p_avail = pos_read(pv_f, aid)  # == 0 when no position exists
        adj = jnp.where(is_buy,
                        jnp.maximum(jnp.minimum(p_avail, 0), -signed64),
                        jnp.minimum(jnp.maximum(p_avail, 0), -signed64))
        unit = jnp.where(is_buy, price, price - 100).astype(_I64)
        risk = (signed64 + adj) * unit
        trade_ok = is_trade & valid & be_v & bal_ok & ~(bal_g < risk)

        # -------------------------------------------------- TRADE: sweep
        # the match loop (KProcessor.java:237-258) as ONE multi-operand
        # lax.sort + prefix sum over the opposite side's slots. Profiled
        # on v5e: the sort network is ~30us at (1024, 128) while argsort
        # + per-payload take_along gathers cost ~9ms/step — payloads must
        # ride the sort, and the inverse permutation is a second sort
        # keyed on the slot index, never a gather.
        g = lambda a: pick_side(a, opp_is0)            # (X, N) opp side
        m_used = g(sl["slot_used"])
        m_price, m_size = g(sl["slot_price"]), g(sl["slot_size"])
        m_oid, m_aid, m_seq = g(sl["slot_oid"]), g(sl["slot_aid"]), g(sl["slot_seq"])
        crossing = m_used & jnp.where(
            is_buy[:, None], m_price <= price[:, None], m_price >= price[:, None])
        crossing = crossing & trade_ok[:, None]
        key = _priority_key(opp[:, None], m_price, m_seq)
        BIG = jnp.asarray((1 << 62), _I64)
        masked_key = jnp.where(crossing, key, BIG)
        slot_ids = jnp.broadcast_to(jnp.arange(N, dtype=_I32), (X, N))
        (_, cross_s, sz_raw_s, oid_s, aid_s, price_s, slot_s) = jax.lax.sort(
            (masked_key, crossing, m_size, m_oid, m_aid, m_price, slot_ids),
            num_keys=1, dimension=1)                   # (X, N) best-first
        sz_sorted = jnp.where(cross_s, sz_raw_s, 0)
        prefix = jnp.cumsum(sz_sorted, axis=1) - sz_sorted   # exclusive
        z = jnp.where(trade_ok, size, 0)[:, None]
        fill_sorted = jnp.clip(z - prefix, 0, sz_sorted)
        filled_total = jnp.sum(fill_sorted, axis=1).astype(_I32)
        residual = (size - jnp.where(trade_ok, filled_total, 0)).astype(_I32)
        nfill = jnp.sum(fill_sorted > 0, axis=1).astype(_I32)
        overflow_fills = nfill > E

        # ------------------------- capacity envelope (SURVEY.md §7 H2/H3)
        # A message that would overflow its book side (no free resting
        # slot for the residual) or sweep more makers than max_fills is
        # rejected AS A UNIT — no fills, no state change, OUT REJECT on
        # the wire — mirrored exactly by the oracle's capacity envelope.
        # Per-message policy; the batch continues (no sticky poison).
        side_is0 = (side == 0)[:, None]
        own = lambda a: pick_side(a, side_is0)
        o_used_pre = own(sl["slot_used"])
        free_idx = jnp.argmax(~o_used_pre, axis=1).astype(_I32)
        have_free = jnp.any(~o_used_pre, axis=1)
        rest_want = trade_ok & (residual > 0)
        overflow_book = rest_want & ~have_free
        cap_reject = trade_ok & (overflow_book | overflow_fills)
        trade_acc = trade_ok & ~cap_reject

        # margin netting blocks part of the opposite position (:179) —
        # applied only for accepted messages
        adj_write = trade_acc & (adj != 0)
        pv_f = pos_write(pv_f, aid,
                         pos_read(pv_f, aid)
                         + jnp.where(adj_write, -adj, 0))

        # write back maker sizes via the inverse permutation: a second
        # sort keyed on the carried slot index (slot_s is a permutation
        # of 0..N-1 per lane, so this restores slot order exactly)
        _, new_sz_s = jax.lax.sort(
            (slot_s, (sz_raw_s - fill_sorted).astype(_I32)),
            num_keys=1, dimension=1)
        new_m_size = new_sz_s
        new_m_used = m_used & (new_m_size > 0)
        slot_size = set_side(sl["slot_size"], opp_oh,
                             jnp.where(trade_acc[:, None], new_m_size, m_size))
        slot_used = set_side(sl["slot_used"], opp_oh,
                             jnp.where(trade_acc[:, None], new_m_used, m_used))

        # compact per-trade outputs (priority order), truncated at E.
        # E > N is legal (a sweep can cross at most N makers): the [:E]
        # slice clamps at N, so pad the tail back out to E.
        def cap_e(a):
            a = a[:, :E]
            if a.shape[1] < E:
                a = jnp.pad(a, ((0, 0), (0, E - a.shape[1])))
            return a

        fo_oid = cap_e(oid_s)
        fo_aid = cap_e(aid_s)
        fo_price = cap_e(price_s)
        fo_fill = cap_e(fill_sorted).astype(_I32)

        # ---------------------------------- TRADE: position updates
        # Exact closed-form replay of the per-trade fill sequence (maker
        # fill then taker fill per trade, KProcessor.java:272-273),
        # including delete-at-zero/recreate semantics. Key identity:
        # create(s) == update from (0,0), and a delete only ever happens
        # when the running amount IS zero — so the running amount is the
        # plain prefix sum, and `available` restarts from zero after the
        # account's LAST zero-crossing within the sweep:
        #   amt_final  = amt0 + sum(fills)
        #   avail_fin  = sum(fills after last zero prefix)   if any zero
        #              = avail0 + sum(fills)                 otherwise
        # This replaces a 2E-deep sequential loop with a few (S,2E,2E)
        # masked reductions — pure VPU work, no serialization. (Masked
        # where+sum rather than int64 einsum: an s64 dot_general hits
        # XLA:TPU's unimplemented X64-rewrite path and fails to compile.)
        twoE = 2 * E
        idx2 = jnp.arange(twoE, dtype=_I32)
        # interleave maker/taker entries [m0, t0, m1, t1, ...] via
        # stack+reshape — a pure relayout; the earlier strided
        # .at[:, 0::2].set form lowered to serialized scatters
        # (~1.4us each, profiled)
        def interleave(m, t):
            return jnp.stack([m, t], axis=-1).reshape(X, twoE)

        acc = interleave(fo_aid, jnp.broadcast_to(aid[:, None], (X, E)))
        m_sgn = jnp.where(is_buy[:, None], -fo_fill, fo_fill).astype(_I64)
        t_sgn = jnp.where(is_buy[:, None], fo_fill, -fo_fill).astype(_I64)
        sgn = interleave(m_sgn, t_sgn)
        fv = (fo_fill > 0) & trade_acc[:, None]
        fvalid = interleave(fv, fv)
        a0 = pos_read(pa_f, acc)   # 0 when no position exists
        v0 = pos_read(pv_f, acc)
        # eq[s, i, j]: entry i is a VALID contributor to entry j's account.
        # Only the contributor side is validity-gated: every entry j —
        # valid or not — then computes its account's exact final value, so
        # ALL duplicate scatter targets carry identical values and the
        # plain put_along below is deterministic with no dummy column.
        # (Profiled: the old pad-concat + slice around a (S, A+1) scatter
        # copied the 16MB position arrays twice and cost ~2ms per call.)
        eq = ((acc[:, :, None] == acc[:, None, :])
              & fvalid[:, :, None])                          # (S, i, j)
        le = idx2[:, None] <= idx2[None, :]
        sgn_b = sgn[:, :, None]                              # (S, i, 1)
        prefix = a0 + jnp.sum(jnp.where(eq & le[None], sgn_b, 0), axis=1)
        zero = fvalid & (prefix == 0)
        # per entry j: index of its account's last zero prefix (-1 if none)
        jlast = jnp.max(
            jnp.where(zero[:, :, None] & eq, idx2[None, :, None], -1), axis=1)
        after = eq & (idx2[None, :, None] > jlast[:, None, :])
        avail_sum = jnp.sum(jnp.where(after, sgn_b, 0), axis=1)
        total = jnp.sum(jnp.where(eq, sgn_b, 0), axis=1)
        anyzero = jnp.any(zero[:, :, None] & eq, axis=1)
        amt_fin = a0 + total
        avail_fin = jnp.where(anyzero, avail_sum, v0 + total)
        used_fin = amt_fin != 0
        # untouched accounts land on identity writes (amt_fin = a0 etc.),
        # so no masking is needed: scatter values directly. Deleted
        # positions (amt_fin == 0) write avail = 0 — the no-used-flag
        # invariant.
        pa_f = pos_write(pa_f, acc, amt_fin)
        pv_f = pos_write(pv_f, acc, jnp.where(used_fin, avail_fin, 0))

        # taker balance credit: sum of fill * improvement (maker credit is
        # size * 0 == 0 — the structural fact the scheduler relies on).
        # Each per-fill product is Java int*int — wraps at int32 BEFORE
        # the long balance add (KProcessor.java:286, oracle._fill_order)
        improve = (jnp.where(trade_acc[:, None], price[:, None], 0)
                   - fo_price).astype(_I32)
        signed_credit = jnp.where(is_buy[:, None], fo_fill, -fo_fill).astype(_I32)
        credit = jnp.sum((signed_credit * improve).astype(_I64), axis=1)

        # ------------------------------------------------- TRADE: rest
        # (free slot existence already established by the capacity
        # envelope: trade_acc & rest_want implies have_free)
        # Q9 prev-echo: tail of my price bucket = max seqno among used
        # same-price slots on my side
        o_price, o_seq_ = own(sl["slot_price"]), own(sl["slot_seq"])
        same_level = o_used_pre & (o_price == price[:, None])
        bucket_nonempty = jnp.any(same_level, axis=1)
        tail_idx = jnp.argmax(
            jnp.where(same_level, o_seq_, -1), axis=1).astype(_I32)
        tail_oid = _ta1(own(sl["slot_oid"]), tail_idx)

        do_rest = rest_want & trade_acc
        seqno = seq_v
        # one-hot write of the rested order into (lane, side, free_idx)
        slot_oh = (free_idx[:, None] == jnp.arange(N, dtype=_I32))[:, None, :]
        wr = side_oh & slot_oh & do_rest[:, None, None]      # (X, 2, N)
        slot_oid = jnp.where(wr, oid[:, None, None], sl["slot_oid"])
        slot_aid = jnp.where(wr, aid[:, None, None], sl["slot_aid"])
        slot_price = jnp.where(wr, price[:, None, None], sl["slot_price"])
        slot_size = jnp.where(wr, residual[:, None, None], slot_size)
        slot_seq = jnp.where(wr, seqno[:, None, None], sl["slot_seq"])
        slot_used = slot_used | wr
        seq = seqno + do_rest.astype(_I32)

        # --------------------------------------------------------- CANCEL
        # removeOrder (KProcessor.java:289-323): slot lookup by oid +
        # ownership, then margin release (postRemoveAdjustments :325-333)
        is_cancel = act == L_CANCEL
        hit = sl["slot_used"] & (sl["slot_oid"] == oid[:, None, None])
        hit_flat = hit.reshape(X, 2 * N)
        hit_any = jnp.any(hit_flat, axis=1)
        hit_idx = jnp.argmax(hit_flat, axis=1).astype(_I32)
        h_side = hit_idx // N
        c_aid = _ta1(sl["slot_aid"].reshape(X, 2 * N), hit_idx)
        c_price = _ta1(sl["slot_price"].reshape(X, 2 * N), hit_idx)
        c_size = _ta1(sl["slot_size"].reshape(X, 2 * N), hit_idx)
        cancel_ok = is_cancel & hit_any & (c_aid == aid)
        clear = ((hit_idx[:, None] == jnp.arange(2 * N, dtype=_I32))
                 & cancel_ok[:, None]).reshape(X, 2, N)
        slot_used = slot_used & ~clear
        # margin release
        c_isbuy = h_side == 0
        c_signed = jnp.where(c_isbuy, c_size, -c_size).astype(_I64)
        cp_amt = pos_read(pa_f, aid)
        cp_avail_raw = pos_read(pv_f, aid)
        # amt == avail == 0 when no position exists, so blocked == 0
        blocked = cp_amt - cp_avail_raw
        c_adj = jnp.where(c_isbuy,
                          jnp.maximum(jnp.minimum(blocked, 0), -c_signed),
                          jnp.minimum(jnp.maximum(blocked, 0), -c_signed))
        c_unit = jnp.where(c_isbuy, c_price, c_price - 100).astype(_I64)
        c_release = (c_signed + c_adj) * c_unit
        c_adj_write = cancel_ok & (c_adj != 0)
        pv_f = pos_write(pv_f, aid,
                         cp_avail_raw + jnp.where(c_adj_write, c_adj, 0))

        # ------------------------------------------- balance delta merge
        delta = (jnp.where(transfer_ok, size64, 0)
                 + jnp.where(trade_acc, -risk + credit, 0)
                 + jnp.where(cancel_ok, c_release, 0))
        dense_delta = jnp.zeros((A,), _I64).at[aid].add(delta)
        dense_create = jnp.zeros((A,), bool).at[aid].max(create_ok)
        if axis_name is not None:
            dense_delta = jax.lax.psum(dense_delta, axis_name)
            dense_create = jax.lax.psum(
                dense_create.astype(_I32), axis_name) > 0
        bal = st["bal"] + dense_delta
        bal_used = st["bal_used"] | dense_create

        err = st["err"]
        if axis_name is not None:
            # any shard's sticky error becomes globally visible (and the
            # replicated err stays identical across shards)
            err = jax.lax.pmax(err, axis_name)

        # ------------------------------------------------ metrics delta
        cnt = lambda m: jnp.sum(m.astype(_I64))
        met = (
            cnt(act != L_NOP),                                 # MSGS
            cnt(trade_acc),                                    # TRADES_OK
            jnp.sum(jnp.where(trade_acc, nfill, 0).astype(_I64)),
            jnp.sum(jnp.where(trade_acc, filled_total, 0).astype(_I64)),
            cnt(cap_reject),                                   # REJ_CAPACITY
            cnt(is_trade & ~trade_ok),                         # REJ_RISK
            cnt(do_rest),                                      # RESTED
            cnt(cancel_ok),                                    # CANCELS_OK
            cnt(is_cancel & ~cancel_ok),                       # REJ_CANCEL
            cnt(transfer_ok),                                  # TRANSFERS_OK
            cnt(((act == L_CREATE) & ~create_ok)
                | ((act == L_TRANSFER) & ~transfer_ok)
                | ((act == L_ADD_SYMBOL) & ~addsym_ok)),       # REJ_OTHER
            jnp.zeros((), _I64),                               # BARRIERS
        )
        if compact:
            # scalar-tuple carry: no per-step (12,) concatenate
            metrics = tuple(m + d for m, d in zip(st["metrics"], met))
        else:
            met = jnp.stack(met)
            if axis_name is not None:
                met = jax.lax.psum(met, axis_name)
            metrics = st["metrics"] + met

        # ---------------------------------------------- histogram deltas
        # one-hot scatter-adds into the power-of-two bucket rows. Depth
        # observes the touched book AFTER the message (final slot_used,
        # cancel clear included); padding/scrap rows carry act=NOP so
        # trade_acc/cancel_ok exclude them by construction.
        obs_depth = trade_acc | cancel_ok
        depth = jnp.sum(slot_used.reshape(X, 2 * N).astype(_I32), axis=1)
        d_fills = (jnp.zeros((N_HIST_BUCKETS,), _I64)
                   .at[hist_bucket(nfill)].add(trade_acc.astype(_I64)))
        d_depth = (jnp.zeros((N_HIST_BUCKETS,), _I64)
                   .at[hist_bucket(depth)].add(obs_depth.astype(_I64)))
        occ = jnp.sum((act != L_NOP).astype(_I32))
        if axis_name is not None:
            # shard-invariance: merge the per-shard fills/depth deltas;
            # occupancy counts the GLOBAL step population, so psum the
            # count BEFORE bucketing — the resulting row is identical
            # on every shard and needs no merge of its own
            d_fills = jax.lax.psum(d_fills, axis_name)
            d_depth = jax.lax.psum(d_depth, axis_name)
            occ = jax.lax.psum(occ, axis_name)
        d_occ = (jnp.zeros((N_HIST_BUCKETS,), _I64)
                 .at[hist_bucket(occ)].add((occ > 0).astype(_I64)))
        if compact:
            hist = tuple(h + d for h, d in
                         zip(st["hist"], (d_fills, d_depth, d_occ)))
        else:
            hist = st["hist"] + jnp.stack((d_fills, d_depth, d_occ))

        ok = jnp.where(
            is_trade, trade_acc,
            jnp.where(is_cancel, cancel_ok,
                      jnp.where(act == L_CREATE, create_ok,
                                jnp.where(act == L_TRANSFER, transfer_ok,
                                          jnp.where(act == L_ADD_SYMBOL,
                                                    addsym_ok, act == L_NOP)))))

        new_rows = {
            "slot_oid": slot_oid, "slot_aid": slot_aid,
            "slot_price": slot_price, "slot_size": slot_size,
            "slot_seq": slot_seq, "slot_used": slot_used,
        }
        if compact:
            # Scatter the W updated rows back into the full device state.
            # Duplicate indices only occur on the scrap lane (padding,
            # act=NOP), whose computed rows are bitwise identity — so the
            # duplicate-index scatter is deterministic by construction.
            new_st = dict(st)
            for k, v in new_rows.items():
                new_st[k] = st[k].at[lanes].set(v)
            new_st["seq"] = st["seq"].at[lanes].set(seq)
            new_st["book_exists"] = st["book_exists"].at[lanes].set(book_exists)
            if cfg.pos_dma:
                # DMA the updated (X, A) blocks back in place (the
                # kernel itself skips scrap-lane rows)
                new_st["pos_amt"] = rowdma.scatter_lane_rows(
                    st["pos_amt"], lanes, rowdma.split_rows(pa_f), S - 1)
                new_st["pos_avail"] = rowdma.scatter_lane_rows(
                    st["pos_avail"], lanes, rowdma.split_rows(pv_f), S - 1)
            else:
                new_st["pos_amt"] = pa_f
                new_st["pos_avail"] = pv_f
            new_st.update(bal=bal, bal_used=bal_used, err=err,
                          metrics=metrics, hist=hist)
        else:
            new_st = {
                **new_rows,
                "seq": seq, "book_exists": book_exists,
                "pos_amt": pa_f, "pos_avail": pv_f,
                "bal": bal, "bal_used": bal_used, "err": err,
                "metrics": metrics, "hist": hist,
                "fillbuf": st["fillbuf"], "filloff": st["filloff"],
            }
        outs = {
            "ok": ok,
            "residual": jnp.where(trade_acc, residual, size).astype(_I32),
            "append": bucket_nonempty & do_rest,
            "prev_oid": tail_oid,
            "nfill": jnp.where(trade_acc, nfill, 0),
            "cap_reject": cap_reject,
            "fill_oid": fo_oid, "fill_aid": fo_aid,
            "fill_price": fo_price, "fill_size": fo_fill,
            "err": err,
        }
        return new_st, outs

    def step(state, batch):
        return jax.lax.scan(one_step, state, batch, unroll=cfg.unroll)

    return step


# ---------------------------------------------------------------------------
# compact-I/O chunk: the serving-path wrapper around the scan


def chunk_compaction(cfg: LaneConfig, T: int, M: int, step):
    """Wrap a (state, (T,S) batch) scan `step` with device-side input
    scatter and output compaction.

    Motivation: host<->device traffic, not FLOPs, bounds serving
    throughput (the dense (T,S,E) fill grids are >95% padding). Nothing
    O(T*S) crosses the boundary: inputs arrive as (M,) message vectors
    with (t, lane) schedule coordinates and are scattered to the grid on
    device, and outputs return as per-message (M,) vectors. Fills are appended to
    the PERSISTENT state fill log (state["fillbuf"], in cb order — the
    session packs cb sorted by (t, lane) so the order is deterministic);
    the host fetches the used prefix once per batch. Overflowing the log
    sets the sticky LERR_FILLBUF_FULL error (H3 envelope knob
    `fill_buffer`).

    The sharded path wraps the same chunk around the shard_map'd step
    (parallel/mesh.py): GSPMD gathers each window's compact fills over
    the mesh and the append lands identically on every shard's
    replicated log.

    Under active-lane compaction (cfg.width > 0) the scan grid is
    (T, W) message slots instead of (T, S) lanes: cb carries a "slot"
    coordinate (position within the step, assigned by the scheduler's
    width cap) and the per-step batch includes the (T, W) lane map.
    Padding slots point at the scrap lane S-1 with act=NOP, so their
    row writes are bitwise identity.

    t >= T marks padding entries."""
    S, E = cfg.lanes, cfg.max_fills
    FB = cfg.fill_buffer
    compact = cfg.width > 0
    X = cfg.width if compact else S
    assert not compact or M * E <= _fill_slack(cfg), (
        f"chunk M={M} x max_fills={E} exceeds the fill-log slack "
        f"{_fill_slack(cfg)} — the block append could clamp backward and "
        f"corrupt earlier fills without tripping the sticky error")

    def chunk(state, cb):
        valid = cb["t"] < T
        col = cb["slot"] if compact else cb["lane"]
        flat = jnp.where(valid, cb["t"] * X + col, T * X).astype(_I32)

        def grid(v, dt, fill=0):
            z = jnp.full((T * X + 1,), fill, dt)
            return z.at[flat].set(v.astype(dt))[:T * X].reshape(T, X)

        batch = {
            "act": grid(cb["act"], _I32), "oid": grid(cb["oid"], _I64),
            "aid": grid(cb["aid"], _I32), "price": grid(cb["price"], _I32),
            "size": grid(cb["size"], _I32),
        }
        if compact:
            batch["lane"] = grid(cb["lane"], _I32, fill=S - 1)
        state, outs = step(state, batch)

        gflat = jnp.minimum(flat, T * X - 1)

        def pick(a):  # (T, X, ...) -> (M, ...) per-message gather
            return a.reshape((T * X,) + a.shape[2:])[gflat]

        nfill = jnp.where(valid, pick(outs["nfill"]), 0)
        total = jnp.sum(nfill)
        fo, fa = pick(outs["fill_oid"]), pick(outs["fill_aid"])
        fp, fs = pick(outs["fill_price"]), pick(outs["fill_size"])

        state = dict(state)
        # append to the persistent fill log at the running offset
        base = state["filloff"][0]
        offs = base + (jnp.cumsum(nfill) - nfill).astype(_I64)
        eidx = jnp.arange(E, dtype=_I64)[None, :]
        mask = eidx < nfill[:, None].astype(_I64)
        new_off = base + total.astype(_I64)
        if compact:
            # Stream-compact the (M, E) fill grid with ONE multi-operand
            # sort — valid entries keyed by their window-relative log
            # position (already unique and in (t, lane, e) order),
            # padding keyed past the end — then append the packed block
            # with a single in-place dynamic_update_slice. The previous
            # per-entry scatter serialized on TPU (~4.7ms per window at
            # M=4096, profiled); the sort + contiguous DUS is ~2 orders
            # cheaper. DUS clamps the start when the log overflows; the
            # sticky error below fires before the host ever reads fills.
            rel = offs[:, None] - base + eidx              # (M, E)
            key = jnp.where(mask, rel, M * E).astype(_I32).reshape(-1)
            _, so, sa, sp, ss = jax.lax.sort(
                (key, fo.astype(_I64).reshape(-1),
                 fa.astype(_I64).reshape(-1), fp.astype(_I64).reshape(-1),
                 fs.astype(_I64).reshape(-1)), num_keys=1)
            blk = jnp.stack([so, sa, sp, ss])              # (4, M*E)
            buf = jax.lax.dynamic_update_slice(
                state["fillbuf"], blk, (jnp.zeros((), _I64), base))
        else:
            pos = jnp.where(mask, jnp.minimum(offs[:, None] + eidx, FB), FB)
            pos = pos.astype(_I32).reshape(-1)
            buf = state["fillbuf"]
            for c, arr in enumerate((fo, fa, fp, fs)):
                buf = buf.at[c].set(
                    buf[c].at[pos].set(arr.astype(_I64).reshape(-1)))
        err = state["err"]
        err = jnp.where((err == LERR_OK) & (new_off > FB),
                        jnp.asarray(LERR_FILLBUF_FULL, _I32), err)
        state["fillbuf"] = buf
        state["filloff"] = jnp.full((1,), 0, _I64) + new_off
        state["err"] = err
        # ALL per-message outputs ride ONE (8, M) i64 array — a single
        # device->host transfer per window (each separate np.asarray
        # is a blocking round trip). Rows 6/7 broadcast the err/total
        # scalars.
        packed = jnp.stack([
            jnp.where(valid, pick(outs["ok"]), False).astype(_I64),
            pick(outs["residual"]).astype(_I64),
            jnp.where(valid, pick(outs["append"]), False).astype(_I64),
            pick(outs["prev_oid"]),
            jnp.where(valid, pick(outs["cap_reject"]), False).astype(_I64),
            nfill.astype(_I64),
            jnp.full((M,), 0, _I64) + err.astype(_I64),
            jnp.full((M,), 0, _I64) + total.astype(_I64),
        ])
        return state, {"packed": packed}

    return chunk


@functools.lru_cache(maxsize=None)
def build_lane_chunk(cfg: LaneConfig, T: int, M: int):
    """Single-device compact-I/O chunk fn, jitted with state donation and
    cached per static shape — sessions share compiled executables."""
    return jax.jit(chunk_compaction(cfg, T, M, build_lane_step(cfg)),
                   donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def build_gauges(cfg: LaneConfig):
    """Jitted point-in-time gauges over the lane state (book depth,
    open orders, live books/accounts/positions) — the state-derived half
    of the observability surface; counters live in state['metrics']."""
    def gauges(state):
        used = state["slot_used"]
        depth = jnp.sum(used.astype(_I32), axis=2)     # (S, 2)
        pa = state["pos_amt"]
        if cfg.pos_dma:  # planar lo/hi rows: live iff either half != 0
            v = pa.reshape(pa.shape[0], 2, -1)
            live = (v[:, 0] != 0) | (v[:, 1] != 0)
        else:
            live = pa != 0
        return {
            "open_orders": jnp.sum(used.astype(_I64)),
            "books": jnp.sum(state["book_exists"].astype(_I64)),
            "accounts": jnp.sum(state["bal_used"].astype(_I64)),
            "positions": jnp.sum(live.astype(_I64)),
            "max_book_depth": jnp.max(depth).astype(_I64),
        }

    return jax.jit(gauges)


@functools.lru_cache(maxsize=None)
def build_fill_reset(cfg: LaneConfig):
    """Tiny jitted op: rewind the fill log (the host consumed it)."""
    def reset(state):
        state = dict(state)
        state["filloff"] = jnp.zeros((1,), _I64)
        return state

    return jax.jit(reset, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# barrier ops (rare; invoked by the host between scan dispatches)

@functools.lru_cache(maxsize=None)
def build_barrier_ops(cfg: LaneConfig, axis_name: Optional[str] = None):
    """payout/remove_symbol as standalone jitted-able fns over ONE lane.

    Both wipe the lane's book with per-order margin release in the
    reference's wipe order — min price level first, FIFO within level,
    buy side then sell side (oracle._wipe_book_fixed) — which is
    sequential per account (each release changes `available`, feeding the
    next release's netting), hence the fori_loop over slots in wipe
    order. PAYOUT then credits `amount * size` per holder (YES) or just
    deletes positions (NO) — exchange_test.js:76-79 intent, oracle
    `_payout` fixed mode."""
    S, N, A = cfg.lanes, cfg.slots, cfg.accounts
    lane_ids = jnp.arange(S, dtype=_I32)

    def _pos_row(st, key, lane):
        """One lane's positions as an (A,) s64 row, either layout."""
        if cfg.pos_dma:
            from kme_tpu.ops import rowdma

            r = jax.lax.dynamic_index_in_dim(
                st[key], lane, 0, keepdims=False).reshape(2 * A)
            return rowdma.join64(r[:A], r[A:])
        return jax.lax.dynamic_slice_in_dim(st[key], lane * A, A)

    def _pos_row_set(st_arr, lane, row64):
        """Write an (A,) s64 row back at `lane`, either layout."""
        if cfg.pos_dma:
            from kme_tpu.ops import rowdma

            lo, hi = rowdma.split64(row64)
            packed = jnp.concatenate([lo, hi]).reshape(st_arr.shape[1:])
            return st_arr.at[lane].set(packed)
        return jax.lax.dynamic_update_slice_in_dim(
            st_arr, row64.astype(st_arr.dtype), lane * A, 0)

    def wipe_lane(st, lane, do):
        """Release margin for every resting order of `lane`, clear slots.
        `do` gates the whole operation."""
        sl = lambda k: st[k][lane]                      # (2, N)
        used = sl("slot_used")
        price = sl("slot_price")
        seqno = sl("slot_seq")
        # wipe order: side-major (buy side first), then (price, seqno) —
        # the reference's wipe sequence (oracle._wipe_book_fixed). The
        # side tag (1<<44) dominates the (price<<32 | seq) key range.
        key = (jnp.repeat(jnp.arange(2, dtype=_I64)[:, None] * (1 << 44), N, 1)
               + (price.astype(_I64) << 32) + seqno.astype(_I64))
        key = jnp.where(used, key, jnp.asarray(1 << 62, _I64))
        order = jnp.argsort(key.reshape(2 * N))
        n_used = jnp.sum(used)

        def body(i, carry):
            pos_amt, pos_avail, bal_delta = carry
            flat = order[i]
            s_side = flat // N
            s_slot = flat % N
            active = do & (i < n_used)
            a = st["slot_aid"][lane, s_side, s_slot]
            pr = st["slot_price"][lane, s_side, s_slot]
            sz = st["slot_size"][lane, s_side, s_slot]
            isbuy = s_side == 0
            signed = jnp.where(isbuy, sz, -sz).astype(_I64)
            amt = pos_amt[a]
            avail = pos_avail[a]        # 0 when no position exists
            blocked = amt - avail
            adj = jnp.where(isbuy,
                            jnp.maximum(jnp.minimum(blocked, 0), -signed),
                            jnp.minimum(jnp.maximum(blocked, 0), -signed))
            unit = jnp.where(isbuy, pr, pr - 100).astype(_I64)
            release = (signed + adj) * unit
            pos_avail = pos_avail.at[a].add(jnp.where(active & (adj != 0), adj, 0))
            bal_delta = bal_delta.at[a].add(jnp.where(active, release, 0))
            return pos_amt, pos_avail, bal_delta

        # zero delta derived from lane-sharded state so its varying-axis
        # type matches the loop body's output under shard_map
        zv64 = (st["seq"][0] * 0).astype(_I64)
        carry = (_pos_row(st, "pos_amt", lane),
                 _pos_row(st, "pos_avail", lane),
                 jnp.zeros((A,), _I64) + zv64)
        pos_amt_l, pos_avail_l, bal_delta = jax.lax.fori_loop(
            0, 2 * N, body, carry)
        return pos_amt_l, pos_avail_l, bal_delta

    def settle(state, lane, credit_size, mode):
        """mode: 0 = REMOVE_SYMBOL, 1 = PAYOUT YES, 2 = PAYOUT NO.

        Returns (state, ok). Under shard_map, `lane` is the LOCAL lane
        index on the owning shard; other shards call with do=False via
        lane=-1."""
        do = (lane >= 0) & state["book_exists"][jnp.maximum(lane, 0)]
        lane_c = jnp.maximum(lane, 0)
        pos_amt_l, pos_avail_l, bal_delta = wipe_lane(state, lane_c, do)
        st = dict(state)

        def upd_pos(key, new_row):
            cur = _pos_row(st, key, lane_c)
            return _pos_row_set(st[key], lane_c,
                                jnp.where(do, new_row, cur))

        st["pos_amt"] = upd_pos("pos_amt", pos_amt_l)
        st["pos_avail"] = upd_pos("pos_avail", pos_avail_l)
        st["slot_used"] = st["slot_used"].at[lane_c].set(
            jnp.where(do, False, st["slot_used"][lane_c]))
        st["book_exists"] = st["book_exists"].at[lane_c].set(
            jnp.where(do, False, st["book_exists"][lane_c]))

        # payout credit/delete over the lane's positions (a holder is any
        # account with amt != 0 — the no-used-flag invariant)
        is_payout = mode > 0
        credit = (mode == 1)
        pm = jnp.where(do & is_payout, True, False)
        amts = _pos_row(st, "pos_amt", lane_c)
        pay = jnp.where(pm & credit,
                        amts * credit_size.astype(_I64), 0)
        bal_delta = bal_delta + pay

        def clear_pos(key):
            cur = _pos_row(st, key, lane_c)
            return _pos_row_set(st[key], lane_c, jnp.where(pm, 0, cur))

        st["pos_amt"] = clear_pos("pos_amt")
        st["pos_avail"] = clear_pos("pos_avail")

        if axis_name is not None:
            bal_delta = jax.lax.psum(bal_delta, axis_name)
            do_any = jax.lax.psum(do.astype(_I32), axis_name) > 0
        else:
            do_any = do
        st["bal"] = st["bal"] + bal_delta
        if cfg.width > 0:  # scalar-tuple metrics carry (compact mode)
            mets = list(st["metrics"])
            mets[MET_BARRIERS] = mets[MET_BARRIERS] + do_any.astype(_I64)
            st["metrics"] = tuple(mets)
        else:
            st["metrics"] = st["metrics"].at[MET_BARRIERS].add(
                do_any.astype(_I64))
        return st, do_any

    return settle
