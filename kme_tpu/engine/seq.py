"""Sequential Pallas mega-kernel engine (round 4).

The round-3 profile showed the scan step of the vectorized sweep
engine this one replaced to be OP-COUNT-bound: ~185 XLA ops/step at
~0.25us launch overhead each, with occupancy capped at ~4.4 msgs/step
by hot-lane serialization under its conflict-free scheduler (one
message per lane per step). This engine removes both limits at once:
ONE Pallas kernel processes a micro-batch
of B messages STRICTLY SEQUENTIALLY — the reference's own execution
model (KProcessor.java:95-126, single StreamThread) — with the entire
engine state VMEM-resident for the duration of the call. Sequential
execution inside the kernel IS serial replay, so no scheduling
constraints exist at all: same-account runs, hot-symbol bursts and the
10-account stock harness (exchange_test.js:18) run at full speed
(SURVEY.md §7 H1 dissolves).

Measured basis (scripts/exp_seqkernel.py, v5e chip): a bare sequential
sweep body runs at ~64ns/msg — two orders of magnitude under the sweep
engine's per-step floor.

Semantics: compat='fixed' exactly, byte for byte what the oracle
(oracle/engine.py) defines, including the capacity envelope (slots /
max_fills per-message rejects), Q9 prev-echo, Java int32/int64 wrap
arithmetic, and barrier settles (payout/remove wipe order: buy side
first, (price, seq) within a side — oracle._wipe_book_fixed).

Data layout (everything int32 — the Mosaic kernel boundary refuses
s64; 64-bit balance/position values live as planar lo/hi i32 pairs and
are recombined only in scalar emulation helpers inside the kernel):

- book planes (2*S*NR, 128), row = lane*2*NR + side*NR + r, side 0 =
  buy, N = NR*128 slots/side: oid lo/hi, aid, price, size, seq.
  A slot is occupied iff size > 0 (no used flag).
- positions (fixed mode): a DENSE direct-indexed store, one entry per
  (lane, account) pair — what the deployment can hold, so no stream
  of any length exhausts it (the canonical snapshot has always been
  dense in S*A). ONE plane `pos` of 8-row tiles: tile lane*PTL +
  (acc >> 8) holds 256 accounts of one lane, rows [amt lo, amt hi,
  avail lo, avail hi] for accounts 0..127 of the tile and the same
  four for 128..255, so a lane's positions are PTL = ceil(A/256)
  consecutive tiles (a payout scans those, not the store) and one
  4 KB DMA moves whole entries. An absent position is all zeros (the
  delete-at-zero invariant). The plane
  lives in HBM at every size (1024 x 2048 is 33 MB) and the kernel
  keeps ONE tile in a VMEM scratch, written back when dirty and
  replaced on a miss — the `hbm_books` idiom; a miss costs well under
  a microsecond on the v5e (PERF.md, PR 29), so a small store has no
  VMEM-resident home of its own. Java mode keeps its own value-keyed
  hash (Q11).
- balances (A/128, 128) lo/hi/used planes.
- per-lane seq counters and book-exists flags as (ceil(S/128), 128)
  planes.

Mosaic constraints that shaped the code (all hit on the real chip,
see scripts/exp_seqkernel.py): jax_enable_x64 poisons fori_loop
induction vars / weak int literals / scalar jnp.sum with i64 that the
lowering cannot convert (use fori32 + np.int32 literals + min/max
reductions only); i1-vector selects do not legalize (select on i32,
compare once); with input_output_aliases the OUTPUT VMEM ref starts
initialized with the input's bytes and state must be read AND written
through it.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from kme_tpu import _jaxsetup
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# dense lane op codes (the host router packs these; 7/8/9 below are the
# barriers)
L_NOP = 0
L_BUY = 1
L_SELL = 2
L_CANCEL = 3
L_CREATE = 4
L_TRANSFER = 5
L_ADD_SYMBOL = 6

# engine error codes (sticky, per call). Book/fill CAPACITY overflow is
# NOT an error: it is a per-message REJECT (the H2/H3 envelope policy —
# the offending order is refused as a unit, surfaced as an OUT REJECT in
# the wire stream, and the batch continues). What is sticky is a bound
# of a session buffer or of java mode's device surface, not of the
# engine's semantics (the java-mode codes are below).
LERR_OK = 0
LERR_FILLBUF_FULL = 3  # a call's fill log exhausted (SeqConfig.fill_cap)

# on-device metrics counters (int64 on the host, accumulated from each
# call's scalar row — SURVEY.md §5's replacement for the reference's
# untouched JMX metrics)
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

# on-device distribution histograms: power-of-two buckets accumulated
# next to the metrics counters and fetched in the same device transfer
# (no extra round-trips). Bucket index for value v is
# #{k in 0..14 : v >= 2^k}: v <= 0 -> bucket 0, v == 1 -> 1,
# v in [2^(i-1), 2^i) -> i, v >= 2^14 -> 15.
HIST_FILLS = 0        # makers swept per ACCEPTED trade (0 = pure rest)
HIST_DEPTH = 1        # resting orders (both sides) in the touched book
#                       after each accepted trade/cancel
HIST_OCCUPANCY = 2    # non-NOP messages per kernel call; empty calls
#                       are unobserved
N_HIST = 3
N_HIST_BUCKETS = 16

HIST_NAMES = ("fills_per_order", "book_depth", "batch_occupancy")

# scalar-row histogram window: lanes [HIST_LANE0, HIST_LANE0 + 3*16) of
# output row 0 carry the PER-CALL power-of-two histogram deltas (fills,
# depth, occupancy — HIST_NAMES order). They are accumulated in a VMEM
# scratch row already pre-offset to these lanes, so the epilogue merge
# is one masked where (no lane rotate). Lanes 2..13 hold the 12 metric
# deltas, so the window starts right after them.
HIST_LANE0 = 2 + N_METRICS
# the lane after the histograms: position tiles the call brought in
# from HBM (fixed mode; java has no such store and leaves it 0)
POS_TILES_LANE = HIST_LANE0 + N_HIST * N_HIST_BUCKETS
# and the two after it, the barrier section's work in this call (fixed
# mode): resting orders `wipe_side` took off, one loop turn each, and
# positions a YES payout credited
WIPED_LANE = POS_TILES_LANE + 1
CREDITED_LANE = POS_TILES_LANE + 2

# barrier acts (device-executed, ordinary messages of a call)
L_PAYOUT_YES = 7
L_PAYOUT_NO = 8
L_REMOVE_SYMBOL = 9

LERR_HASH_FULL = 4   # java mode's position hash exhausted (pos_cap)
LERR_JAVA_DOMAIN = 5   # java mode: price/size outside the device domain
LERR_JAVA_CAP = 6      # java mode: slots/max_fills device bound exceeded
                       # (the reference's stores are unbounded; hitting
                       # a static capacity is fatal, never a REJECT)

I32 = jnp.int32
_i = np.int32
MIN32 = _i(-(1 << 31))
BIG = _i(1 << 30)
LN = 128

BOOK_KEYS = ("bo_lo", "bo_hi", "ba", "bp", "bs", "bq")
_BS = BOOK_KEYS.index("bs")
_STATE_KEYS = BOOK_KEYS + (
    "seqc", "bex", "bal_lo", "bal_hi", "bal_u", "pos", "dep", "err")

# one tile of the position plane: 8 rows = 256 accounts x 4 values
POS_TILE_ROWS = 8
POS_TILE_ACCOUNTS = 256

# java mode: Q11 positions are keyed by 128-bit pairs — real keys
# (aid, sid), garbage keys (amount, available) — with true deletion
# (delete-at-zero pops arbitrary keys), so the hash carries four key
# planes + an explicit state plane (0 empty / 1 live / 2 tombstone),
# plus raw-id lookup tables (dense idx -> Java-long aid, lane -> sid)
# the maker-fill path needs to BUILD keys from device-resident ids.
_STATE_KEYS_JAVA = (
    "bo_lo", "bo_hi", "ba", "bp", "bs", "bq",
    "seqc", "bex", "bal_lo", "bal_hi", "bal_u",
    "hka_lo", "hka_hi", "hkb_lo", "hkb_hi", "hstate",
    "ha_lo", "ha_hi", "hv_lo", "hv_hi",
    "araw_lo", "araw_hi", "sraw_lo", "sraw_hi", "err")


def state_keys(cfg: SeqConfig):
    return _STATE_KEYS_JAVA if cfg.compat == "java" else _STATE_KEYS

AMASK = _i((1 << 30) - 1)   # java: ba plane packs aidx | is_buy << 30


@dataclasses.dataclass(frozen=True)
class SeqConfig:
    """Static shapes; one Mosaic program per distinct value."""

    lanes: int = 1024          # S symbols
    slots: int = 128           # N resting orders per side (mult of 128)
    accounts: int = 2048       # A dense account capacity (mult of 128)
    max_fills: int = 16        # E makers swept per taker (H3 envelope)
    batch: int = 4096          # B messages per kernel call (mult of 128)
    # java mode only (its keys are VALUES, Q11, so accounts x symbols
    # does not bound them): hash capacity (pow2 mult of 128) and the
    # most tiles probed before HASH_FULL. Fixed mode reads neither: its
    # position store is sized by lanes x accounts (pos_capacity)
    pos_cap: int = 1 << 17
    fill_cap: int = 1 << 15    # fill entries per call (mult of 128)
    probe_max: int = 64
    # compat='java' replicates the reference quirk-for-quirk ON DEVICE
    # (Q1 merged sid-0 book, Q2 ghost trades, Q9, Q11 value-as-key
    # positions with a 128-bit-key tombstoned hash) for the stock wire
    # surface: CREATE/TRANSFER/ADD_SYMBOL(sid>=0)/BUY/SELL/CANCEL with
    # in-domain prices/sizes. Barriers and negative-sid symbols (dead or
    # broken paths in the reference -- Q3-Q6) are routed to the native
    # engine instead; out-of-domain fields trip a sticky error. fixed
    # mode is the performance/envelope path; java mode is the
    # quirk-exact-parity-on-TPU path (COMPAT.md).
    compat: str = "fixed"
    # hbm_books: book planes live in HBM (pl.ANY) and the kernel keeps
    # ONE lane's rows in a VMEM scratch cache, flushed/loaded on lane
    # switch. VMEM cannot hold deep books (slots=8192 at S=1024 is
    # ~400MB across the planes); the Zipf hot lane needs thousands of
    # resting slots for the envelope to stop rejecting flow the
    # reference (unbounded lists, KProcessor.java:200-223) accepts.
    # Lane locality makes switches cheap, and HBM bandwidth (~800GB/s)
    # dwarfs the ~64KB/plane moved per switch.
    hbm_books: bool = False

    def __post_init__(self):
        if self.compat not in ("fixed", "java"):
            raise ValueError(f"unknown compat {self.compat!r}")
        assert self.slots % LN == 0 and self.slots >= LN
        assert self.accounts % LN == 0
        assert self.batch % LN == 0
        assert self.pos_cap % LN == 0 and (
            self.pos_cap & (self.pos_cap - 1)) == 0
        assert self.fill_cap % LN == 0
        assert self.max_fills <= LN
        assert self.lanes * self.accounts + self.accounts < (1 << 31), \
            "hash keys must fit int32"

    @property
    def nr(self):
        return self.slots // LN

    @property
    def srows(self):
        return -(-self.lanes // LN)

    @property
    def arows(self):
        return self.accounts // LN

    @property
    def caprows(self):
        return self.pos_cap // LN

    @property
    def pos_capacity(self):
        """Positions the store can hold. Fixed mode: every (lane,
        account) pair the configuration has, so it cannot fill; java
        mode: the hash's pos_cap."""
        if self.compat == "java":
            return self.pos_cap
        return self.lanes * self.accounts

    @property
    def pos_tiles_per_lane(self):
        return -(-self.accounts // POS_TILE_ACCOUNTS)

    @property
    def pos_rows(self):
        """Rows of the fixed-mode `pos` plane."""
        return self.lanes * self.pos_tiles_per_lane * POS_TILE_ROWS


def make_seq_state(cfg: SeqConfig):
    S, NR = cfg.lanes, cfg.nr
    z = lambda r: jnp.zeros((r, LN), I32)
    common = {
        "bo_lo": z(2 * S * NR), "bo_hi": z(2 * S * NR), "ba": z(2 * S * NR),
        "bp": z(2 * S * NR), "bs": z(2 * S * NR), "bq": z(2 * S * NR),
        "seqc": z(cfg.srows), "bex": z(cfg.srows),
        "bal_lo": z(cfg.arows), "bal_hi": z(cfg.arows), "bal_u": z(cfg.arows),
        "err": z(1),
    }
    if cfg.compat == "java":
        common.update({
            "hka_lo": z(cfg.caprows), "hka_hi": z(cfg.caprows),
            "hkb_lo": z(cfg.caprows), "hkb_hi": z(cfg.caprows),
            "hstate": z(cfg.caprows),
            "ha_lo": z(cfg.caprows), "ha_hi": z(cfg.caprows),
            "hv_lo": z(cfg.caprows), "hv_hi": z(cfg.caprows),
            "araw_lo": z(cfg.arows), "araw_hi": z(cfg.arows),
            "sraw_lo": z(cfg.srows), "sraw_hi": z(cfg.srows),
        })
    else:
        common.update({
            "pos": z(cfg.pos_rows),
            # per-lane occupied-slot count (both sides), maintained
            # incrementally for the book-depth histogram: a both-plane
            # reduction per message would dwarf the message cost
            "dep": z(cfg.srows),
        })
    return common


# ---------------------------------------------------------------------------
# output plane layout (host unpack in unpack_out)

def out_rows(cfg: SeqConfig):
    """Output plane rows: [0] scalars (err, fill_total, metric deltas);
    [1, 1+5BR) per-message regions (flags/residual/nfill/prev lo/hi);
    [1+5BR, ...) fills in GROUPS of 5 rows per 128 entries (oid lo/hi,
    aid, price, size) so the used prefix is ONE contiguous row slice —
    the host fetches header + exactly ceil(fill_total/128) groups."""
    BR, FR = cfg.batch // LN, cfg.fill_cap // LN
    return 1 + 5 * BR + 5 * FR


def hdr_rows(cfg: SeqConfig):
    return 1 + 5 * (cfg.batch // LN)


# ---------------------------------------------------------------------------
# kernel-side helpers (scalar i64 emulation on i32 pairs etc.)

def _fori32(n, body, init):
    """while_loop with an np.int32 counter (see module docstring)."""
    def cond(c):
        return c[0] < _i(n)

    def step(c):
        i, carry = c
        return i + _i(1), body(i, carry)

    return jax.lax.while_loop(cond, step, (_i(0), init))[1]


def _u_lt(a, b):
    return (a ^ MIN32) < (b ^ MIN32)


def _add64(alo, ahi, blo, bhi):
    rlo = alo + blo
    carry = _u_lt(rlo, alo).astype(I32)
    return rlo, ahi + bhi + carry


def _sx(v):
    """sign-extend i32 scalar to an (lo, hi) pair."""
    return v, v >> _i(31)


def _neg64(lo, hi):
    return -lo, ~hi + (lo == _i(0)).astype(I32)


def _lt64(alo, ahi, blo, bhi):
    return (ahi < bhi) | ((ahi == bhi) & _u_lt(alo, blo))


def _sel64(c, a, b):
    return jnp.where(c, a[0], b[0]), jnp.where(c, a[1], b[1])


def _min64(a, b):
    return _sel64(_lt64(*a, *b), a, b)


def _max64(a, b):
    return _sel64(_lt64(*a, *b), b, a)


def _muls64(a, b):
    """Exact i64 product of i32 `a` and SMALL i32 `b` (|b| <= ~2^14):
    16-bit split keeps every partial in i32 range."""
    t1 = (a & _i(0xFFFF)) * b            # [0, 2^16) * b
    t2 = (a >> _i(16)) * b               # [-2^15, 2^15) * b
    return _add64(t2 << _i(16), t2 >> _i(16), *_sx(t1))


def _mul64(alo, ahi, blo, bhi):
    """Full 64x64 -> 64 wrap product (Java long multiply) via 8-bit
    limbs — every limb product < 2^16 and limb accumulators stay far
    inside i32. Only the rare payout credit path uses this."""
    M = _i(0xFF)
    a = [(alo >> _i(8 * k)) & M for k in range(4)] + \
        [(ahi >> _i(8 * k)) & M for k in range(4)]
    b = [(blo >> _i(8 * k)) & M for k in range(4)] + \
        [(bhi >> _i(8 * k)) & M for k in range(4)]
    limbs = []
    carry = _i(0)
    for k in range(8):
        acc = carry
        for i2 in range(k + 1):
            acc = acc + a[i2] * b[k - i2]
        limbs.append(acc & M)
        carry = acc >> _i(8)
    lo = limbs[0] | (limbs[1] << _i(8)) | (limbs[2] << _i(16)) \
        | (limbs[3] << _i(24))
    hi = limbs[4] | (limbs[5] << _i(8)) | (limbs[6] << _i(16)) \
        | (limbs[7] << _i(24))
    return lo, hi


# ---------------------------------------------------------------------------
# the kernel

@functools.lru_cache(maxsize=None)
def build_seq_step(cfg: SeqConfig):
    """Returns the jitted (state, msgs) -> (state, out_plane) callable.

    msgs: dict of (B,) int32 arrays act/oid_lo/oid_hi/aid/price/size/
    lane (host router output; padding entries carry act = L_NOP).
    out_plane: (out_rows, 128) int32 — see unpack_out.
    """
    S, NR, E, B = cfg.lanes, cfg.nr, cfg.max_fills, cfg.batch
    CAPR, FB = cfg.caprows, cfg.fill_cap
    BR, FR = B // LN, FB // LN
    NROWS = out_rows(cfg)
    PROBE = min(cfg.probe_max, CAPR)
    CAPMASK = _i(cfg.pos_cap - 1)

    HBM = cfg.hbm_books
    JAVA = cfg.compat == "java"
    PTL = cfg.pos_tiles_per_lane
    KEYS = state_keys(cfg)
    NSMEM = 12 if JAVA else 7

    def kernel(*args):
        # args: NSMEM message arrays, then aliased state ins, state outs
        # + out plane, then scratch: an SMEM scalar row (cross-section
        # results — the heavy sections run under pl.when branches so
        # non-trade messages skip the trade machinery entirely), then
        # (hbm_books) 6 VMEM scratch planes + a DMA semaphore array,
        # then (fixed mode) one position tile + its DMA semaphore.
        (act_s, oidlo_s, oidhi_s, aid_s, price_s, size_s,
         lane_s) = args[:7]
        if JAVA:
            aidrlo_s, aidrhi_s, sidrlo_s, sidrhi_s, flags_s = args[7:12]
        refs = args[NSMEM:]
        nst = len(KEYS)
        outs = refs[nst:]
        st = dict(zip(KEYS, outs[:nst]))
        out = outs[nst]
        sm = refs[nst + nst + 1]
        vr = refs[nst + nst + 2]
        extra = list(refs[nst + nst + 3:])
        if HBM:
            scr = dict(zip(BOOK_KEYS, extra[:6]))
            dsem = extra[6]
            del extra[:7]
        if not JAVA:
            pscr, psem = extra

        ci = jax.lax.broadcasted_iota(I32, (1, LN), 1)
        # flat slot index over an (NR, 128) side block
        fi = (jax.lax.broadcasted_iota(I32, (NR, LN), 0) * _i(LN)
              + jax.lax.broadcasted_iota(I32, (NR, LN), 1))

        def pick(row, l):
            """exact scalar extract from a (1,128) row at lane l."""
            return MIN32 ^ jnp.max(
                jnp.where(ci == l, row ^ MIN32, MIN32))

        def pick2(blk, f):
            """extract from an (NR,128) block at flat index f."""
            return MIN32 ^ jnp.max(
                jnp.where(fi == f, blk ^ MIN32, MIN32))

        def put(ref, r, l, v):
            row = ref[pl.ds(r, 1), :]
            ref[pl.ds(r, 1), :] = jnp.where(ci == l, v, row)

        def rget(ref, r, l):
            return pick(ref[pl.ds(r, 1), :], l)

        def set_err(code):
            r0 = st["err"][0:1, :]
            st["err"][0:1, :] = jnp.where(
                (ci == _i(0)) & (r0 == _i(LERR_OK)), code, r0)

        def hbucket(v):
            """power-of-two bucket index of scalar v:
            #{k in 0..14 : v >= 2^k}."""
            b = _i(0)
            for k2 in range(N_HIST_BUCKETS - 1):
                b = b + (v >= _i(1 << k2)).astype(I32)
            return b

        def hist_obs(cond, lane0, v):
            """bump the scratch histogram row (pre-offset scalar-row
            lanes) at bucket(v) of the histogram starting at lane0."""
            @pl.when(cond)
            def _():
                hr = vr[NR + 2:NR + 3, :]
                vr[NR + 2:NR + 3, :] = hr + (
                    ci == _i(lane0) + hbucket(v)).astype(I32)

        # -------- balances (row r = acc >> 7, lane l = acc & 127)
        def bal_get(acc):
            r, l = acc >> _i(7), acc & _i(127)
            return rget(st["bal_lo"], r, l), rget(st["bal_hi"], r, l)

        def bal_add(acc, dlo, dhi):
            r, l = acc >> _i(7), acc & _i(127)
            lo, hi = rget(st["bal_lo"], r, l), rget(st["bal_hi"], r, l)
            nlo, nhi = _add64(lo, hi, dlo, dhi)
            put(st["bal_lo"], r, l, nlo)
            put(st["bal_hi"], r, l, nhi)

        # -------- positions (fixed mode): the dense store -------------
        # sm[16] the tile the scratch holds (-1: none), sm[17] whether
        # it was written, sm[18] tiles brought in from HBM this call
        # (sm[19], sm[20]: orders wiped and positions credited by the
        # barrier section this call)
        def pos_copy(tile, flush):
            hbm = st["pos"].at[pl.ds(tile * _i(POS_TILE_ROWS),
                                     POS_TILE_ROWS)]
            src, dst = (pscr, hbm) if flush else (hbm, pscr)
            cp = pltpu.make_async_copy(src, dst, psem.at[_i(0)])
            cp.start()
            cp.wait()

        def pos_bring(tile):
            """make the scratch hold `tile` of the HBM plane."""
            cur = sm[16]

            @pl.when(tile != cur)
            def _():
                @pl.when(sm[17] != _i(0))
                def _():
                    pos_copy(cur, True)

                pos_copy(tile, False)
                sm[16] = tile
                sm[17] = _i(0)
                sm[18] = sm[18] + _i(1)

        def pos_at(lane, acc):
            """bring in the pair's tile -> (row of amt lo, lane) of its
            entry in the scratch."""
            pos_bring(lane * _i(PTL) + (acc >> _i(8)))
            return ((acc >> _i(7)) & _i(1)) * _i(4), acc & _i(127)

        def pos_get(lane, acc):
            """-> (amt lo, hi, avail lo, hi); zeros when absent."""
            r, l = pos_at(lane, acc)
            return tuple(rget(pscr, r + _i(k_), l) for k_ in range(4))

        def pos_set(lane, acc, alo, ahi, vlo, vhi):
            r, l = pos_at(lane, acc)
            for k_, v in enumerate((alo, ahi, vlo, vhi)):
                put(pscr, r + _i(k_), l, v)
            sm[17] = _i(1)

        def fill_one(lane, acc, sgn_fill):
            """fillOrder's position half (KProcessor.java:276-287),
            fixed mode: create == update-from-(0,0); delete-at-zero
            writes (0,0). sgn_fill: signed i32 size."""
            alo, ahi, vlo, vhi = pos_get(lane, acc)
            nalo, nahi = _add64(alo, ahi, *_sx(sgn_fill))
            nvlo, nvhi = _add64(vlo, vhi, *_sx(sgn_fill))
            dead = (nalo == _i(0)) & (nahi == _i(0))
            z = _i(0)
            pos_set(lane, acc, nalo, nahi,
                    jnp.where(dead, z, nvlo), jnp.where(dead, z, nvhi))

        # -------- java (Q11) position hash: 128-bit keys, tombstones --
        if JAVA:
            def jhome(kal, kah, kbl, kbh):
                h = (kal * _i(-1640531527) ^ kah * _i(-2048144789)
                     ^ kbl * _i(-1028477387) ^ kbh * _i(69069))
                return (h >> _i(7)) & (CAPMASK >> _i(7))

            def _jtile(t, kal, kah, kbl, kbh):
                """probe one tile -> (hidx, empty) lane minima."""
                srow = st["hstate"][pl.ds(t, 1), :]
                live = srow == _i(1)
                eq = (live
                      & (st["hka_lo"][pl.ds(t, 1), :] == kal)
                      & (st["hka_hi"][pl.ds(t, 1), :] == kah)
                      & (st["hkb_lo"][pl.ds(t, 1), :] == kbl)
                      & (st["hkb_hi"][pl.ds(t, 1), :] == kbh))
                hidx = jnp.min(jnp.where(eq, ci, BIG))
                empty = jnp.min(jnp.where(srow == _i(0), ci, BIG))
                return hidx, empty

            def jfind(kal, kah, kbl, kbh):
                """-> (flat entry or -1, err). Tombstones are passed
                over; an EMPTY slot ends the probe. First tile probes
                straight-line (while_loop entry costs ~0.9us on this
                Mosaic — see h_find)."""
                t0 = jhome(kal, kah, kbl, kbh)
                hidx, empty = _jtile(t0, kal, kah, kbl, kbh)
                found = hidx < BIG
                stop0 = found | (empty < BIG) | (_i(1) >= _i(PROBE))
                sm[14] = jnp.where(found, t0 * _i(LN) + hidx, _i(-1))
                sm[15] = ((~found) & (_i(1) >= _i(PROBE))).astype(I32)

                @pl.when(~stop0)
                def _():
                    def body(c):
                        t, probes, res, done = c
                        hx, em = _jtile(t, kal, kah, kbl, kbh)
                        fnd = hx < BIG
                        stop = (fnd | (em < BIG)
                                | (probes + _i(1) >= _i(PROBE)))
                        res = jnp.where(fnd, t * _i(LN) + hx, res)
                        return ((t + _i(1)) & (CAPMASK >> _i(7)),
                                probes + _i(1), res, stop)

                    _, probes, res, _ = jax.lax.while_loop(
                        lambda c: ~c[3], body,
                        ((t0 + _i(1)) & (CAPMASK >> _i(7)), _i(1),
                         _i(-1), False))
                    sm[14] = res
                    sm[15] = ((res < _i(0))
                              & (probes >= _i(PROBE))).astype(I32)

                return sm[14], sm[15] != _i(0)

            def jslot_for_insert(kal, kah, kbl, kbh):
                """-> (flat slot, found_live, err): the live match if it
                exists, else the first reusable (tombstone/empty) slot
                seen on the probe path. First tile straight-line (see
                jfind)."""
                t0 = jhome(kal, kah, kbl, kbh)
                srow = st["hstate"][pl.ds(t0, 1), :]
                hidx, empty = _jtile(t0, kal, kah, kbl, kbh)
                free = jnp.min(jnp.where(srow != _i(1), ci, BIG))
                found0 = hidx < BIG
                reuse0 = jnp.where(free < BIG, t0 * _i(LN) + free,
                                   _i(-1))
                res0 = jnp.where(found0, t0 * _i(LN) + hidx, _i(-1))
                stop0 = (found0 | (empty < BIG)
                         | (_i(1) >= _i(PROBE)))
                sm[13] = res0
                sm[14] = reuse0

                @pl.when(~stop0)
                def _():
                    def body(c):
                        t, probes, res, reuse, done = c
                        sr = st["hstate"][pl.ds(t, 1), :]
                        hx, em = _jtile(t, kal, kah, kbl, kbh)
                        fr = jnp.min(jnp.where(sr != _i(1), ci, BIG))
                        fnd = hx < BIG
                        reuse = jnp.where((reuse < _i(0)) & (fr < BIG),
                                          t * _i(LN) + fr, reuse)
                        res = jnp.where(fnd, t * _i(LN) + hx, res)
                        stop = (fnd | (em < BIG)
                                | (probes + _i(1) >= _i(PROBE)))
                        return ((t + _i(1)) & (CAPMASK >> _i(7)),
                                probes + _i(1), res, reuse, stop)

                    _, probes, res, reuse, _ = jax.lax.while_loop(
                        lambda c: ~c[4], body,
                        ((t0 + _i(1)) & (CAPMASK >> _i(7)), _i(1),
                         res0, reuse0, False))
                    sm[13] = res
                    sm[14] = reuse

                resv = sm[13]
                reusev = sm[14]
                found = resv >= _i(0)
                slot = jnp.where(found, resv, reusev)
                return slot, found, slot < _i(0)

            def jvals(e):
                r, l = e >> _i(7), e & _i(127)
                rr = jnp.where(e >= _i(0), r, _i(0))
                there = e >= _i(0)
                z = _i(0)
                return (jnp.where(there, rget(st["ha_lo"], rr, l), z),
                        jnp.where(there, rget(st["ha_hi"], rr, l), z),
                        jnp.where(there, rget(st["hv_lo"], rr, l), z),
                        jnp.where(there, rget(st["hv_hi"], rr, l), z))

            def jwrite(e, kal, kah, kbl, kbh, alo, ahi, vlo, vhi):
                r, l = e >> _i(7), e & _i(127)

                @pl.when(e >= _i(0))
                def _():
                    put(st["hstate"], r, l, _i(1))
                    put(st["hka_lo"], r, l, kal)
                    put(st["hka_hi"], r, l, kah)
                    put(st["hkb_lo"], r, l, kbl)
                    put(st["hkb_hi"], r, l, kbh)
                    put(st["ha_lo"], r, l, alo)
                    put(st["ha_hi"], r, l, ahi)
                    put(st["hv_lo"], r, l, vlo)
                    put(st["hv_hi"], r, l, vhi)

            def jdelete(e):
                r, l = e >> _i(7), e & _i(127)

                @pl.when(e >= _i(0))
                def _():
                    put(st["hstate"], r, l, _i(2))   # tombstone

            def jfill_one(alo, ahi, slo, shi, sgn_fill):
                """fillOrder java (Q11, KProcessor.java:276-287): first
                fill creates the real (aid, sid) entry; later fills
                read the real entry but write/delete the VALUE-as-key
                (amount, available) target. -> err flag."""
                e, err0 = jfind(alo, ahi, slo, shi)
                amt_lo, amt_hi, av_lo, av_hi = jvals(e)
                absent = e < _i(0)
                nalo, nahi = _add64(amt_lo, amt_hi, *_sx(sgn_fill))
                nvlo, nvhi = _add64(av_lo, av_hi, *_sx(sgn_fill))
                err = err0

                @pl.when(absent & ~err0)
                def _():
                    s2, _f, e2 = jslot_for_insert(alo, ahi, slo, shi)
                    jwrite(s2, alo, ahi, slo, shi,
                           sgn_fill, sgn_fill >> _i(31),
                           sgn_fill, sgn_fill >> _i(31))

                    @pl.when(e2)
                    def _():
                        set_err(_i(LERR_HASH_FULL))

                @pl.when(~absent)
                def _():
                    # target key = the OLD value (amount, available)
                    dead = (nalo == _i(0)) & (nahi == _i(0))

                    @pl.when(dead)
                    def _():
                        t_e, _te = jfind(amt_lo, amt_hi, av_lo, av_hi)
                        jdelete(t_e)   # pop(target, None): no-op absent

                    @pl.when(~dead)
                    def _():
                        s2, _f, e2 = jslot_for_insert(
                            amt_lo, amt_hi, av_lo, av_hi)
                        jwrite(s2, amt_lo, amt_hi, av_lo, av_hi,
                               nalo, nahi, nvlo, nvhi)

                        @pl.when(e2)
                        def _():
                            set_err(_i(LERR_HASH_FULL))

                return err

            def araw_of(acc):
                r, l = acc >> _i(7), acc & _i(127)
                return (rget(st["araw_lo"], r, l),
                        rget(st["araw_hi"], r, l))

            def sraw_of(lane):
                r, l = lane >> _i(7), lane & _i(127)
                return (rget(st["sraw_lo"], r, l),
                        rget(st["sraw_hi"], r, l))

        # -------- book row access -------------------------------------
        # Under hbm_books the CURRENT lane's rows live in the VMEM
        # scratch cache (lane arg ignored; the switch logic in `one`
        # guarantees the cache holds the message's lane before any
        # book-touching path runs).
        def side_base(lane, side):
            return lane * _i(2 * NR) + side * _i(NR)

        def _rows(start, n):
            """Static slice for constant starts (pl.ds rejects numpy
            scalars), dynamic pl.ds for traced ones."""
            if isinstance(start, (int, np.integer)):
                return slice(int(start), int(start) + n)
            return pl.ds(start, n)

        def side_blk(key, lane, side):
            if HBM:
                return scr[key][_rows(side * _i(NR), NR), :]
            return st[key][_rows(side_base(lane, side), NR), :]

        def side_put(key, lane, side, blk):
            if HBM:
                scr[key][_rows(side * _i(NR), NR), :] = blk
            else:
                st[key][_rows(side_base(lane, side), NR), :] = blk

        def slot_write(key, lane, side, f, v):
            blk = side_blk(key, lane, side)
            side_put(key, lane, side, jnp.where(fi == f, v, blk))

        def books_flush(cur):
            """scratch -> HBM rows of lane `cur` (all 6 planes)."""
            for k_, key in enumerate(BOOK_KEYS):
                pltpu.make_async_copy(
                    scr[key], st[key].at[pl.ds(cur * _i(2 * NR), 2 * NR)],
                    dsem.at[_i(k_)]).start()
            for k_, key in enumerate(BOOK_KEYS):
                pltpu.make_async_copy(
                    scr[key], st[key].at[pl.ds(cur * _i(2 * NR), 2 * NR)],
                    dsem.at[_i(k_)]).wait()

        def books_load(lane):
            """HBM rows of `lane` -> scratch (all 6 planes)."""
            for k_, key in enumerate(BOOK_KEYS):
                pltpu.make_async_copy(
                    st[key].at[pl.ds(lane * _i(2 * NR), 2 * NR)],
                    scr[key], dsem.at[_i(k_)]).start()
            for k_, key in enumerate(BOOK_KEYS):
                pltpu.make_async_copy(
                    st[key].at[pl.ds(lane * _i(2 * NR), 2 * NR)],
                    scr[key], dsem.at[_i(k_)]).wait()

        # -------- margin release shared by cancel + wipe --------------
        def release_margin(lane, acc, o_isbuy, o_price, o_size):
            """postRemoveAdjustments (KProcessor.java:325-333): returns
            the balance credit and applies the avail adjustment."""
            signed = jnp.where(o_isbuy, o_size, -o_size)
            alo, ahi, vlo, vhi = pos_get(lane, acc)
            blo, bhi = _add64(alo, ahi, *_neg64(vlo, vhi))  # blocked
            z64 = (_i(0), _i(0))
            nsg = _neg64(*_sx(signed))
            adjlo, adjhi = _sel64(
                o_isbuy,
                _max64(_min64((blo, bhi), z64), nsg),
                _min64(_max64((blo, bhi), z64), nsg))
            unit = jnp.where(o_isbuy, o_price, o_price - _i(100))
            rel_lo, rel_hi = _muls64(signed + adjlo, unit)
            adj_nz = (adjlo != _i(0)) | (adjhi != _i(0))

            @pl.when(adj_nz)
            def _():
                nvlo, nvhi = _add64(vlo, vhi, adjlo, adjhi)
                pos_set(lane, acc, alo, ahi, nvlo, nvhi)

            return rel_lo, rel_hi

        # -------- output row helpers ----------------------------------
        def out_put(region_row, m, v):
            r = region_row + (m >> _i(7))
            put(out, r, m & _i(127), v)

        def fill_put(field, p, v):
            # group layout: 5 consecutive rows per 128 fill entries
            r = _i(1 + 5 * BR) + (p >> _i(7)) * _i(5) + _i(field)
            put(out, r, p & _i(127), v)

        # ==============================================================
        def one(m, carry):
            (fill_total, cur_lane, met) = carry
            act = act_s[m]
            lane = lane_s[m]
            acc = aid_s[m]
            limit = price_s[m]
            size = size_s[m]
            t_oidlo = oidlo_s[m]
            t_oidhi = oidhi_s[m]

            is_trade = (act == _i(L_BUY)) | (act == _i(L_SELL))
            is_buy = act == _i(L_BUY)
            is_cancel = act == _i(L_CANCEL)
            is_barrier = ((act == _i(L_PAYOUT_YES))
                          | (act == _i(L_PAYOUT_NO))
                          | (act == _i(L_REMOVE_SYMBOL)))
            side = jnp.where(is_buy, _i(0), _i(1))
            opp = _i(1) - side
            # sgn: buy -> +1 (low ask first), sell -> -1 (high bid first)
            sgn = jnp.where(is_buy, _i(1), _i(-1))
            if JAVA:
                # Q1: sid=0's buy and sell books share one key (-0 == 0)
                # — both directions rest into and sweep side 0
                merged = (flags_s[m] & _i(1)) != _i(0)
                side = jnp.where(merged, _i(0), side)
                opp = jnp.where(merged, _i(0), opp)
                a_rlo, a_rhi = aidrlo_s[m], aidrhi_s[m]
                s_rlo, s_rhi = sidrlo_s[m], sidrhi_s[m]

            if HBM:
                needs_books = is_trade | is_cancel | is_barrier
                do_switch = needs_books & (lane != cur_lane)

                @pl.when(do_switch & (cur_lane >= _i(0)))
                def _():
                    books_flush(cur_lane)

                @pl.when(do_switch)
                def _():
                    books_load(lane)

                cur_lane = jnp.where(do_switch, lane, cur_lane)

            lr, ll = lane >> _i(7), lane & _i(127)
            bex_v = rget(st["bex"], lr, ll) != _i(0)

            if JAVA:
                # raw-id tables: every actor-ful message refreshes its
                # dense->raw binding (idempotent); ADD_SYMBOL binds the
                # lane's sid (trades gate on book_exists, so fills only
                # ever read bound lanes)
                has_actor = (is_trade | is_cancel | (act == _i(L_CREATE))
                             | (act == _i(L_TRANSFER)))

                @pl.when(has_actor)
                def _():
                    ar, al = acc >> _i(7), acc & _i(127)
                    put(st["araw_lo"], ar, al, a_rlo)
                    put(st["araw_hi"], ar, al, a_rhi)

                @pl.when(act == _i(L_ADD_SYMBOL))
                def _():
                    put(st["sraw_lo"], lr, ll, s_rlo)
                    put(st["sraw_hi"], lr, ll, s_rhi)

            blo, bhi = bal_get(acc)
            bal_ok = rget(st["bal_u"], acc >> _i(7), acc & _i(127)) != _i(0)

            # ---------------- CREATE / TRANSFER / ADD_SYMBOL ----------
            create_ok = (act == _i(L_CREATE)) & ~bal_ok
            neg_sz = -size  # Java int negation (wraps at INT_MIN)
            transfer_ok = ((act == _i(L_TRANSFER)) & bal_ok
                           & ~_lt64(blo, bhi, *_sx(neg_sz)))
            addsym_ok = (act == _i(L_ADD_SYMBOL)) & ~bex_v

            @pl.when(create_ok)
            def _():
                put(st["bal_u"], acc >> _i(7), acc & _i(127), _i(1))

            @pl.when(transfer_ok)
            def _():
                bal_add(acc, *_sx(size))

            @pl.when(addsym_ok)
            def _():
                put(st["bex"], lr, ll, _i(1))

            # ---------------- cross-section scalar defaults -----------
            # sm: 0 trade_ok, 1 trade_acc, 2 cap_reject, 3 append,
            #     4 residual echo, 5 nfill, 6/7 tail prev lo/hi,
            #     8 do_rest, 9 cancel_ok, 10 emptied-maker count (dep
            #     plane decrement), 11 whether the LAST maker of the
            #     sweep was emptied (fixed mode: every maker before it
            #     was, so the host knows which resting orders a taker
            #     took off the book). The heavy sections below run
            #     under pl.when(act) branches (a NOP/CREATE message
            #     must not pay for hash probes or book reductions) and
            #     publish their scalar results here for the epilogue.
            sm[0] = _i(0)
            sm[1] = _i(0)
            sm[2] = _i(0)
            sm[3] = _i(0)
            sm[4] = size
            sm[5] = _i(0)
            sm[6] = _i(0)
            sm[7] = _i(0)
            sm[8] = _i(0)
            sm[9] = _i(0)
            sm[10] = _i(0)
            sm[11] = _i(0)

            # ================ TRADE section (pl.when-gated) ===========
            @pl.when(is_trade)
            def _trade_section():
                # -------- margin (checkBalance) -----------------------
                valid = ((limit >= _i(0)) & (limit < _i(126))
                         & (size > _i(0)))
                signed = jnp.where(is_buy, size, -size)
                if JAVA:
                    # the reference runs UNVALIDATED fields (no valid
                    # gate); out-of-domain values would corrupt the
                    # dense book layout, so they are a fatal
                    # device-envelope error
                    @pl.when(~valid)
                    def _():
                        set_err(_i(LERR_JAVA_DOMAIN))
                    e_actor, aerr = jfind(a_rlo, a_rhi, s_rlo, s_rhi)
                    palo, pahi, pvlo, pvhi = jvals(e_actor)
                else:
                    palo, pahi, pvlo, pvhi = pos_get(lane, acc)
                z64 = (_i(0), _i(0))
                nsg = _neg64(*_sx(signed))
                adjlo, adjhi = _sel64(
                    is_buy,
                    _max64(_min64((pvlo, pvhi), z64), nsg),
                    _min64(_max64((pvlo, pvhi), z64), nsg))
                unit = jnp.where(is_buy, limit, limit - _i(100))
                risk_lo, risk_hi = _muls64(signed + adjlo, unit)
                gates = bex_v & bal_ok if JAVA \
                    else (valid & bex_v & bal_ok)
                trade_ok = gates & ~_lt64(blo, bhi, risk_lo, risk_hi)

                # -------- phase 1: non-mutating sweep -----------------
                op_blk = side_blk("bp", lane, opp)
                os_blk = side_blk("bs", lane, opp)
                oq_blk = side_blk("bq", lane, opp)

                # working state lives in the vr scratch (rows 0..NR-1:
                # opp-side sizes, row NR: fill slots, row NR+1: fill
                # sizes): vector while-carries cost ~2us/iteration on
                # Mosaic (measured, scripts/exp_devpath.py round 5);
                # scratch rows + scalar-only carries make an iteration
                # tens of ns
                want = jnp.where(trade_ok, size, _i(0))

                # init UNCONDITIONALLY per trade message: the post-loop
                # reads (wsize at the Q2 ghost probe, the merged-book
                # w_blk select) run for every trade, including a
                # balance-rejected one (want == 0) — gating this on
                # `want > 0` would let those reads see the PREVIOUS
                # message's stale scratch rows
                vr[0:NR, :] = os_blk
                z = jnp.zeros((1, LN), I32)
                vr[NR:NR + 1, :] = z
                vr[NR + 1:NR + 2, :] = z

                def sweep(c):
                    # SELF-CONTAINED body: every vector it touches is a
                    # ref load or a recomputed iota — closure-captured
                    # vector VALUES become per-iteration loop inputs in
                    # Mosaic and cost ~2us/iteration (measured)
                    remaining, e, ovf, emptied, nempt, done = c
                    fi2 = (jax.lax.broadcasted_iota(I32, (NR, LN), 0)
                           * _i(LN)
                           + jax.lax.broadcasted_iota(I32, (NR, LN), 1))
                    ci2 = jax.lax.broadcasted_iota(I32, (1, LN), 1)
                    p_blk = side_blk("bp", lane, opp)
                    q_blk = side_blk("bq", lane, opp)
                    wsize = vr[0:NR, :]
                    cross = (wsize > _i(0)) & (
                        (p_blk - limit) * sgn <= _i(0))
                    pstar = jnp.min(jnp.where(cross, p_blk * sgn, BIG))
                    anyc = (pstar < BIG) & (remaining > _i(0))
                    at = cross & (p_blk * sgn == pstar)
                    sstar = jnp.min(jnp.where(at, q_blk, BIG))
                    at2 = at & (q_blk == sstar)
                    flat = jnp.min(jnp.where(at2, fi2, BIG))
                    have = MIN32 ^ jnp.max(
                        jnp.where(fi2 == flat, wsize ^ MIN32, MIN32))
                    fill = jnp.minimum(remaining, have)
                    exceed = anyc & (e >= _i(E))
                    take = anyc & ~exceed

                    @pl.when(take)
                    def _():
                        vr[0:NR, :] = jnp.where(fi2 == flat, wsize - fill,
                                                wsize)
                        fsr = vr[NR:NR + 1, :]
                        vr[NR:NR + 1, :] = jnp.where(ci2 == e, flat, fsr)
                        ffr = vr[NR + 1:NR + 2, :]
                        vr[NR + 1:NR + 2, :] = jnp.where(ci2 == e, fill, ffr)

                    remaining = remaining - jnp.where(take, fill, _i(0))
                    e = e + jnp.where(take, _i(1), _i(0))
                    ovf = ovf | exceed
                    # did the LAST executed trade exhaust its maker exactly?
                    # (the Q2 ghost-trade precondition: the reference loop
                    # re-evaluates its guard only after a maker empties)
                    emptied = jnp.where(take, have - fill == _i(0), emptied)
                    # emptied-maker COUNT: the dep plane's trade decrement
                    nempt = nempt + (take
                                     & (have - fill == _i(0))).astype(I32)
                    done = (~anyc) | exceed | (remaining == _i(0))
                    return remaining, e, ovf, emptied, nempt, done

                (residual_t, nfill, ovf_fills, last_emptied, nempt, _d) = \
                    jax.lax.while_loop(lambda c: ~c[5], sweep,
                                       (want, _i(0), False, False, _i(0),
                                        want == _i(0)))
                wsize = vr[0:NR, :]
                if JAVA:
                    # Q2 (KProcessor.java:237 precedence): with the taker
                    # exhausted, the guard parses to `maker.price >= limit`
                    # regardless of direction — when the last fill emptied
                    # its maker and the NEXT best maker satisfies it, ONE
                    # zero-size trade emits before `maker.size != 0` breaks
                    live_g = wsize > _i(0)
                    gbest = jnp.min(jnp.where(live_g, op_blk * sgn, BIG))
                    g_at = live_g & (op_blk * sgn == gbest)
                    g_ss = jnp.min(jnp.where(g_at, oq_blk, BIG))
                    g_at2 = g_at & (oq_blk == g_ss)
                    gflat = jnp.min(jnp.where(g_at2, fi, BIG))
                    gfc = jnp.where(gbest < BIG, gflat, _i(0))
                    g_price = pick2(op_blk, gfc)
                    ghost = (trade_ok & (residual_t == _i(0)) & last_emptied
                             & (gbest < BIG) & (g_price >= limit))
                    ghost_ok = ghost & (nfill < _i(E))

                    @pl.when(ghost & (nfill >= _i(E)))
                    def _():
                        set_err(_i(LERR_JAVA_CAP))

                    fsr = vr[NR:NR + 1, :]
                    vr[NR:NR + 1, :] = jnp.where(ghost_ok & (ci == nfill),
                                                 gfc, fsr)
                    ffr = vr[NR + 1:NR + 2, :]
                    vr[NR + 1:NR + 2, :] = jnp.where(
                        ghost_ok & (ci == nfill), _i(0), ffr)
                    nfill = nfill + ghost_ok.astype(I32)

                # ---------------- capacity envelope + Q9 ------------------
                w_blk = side_blk("bs", lane, side)      # own side sizes
                if JAVA:
                    # merged (Q1) books: the sweep just consumed from the
                    # SAME side the residual rests on — the free-slot
                    # search and the Q9 bucket tail must see POST-sweep
                    # sizes (the reference's bitmap bit is unset when the
                    # bucket empties mid-sweep, so the rest creates a NEW
                    # bucket with prev = null)
                    w_blk = jnp.where(is_trade & merged, wsize, w_blk)
                wp_blk = side_blk("bp", lane, side)
                wq_blk = side_blk("bq", lane, side)
                free_flat = jnp.min(jnp.where(w_blk == _i(0), fi, BIG))
                have_free = free_flat < BIG
                rest_want = trade_ok & (residual_t > _i(0))
                ovf_book = rest_want & ~have_free
                if JAVA:
                    # unbounded reference stores: hitting a device capacity
                    # is FATAL (sticky error), never a per-message REJECT
                    @pl.when(trade_ok & (ovf_fills | ovf_book))
                    def _():
                        set_err(_i(LERR_JAVA_CAP))

                    cap_reject = is_trade & False
                    trade_acc = trade_ok
                else:
                    cap_reject = trade_ok & (ovf_fills | ovf_book)
                    trade_acc = trade_ok & ~cap_reject
                do_rest = rest_want & trade_acc & have_free

                same_level = (w_blk > _i(0)) & (wp_blk == limit)
                bucket_nonempty = jnp.max(
                    jnp.where(same_level, _i(1), _i(0))) == _i(1)
                smax = jnp.max(jnp.where(same_level, wq_blk, _i(-1)))
                tail_at = same_level & (wq_blk == smax)
                tail_flat = jnp.min(jnp.where(tail_at, fi, BIG))
                tfc = jnp.where(bucket_nonempty, tail_flat, _i(0))
                tail_lo = pick2(side_blk("bo_lo", lane, side), tfc)
                tail_hi = pick2(side_blk("bo_hi", lane, side), tfc)
                append = bucket_nonempty & do_rest

                # ---------------- TRADE phase 2: apply --------------------
                @pl.when(trade_acc)
                def _():
                    # checkBalance debit + adj-write (before the fills, the
                    # reference's order — final state is order-invariant
                    # but the position write must precede fill updates of
                    # the SAME key)
                    bal_add(acc, *_neg64(risk_lo, risk_hi))
                    adj_nz = (adjlo != _i(0)) | (adjhi != _i(0))

                    @pl.when(adj_nz)
                    def _():
                        nvlo, nvhi = _add64(pvlo, pvhi, *_neg64(adjlo, adjhi))
                        if JAVA:
                            # 3-arg setPosition: the REAL key keeps its
                            # amount, only `available` moves
                            # (KProcessor.java:179, exempt from Q11)
                            jwrite(e_actor, a_rlo, a_rhi, s_rlo, s_rhi,
                                   palo, pahi, nvlo, nvhi)
                        else:
                            pos_set(lane, acc, palo, pahi, nvlo, nvhi)

                    # maker size writeback (size==0 deletes the slot)
                    side_put("bs", lane, opp, wsize)

                    def apply_fill(e2, _c):
                        # self-contained: blocks load inside (captured
                        # vectors become per-iteration loop inputs)
                        oa_blk = side_blk("ba", lane, opp)
                        olo_blk = side_blk("bo_lo", lane, opp)
                        ohi_blk = side_blk("bo_hi", lane, opp)
                        mp_blk = side_blk("bp", lane, opp)
                        flat = pick(vr[NR:NR + 1, :], e2)
                        fill = pick(vr[NR + 1:NR + 2, :], e2)
                        maid_raw_plane = pick2(oa_blk, flat)
                        maid = (maid_raw_plane & AMASK) if JAVA \
                            else maid_raw_plane
                        mprice = pick2(mp_blk, flat)
                        p = fill_total + e2
                        pc = jnp.minimum(p, _i(FB - 1))

                        @pl.when(p < _i(FB))
                        def _():
                            fill_put(0, pc, pick2(olo_blk, flat))
                            fill_put(1, pc, pick2(ohi_blk, flat))
                            fill_put(2, pc, maid)
                            fill_put(3, pc, mprice)
                            fill_put(4, pc, fill)

                        # maker fill then taker fill (executeTrade order)
                        msz = jnp.where(is_buy, -fill, fill)
                        tsz = jnp.where(is_buy, fill, -fill)
                        if JAVA:
                            mr, ml = maid >> _i(7), maid & _i(127)
                            m_rlo = rget(st["araw_lo"], mr, ml)
                            m_rhi = rget(st["araw_hi"], mr, ml)
                            me = jfill_one(m_rlo, m_rhi, s_rlo, s_rhi, msz)
                            te = jfill_one(a_rlo, a_rhi, s_rlo, s_rhi, tsz)

                            @pl.when(me | te)
                            def _():
                                set_err(_i(LERR_HASH_FULL))
                        else:
                            fill_one(lane, maid, msz)
                            fill_one(lane, acc, tsz)
                        # taker credit: int*int wraps at i32 before the
                        # long add (KProcessor.java:286); maker credit is 0
                        bal_add(acc, *_sx(tsz * (limit - mprice)))

                        return _c

                    # peeled: fill 0 straight-line, loop only for 2+
                    @pl.when(nfill > _i(0))
                    def _():
                        apply_fill(_i(0), _i(0))

                    @pl.when(nfill > _i(1))
                    def _():
                        jax.lax.while_loop(
                            lambda c: c[0] < nfill,
                            lambda c: (c[0] + _i(1),
                                       apply_fill(c[0], c[1])),
                            (_i(1), _i(0)))

                    @pl.when(fill_total + nfill > _i(FB))
                    def _():
                        set_err(_i(LERR_FILLBUF_FULL))

                    # rest the residual
                    @pl.when(do_rest)
                    def _():
                        seqv = rget(st["seqc"], lr, ll)
                        slot_write("bo_lo", lane, side, free_flat, t_oidlo)
                        slot_write("bo_hi", lane, side, free_flat, t_oidhi)
                        ba_val = (acc | (is_buy.astype(I32) << _i(30))) \
                            if JAVA else acc
                        slot_write("ba", lane, side, free_flat, ba_val)
                        slot_write("bp", lane, side, free_flat, limit)
                        slot_write("bs", lane, side, free_flat, residual_t)
                        slot_write("bq", lane, side, free_flat, seqv)
                        put(st["seqc"], lr, ll, seqv + _i(1))

                # publish section results for the epilogue
                sm[0] = trade_ok.astype(I32)
                sm[1] = trade_acc.astype(I32)
                sm[2] = cap_reject.astype(I32)
                sm[3] = append.astype(I32)
                sm[4] = jnp.where(trade_acc, residual_t, size)
                sm[5] = jnp.where(trade_acc, nfill, _i(0))
                sm[6] = tail_lo
                sm[7] = tail_hi
                sm[8] = do_rest.astype(I32)
                sm[10] = jnp.where(trade_acc, nempt, _i(0))
                if not JAVA:
                    sm[11] = (trade_acc & (nfill > _i(0))
                              & last_emptied).astype(I32)

            # ---------------- CANCEL ----------------------------------
            # (pl.when-gated: only cancels pay for the
            # both-sides oid search)
            @pl.when(is_cancel)
            def _cancel_section():
                # search both sides for the oid among occupied slots
                b0 = side_blk("bo_lo", lane, _i(0))
                b0h = side_blk("bo_hi", lane, _i(0))
                s0 = side_blk("bs", lane, _i(0))
                b1 = side_blk("bo_lo", lane, _i(1))
                b1h = side_blk("bo_hi", lane, _i(1))
                s1 = side_blk("bs", lane, _i(1))
                hit0 = (s0 > _i(0)) & (b0 == t_oidlo) & (b0h == t_oidhi)
                hit1 = (s1 > _i(0)) & (b1 == t_oidlo) & (b1h == t_oidhi)
                f0 = jnp.min(jnp.where(hit0, fi, BIG))
                f1 = jnp.min(jnp.where(hit1, fi, BIG))
                c_side = jnp.where(f0 < BIG, _i(0), _i(1))
                c_flat = jnp.where(f0 < BIG, f0, f1)
                hit_any = is_cancel & (c_flat < BIG)
                cfc = jnp.where(hit_any, c_flat, _i(0))
                c_ba = pick2(side_blk("ba", lane, c_side), cfc)
                c_aid = (c_ba & AMASK) if JAVA else c_ba
                # merged (Q1) books hold both directions in side 0, so java
                # reads the order's direction from the ba tag bit
                c_isbuy = ((c_ba >> _i(30)) & _i(1)) == _i(1) if JAVA \
                    else c_side == _i(0)
                c_price = pick2(side_blk("bp", lane, c_side), cfc)
                c_size = pick2(side_blk("bs", lane, c_side), cfc)
                cancel_ok = hit_any & (c_aid == acc)

                @pl.when(cancel_ok)
                def _():
                    slot_write("bs", lane, c_side, c_flat, _i(0))
                    if JAVA:
                        # postRemoveAdjustments is Q11-CORRUPTED too
                        # (KProcessor.java:332, 2-arg setPosition): the
                        # adj-write lands on the VALUE-as-key target, the
                        # real (aid, sid) entry stays untouched
                        e_c, _ce = jfind(a_rlo, a_rhi, s_rlo, s_rhi)
                        calo, cahi, cvlo, cvhi = jvals(e_c)
                        cblo, cbhi = _add64(calo, cahi, *_neg64(cvlo, cvhi))
                        csigned = jnp.where(c_isbuy, c_size, -c_size)
                        cz = (_i(0), _i(0))
                        cns = _neg64(*_sx(csigned))
                        cjlo, cjhi = _sel64(
                            c_isbuy,
                            _max64(_min64((cblo, cbhi), cz), cns),
                            _min64(_max64((cblo, cbhi), cz), cns))
                        cunit = jnp.where(c_isbuy, c_price,
                                          c_price - _i(100))
                        rlo, rhi = _muls64(csigned + cjlo, cunit)
                        c_nz = (cjlo != _i(0)) | (cjhi != _i(0))

                        @pl.when(c_nz)
                        def _():
                            nvlo, nvhi = _add64(cvlo, cvhi, cjlo, cjhi)
                            s2, _f2, ce2 = jslot_for_insert(
                                calo, cahi, cvlo, cvhi)
                            jwrite(s2, calo, cahi, cvlo, cvhi,
                                   calo, cahi, nvlo, nvhi)

                            @pl.when(ce2)
                            def _():
                                set_err(_i(LERR_HASH_FULL))
                    else:
                        rlo, rhi = release_margin(lane, acc, c_isbuy,
                                                  c_price, c_size)
                    bal_add(acc, rlo, rhi)

                sm[9] = cancel_ok.astype(I32)

            # ---------------- BARRIERS (payout / remove) --------------
            barrier_do = is_barrier & bex_v if not JAVA \
                else is_barrier & False

            @pl.when(barrier_do)
            def _():
                if JAVA:
                    return  # the java router never routes barriers
                # wipe both sides with margin release, buy side first,
                # (price, seq) order within a side (_wipe_book_fixed)
                def wipe_side(wside):
                    pb = side_blk("bp", lane, wside)
                    qb = side_blk("bq", lane, wside)
                    ab = side_blk("ba", lane, wside)

                    def w_body(c):
                        _k, done = c
                        sb = side_blk("bs", lane, wside)
                        used = sb > _i(0)
                        pmin = jnp.min(jnp.where(used, pb, BIG))
                        anyu = pmin < BIG

                        pm = jnp.where(anyu, pmin, _i(0))
                        at = used & (pb == pm)
                        smin = jnp.min(jnp.where(at, qb, BIG))
                        at2 = at & (qb == smin)
                        flat = jnp.min(jnp.where(at2, fi, BIG))
                        fc = jnp.where(anyu, flat, _i(0))

                        @pl.when(anyu)
                        def _():
                            o_aid = pick2(ab, fc)
                            o_price = pick2(pb, fc)
                            o_size = pick2(sb, fc)
                            slot_write("bs", lane, wside, fc, _i(0))
                            rlo, rhi = release_margin(
                                lane, o_aid, wside == _i(0),
                                o_price, o_size)
                            bal_add(o_aid, rlo, rhi)
                            sm[19] = sm[19] + _i(1)

                        return _k + _i(1), ~anyu

                    jax.lax.while_loop(lambda c: ~c[1], w_body,
                                       (_i(0), False))

                wipe_side(_i(0))
                wipe_side(_i(1))
                put(st["bex"], lr, ll, _i(0))

                # payout: credit (YES) / just delete (NO) the lane's
                # positions — its PTL consecutive tiles of the store;
                # zeros ARE deletion (the absence invariant)
                is_payout = act != _i(L_REMOVE_SYMBOL)
                do_credit = act == _i(L_PAYOUT_YES)

                @pl.when(is_payout)
                def _():
                    def scan_tile(b, _c):
                        pos_bring(lane * _i(PTL) + b)

                        def credit_half(half):
                            arow_lo = pscr[4 * half:4 * half + 1, :]
                            arow_hi = pscr[4 * half + 1:4 * half + 2, :]
                            live = (arow_lo != _i(0)) | (arow_hi != _i(0))
                            acc0 = (b * _i(POS_TILE_ACCOUNTS)
                                    + _i(LN * half))

                            @pl.when(jnp.max(jnp.where(live, _i(1), _i(0)))
                                     == _i(1))
                            def _():
                                def credit_one(c):
                                    rem, done = c
                                    l2 = jnp.min(jnp.where(
                                        rem > _i(0), ci, BIG))
                                    anyl = l2 < BIG
                                    lc = jnp.where(anyl, l2, _i(0))

                                    @pl.when(anyl)
                                    def _():
                                        plo, phi = _mul64(
                                            pick(arow_lo, lc),
                                            pick(arow_hi, lc), *_sx(size))
                                        bal_add(acc0 + lc, plo, phi)
                                        sm[20] = sm[20] + _i(1)

                                    rem = jnp.where(ci == lc, _i(0), rem)
                                    return rem, ~anyl

                                jax.lax.while_loop(
                                    lambda c: ~c[1], credit_one,
                                    (jnp.where(live, _i(1), _i(0)), False))

                        @pl.when(do_credit)
                        def _():
                            credit_half(0)
                            credit_half(1)

                        pscr[...] = jnp.zeros((POS_TILE_ROWS, LN), I32)
                        sm[17] = _i(1)
                        return _c

                    _fori32(PTL, scan_tile, _i(0))

            # ---------------- outputs + metrics -----------------------
            t_ok = sm[0] != _i(0)
            t_acc = sm[1] != _i(0)
            capr = sm[2] != _i(0)
            appnd = sm[3]
            resid_v = sm[4]
            nf = sm[5]
            c_ok = sm[9] != _i(0)

            # ------------- dep plane + distribution histograms --------
            # fills-per-order: one observation per ACCEPTED trade
            hist_obs(t_acc, HIST_LANE0, nf)
            if not JAVA:
                # per-lane occupied-slot count: +rested -emptied on an
                # accepted trade, -1 on a cancel, wiped by a barrier;
                # the post-message value feeds the book-depth histogram
                @pl.when(t_acc | c_ok | barrier_do)
                def _():
                    dval = rget(st["dep"], lr, ll)
                    newd = jnp.where(
                        barrier_do, _i(0),
                        dval + sm[8] - sm[10] - c_ok.astype(I32))
                    put(st["dep"], lr, ll, newd)
                    hist_obs(t_acc | c_ok,
                             HIST_LANE0 + N_HIST_BUCKETS, newd)

            ok = jnp.where(
                is_trade, t_acc,
                jnp.where(is_cancel, c_ok,
                          jnp.where(act == _i(L_CREATE), create_ok,
                                    jnp.where(act == _i(L_TRANSFER),
                                              transfer_ok,
                                              jnp.where(
                                                  act == _i(L_ADD_SYMBOL),
                                                  addsym_ok,
                                                  jnp.where(
                                                      is_barrier,
                                                      barrier_do,
                                                      act == _i(L_NOP)))))))
            # bit 3 rides the flags region the fetch already brings: no
            # plane grows for it
            flags = (ok.astype(I32) | (capr.astype(I32) << _i(1))
                     | (appnd << _i(2)) | (sm[11] << _i(3)))
            out_put(_i(1), m, flags)
            out_put(_i(1 + BR), m, resid_v)
            out_put(_i(1 + 2 * BR), m, nf)
            out_put(_i(1 + 3 * BR), m, sm[6])
            out_put(_i(1 + 4 * BR), m, sm[7])

            filled = jnp.where(t_acc, size - resid_v, _i(0))
            cnt = lambda c: c.astype(I32)
            met = (
                met[0] + cnt(act != _i(L_NOP)),
                met[1] + cnt(t_acc),
                met[2] + nf,
                met[3] + filled,
                met[4] + cnt(capr),
                met[5] + cnt(is_trade & ~t_ok),
                met[6] + sm[8],
                met[7] + cnt(c_ok),
                met[8] + cnt(is_cancel & ~c_ok),
                met[9] + cnt(transfer_ok),
                met[10] + cnt(((act == _i(L_CREATE)) & ~create_ok)
                              | ((act == _i(L_TRANSFER)) & ~transfer_ok)
                              | ((act == _i(L_ADD_SYMBOL)) & ~addsym_ok)),
                met[11] + cnt(barrier_do),
            )
            fill_total2 = fill_total + nf
            return (fill_total2, cur_lane, met)

        # per-call histogram deltas accumulate in the scratch row,
        # pre-offset to their final scalar-row lanes
        vr[NR + 2:NR + 3, :] = jnp.zeros((1, LN), I32)
        sm[16] = _i(-1)
        sm[17] = _i(0)
        sm[18] = _i(0)
        sm[19] = _i(0)
        sm[20] = _i(0)
        met0 = tuple(_i(0) for _ in range(N_METRICS))
        fill_total, cur_lane, met = _fori32(
            B, one, (_i(0), _i(-1), met0))
        if HBM:
            @pl.when(cur_lane >= _i(0))
            def _():
                books_flush(cur_lane)
        if not JAVA:
            @pl.when(sm[17] != _i(0))
            def _():
                pos_copy(sm[16], True)

        # batch occupancy: ONE observation per non-empty kernel call
        # (met[0] = this call's non-NOP message count)
        hist_obs(met[0] > _i(0), HIST_LANE0 + 2 * N_HIST_BUCKETS, met[0])

        # scalar row: lane0 err, lane1 fill_total, lanes 2.. metrics,
        # lanes HIST_LANE0.. the histogram deltas (already in place in
        # the scratch row), lane POS_TILES_LANE the position tiles
        # this call brought in from HBM, then the barrier section's
        # wiped orders and credited positions
        errv = pick(st["err"][0:1, :], _i(0))
        scal = jnp.where(ci == _i(0), errv, _i(0))
        scal = jnp.where(ci == _i(1), fill_total, scal)
        for k in range(N_METRICS):
            scal = jnp.where(ci == _i(2 + k), met[k], scal)
        hr = vr[NR + 2:NR + 3, :]
        scal = jnp.where(
            (ci >= _i(HIST_LANE0))
            & (ci < _i(HIST_LANE0 + N_HIST * N_HIST_BUCKETS)), hr, scal)
        scal = jnp.where(ci == _i(POS_TILES_LANE), sm[18], scal)
        scal = jnp.where(ci == _i(WIPED_LANE), sm[19], scal)
        scal = jnp.where(ci == _i(CREDITED_LANE), sm[20], scal)
        out[0:1, :] = scal

    nstate = len(KEYS)
    MSG_FIELDS = ("act", "oid_lo", "oid_hi", "aid", "price", "size",
                  "lane") + (("aidr_lo", "aidr_hi", "sidr_lo",
                              "sidr_hi", "flags") if JAVA else ())

    def _spec(key):
        if (HBM and key in BOOK_KEYS) or key == "pos":
            return pl.BlockSpec(memory_space=pl.ANY)
        return pl.BlockSpec(memory_space=pltpu.VMEM)

    scratches = [pltpu.SMEM((24,), I32),
                 pltpu.VMEM((NR + 3, LN), I32)] \
        + ([pltpu.VMEM((2 * NR, LN), I32)] * 6
           + [pltpu.SemaphoreType.DMA((6,))] if HBM else []) \
        + ([pltpu.VMEM((POS_TILE_ROWS, LN), I32),
            pltpu.SemaphoreType.DMA((1,))] if not JAVA else [])

    def raw_call(state, msgs):
        outs = pl.pallas_call(
            kernel,
            out_shape=tuple(
                [jax.ShapeDtypeStruct(state[k].shape, I32)
                 for k in KEYS]
                + [jax.ShapeDtypeStruct((NROWS, LN), I32)]),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * NSMEM
            + [_spec(k) for k in KEYS],
            out_specs=tuple([_spec(k) for k in KEYS]
                            + [pl.BlockSpec(memory_space=pltpu.VMEM)]),
            input_output_aliases={NSMEM + k: k for k in range(nstate)},
            scratch_shapes=scratches,
            interpret=_jaxsetup.interpret(),
            # the kernel's stable name (and named scope) in a device
            # trace, whatever the program around it is called
            name="seq_step",
        )(*[msgs[f] for f in MSG_FIELDS],
          *[state[k] for k in KEYS])
        new_state = dict(zip(KEYS, outs[:nstate]))
        return new_state, outs[nstate]

    # NOTE: jit-level donation composes badly with the pallas-level
    # input_output_aliases (the donated state buffers get clobbered and
    # the aliased outputs read zeros — observed under interpret); the
    # aliasing alone keeps the in-kernel copy semantics, at the cost of
    # one XLA copy of the state per call (~10MB, ~12us on v5e).
    return jax.jit(raw_call), raw_call


@functools.lru_cache(maxsize=None)
def build_seq_scan(cfg: SeqConfig, k: int):
    """ONE jitted dispatch for k chunks: lax.scan threads the state
    through k kernel invocations and stacks the k output planes on
    device. Every separate dispatch/fetch is a host round trip, so a
    100k-message stream runs as one scan call + two sliced fetches
    instead of ~26 of each."""
    _, raw_call = build_seq_step(cfg)

    def call_scan(state, stacked):
        def body(st, ms):
            st2, outp = raw_call(st, ms)
            return st2, outp

        return jax.lax.scan(body, state, stacked, length=k)

    return jax.jit(call_scan)


def step_cost_analysis(cfg: SeqConfig, k: int = 4):
    """Compiled-scan cost model for the profiler's device plane
    (telemetry/profiler.py): lower + compile a k-chunk NOP batch and
    read XLA's `cost_analysis()` — flops and bytes touched per
    dispatch, normalized to {"flops", "bytes_accessed"}. The lowering
    hits the same jit cache the serving path warms, so calling this on
    a live session costs one metadata read, not a recompile. Returns
    None when the backend exposes no cost model (never raises — the
    profiler degrades, the engine does not)."""
    try:
        state = make_seq_state(cfg)
        cols = {name: np.zeros(cfg.batch, np.int64)
                for name in ("act", "aid", "price", "size", "lane",
                             "oid", "aid_raw", "sid_raw", "flags")}
        one = pack_msgs(cfg, cols, 0)
        stacked = {name: np.broadcast_to(
            v, (k,) + v.shape).copy() for name, v in one.items()}
        compiled = build_seq_scan(cfg, k).lower(state, stacked).compile()
        ca = compiled.cost_analysis()
    except Exception:   # noqa: BLE001 — cost probe only, never fatal
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = ca.get("flops")
    nbytes = ca.get("bytes accessed", ca.get("bytes_accessed"))
    out = {}
    if isinstance(flops, (int, float)) and flops > 0:
        out["flops"] = float(flops)
    if isinstance(nbytes, (int, float)) and nbytes > 0:
        out["bytes_accessed"] = float(nbytes)
    return out or None


# ---------------------------------------------------------------------------
# host-side packing / unpacking

def pack_msgs(cfg: SeqConfig, cols: dict, n: int) -> dict:
    """Columnar router output (numpy, length n <= batch) -> padded
    (B,) i32 input dict. Padding entries are NOPs.

    Single-chunk convenience for tests and __graft_entry__; the serving
    path packs ALL chunks at once in SeqSession._plan (vectorized twin
    of this layout — keep the two in sync)."""
    B = cfg.batch

    def split64(name, src64):
        v = np.zeros(B, np.int64)
        v[:n] = src64[:n]
        return {f"{name}_lo": (v & 0xFFFFFFFF).astype(np.uint32)
                .astype(np.int32),
                f"{name}_hi": (v >> 32).astype(np.int32)}

    out = {}
    for k in ("act", "aid", "price", "size", "lane"):
        a = np.zeros(B, np.int32)
        a[:n] = cols[k][:n]
        out[k] = a
    out.update(split64("oid", cols["oid"]))
    if cfg.compat == "java":
        out.update(split64("aidr", cols["aid_raw"]))
        out.update(split64("sidr", cols["sid_raw"]))
        fl = np.zeros(B, np.int32)
        fl[:n] = cols["flags"][:n]
        out["flags"] = fl
    return out


def unpack_hdr(cfg: SeqConfig, hdr: np.ndarray, n: int) -> dict:
    """Header slice (hdr_rows, 128) -> per-message host dict + scalars."""
    B = cfg.batch
    BR = B // LN
    flat = hdr.reshape(-1)
    scal = flat[:LN]
    base = LN
    flags = flat[base:base + B][:n]
    res = {
        "ok": (flags & 1) != 0,
        "cap_reject": (flags & 2) != 0,
        "append": (flags & 4) != 0,
        # fixed mode: the last maker of this taker's sweep was emptied
        # (the makers before it always are)
        "last_emptied": (flags & 8) != 0,
        "residual": flat[base + BR * LN:base + BR * LN + B][:n],
        "nfill": flat[base + 2 * BR * LN:base + 2 * BR * LN + B][:n],
        "prev_oid": ((flat[base + 3 * BR * LN:base + 3 * BR * LN + B][:n]
                      .astype(np.int64) & 0xFFFFFFFF)
                     | (flat[base + 4 * BR * LN:base + 4 * BR * LN + B][:n]
                        .astype(np.int64) << 32)),
        "err": int(scal[0]),
        "fill_total": int(scal[1]),
        "metrics": scal[2:2 + N_METRICS].astype(np.int64),
        "pos_tiles": int(scal[POS_TILES_LANE]),
        "wiped": int(scal[WIPED_LANE]),
        "credited": int(scal[CREDITED_LANE]),
        "hist": scal[HIST_LANE0:HIST_LANE0 + N_HIST * N_HIST_BUCKETS]
        .astype(np.int64).reshape(N_HIST, N_HIST_BUCKETS),
    }
    return res


def unpack_fills(groups: np.ndarray, ftot: int) -> np.ndarray:
    """Fill group rows (5g, 128) -> (4, ftot) [oid, aid, price, size]."""
    if ftot == 0:
        return np.zeros((4, 0), np.int64)
    g = groups.reshape(-1, 5, LN)
    per = np.transpose(g, (1, 0, 2)).reshape(5, -1)
    f_oid = ((per[0, :ftot].astype(np.int64) & 0xFFFFFFFF)
             | (per[1, :ftot].astype(np.int64) << 32))
    return np.stack([f_oid,
                     per[2, :ftot].astype(np.int64),
                     per[3, :ftot].astype(np.int64),
                     per[4, :ftot].astype(np.int64)])


def unpack_out(cfg: SeqConfig, plane: np.ndarray, n: int) -> dict:
    """Whole-plane unpack (tests / single-shot paths)."""
    HR = hdr_rows(cfg)
    res = unpack_hdr(cfg, plane[:HR], n)
    ftot = res["fill_total"]
    groups = plane[HR:HR + 5 * (-(-max(ftot, 1) // LN))]
    res["fills"] = unpack_fills(groups, ftot)
    return res


def _j64(lo, hi):
    return (lo.astype(np.int64) & 0xFFFFFFFF) | (hi.astype(np.int64) << 32)


def export_java(cfg: SeqConfig, state) -> dict:
    """Host view of a JAVA-mode state: positions keyed by the 128-bit
    (ka, kb) pairs exactly as the java oracle's dict (real keys
    (aid, sid) AND Q11 garbage keys (amount, available)); orders carry
    the direction tag; seq/book planes as in fixed mode."""
    assert cfg.compat == "java"
    S, N, NR = cfg.lanes, cfg.slots, cfg.nr
    h = {k: np.asarray(state[k]) for k in state_keys(cfg)}

    def planes2slot(lo, hi=None):
        v = lo.reshape(S, 2, NR * LN)[:, :, :N]
        if hi is None:
            return v
        return ((v.astype(np.int64) & 0xFFFFFFFF)
                | (hi.reshape(S, 2, NR * LN)[:, :, :N].astype(np.int64)
                   << 32))

    live = h["hstate"].reshape(-1) == 1
    ka = _j64(h["hka_lo"].reshape(-1), h["hka_hi"].reshape(-1))[live]
    kb = _j64(h["hkb_lo"].reshape(-1), h["hkb_hi"].reshape(-1))[live]
    amt = _j64(h["ha_lo"].reshape(-1), h["ha_hi"].reshape(-1))[live]
    av = _j64(h["hv_lo"].reshape(-1), h["hv_hi"].reshape(-1))[live]
    positions = {(int(a), int(b)): (int(x), int(y))
                 for a, b, x, y in zip(ka, kb, amt, av)}
    A = cfg.accounts
    bal = _j64(h["bal_lo"].reshape(-1)[:A], h["bal_hi"].reshape(-1)[:A])
    return {
        "positions": positions,
        "bal": bal,
        "bal_used": h["bal_u"].reshape(-1)[:A] != 0,
        "slot_oid": planes2slot(h["bo_lo"], h["bo_hi"]),
        "slot_ba": planes2slot(h["ba"]).astype(np.int64),
        "slot_price": planes2slot(h["bp"]).astype(np.int32),
        "slot_size": planes2slot(h["bs"]).astype(np.int32),
        "book_exists": h["bex"].reshape(-1)[:S] != 0,
        "err": np.int32(h["err"].reshape(-1)[0]),
    }


# ---------------------------------------------------------------------------
# canonical state import/export for checkpoints

def _pos_views(cfg: SeqConfig, pos, both):
    """The same words seen from both sides: `pos` (pos_rows, 128) i32 as
    [lane, tile, half, value, column], and `both` (2, S, PTL*256) i64
    [amount | available, lane, account] as its i32 words [.., lo | hi]
    (little-endian). Value plane k of `pos` (amt lo, amt hi, avail lo,
    avail hi) IS word k & 1 of value k >> 1 — so each way is four
    strided copies and no pass widens, shifts or ors (at 1024 x 4096 a
    snapshot moves 64 MiB, and that arithmetic is five passes over
    twice as much)."""
    S, PTL = cfg.lanes, cfg.pos_tiles_per_lane
    rows = pos.reshape(S, PTL, 2, 4, LN)
    words = both.view(np.int32).reshape(2, S, PTL, 2, LN, 2)
    return [(rows[:, :, :, k, :], words[k >> 1, ..., k & 1])
            for k in range(4)]


def pos_to_values(cfg: SeqConfig, pos: np.ndarray) -> np.ndarray:
    """`pos` plane -> (2, S, PTL*256) i64 [amount, available]."""
    both = np.empty((2, cfg.lanes,
                     cfg.pos_tiles_per_lane * POS_TILE_ACCOUNTS), np.int64)
    for plane, word in _pos_views(cfg, pos, both):
        word[...] = plane
    return both


def values_to_pos(cfg: SeqConfig, both: np.ndarray) -> np.ndarray:
    """Inverse of pos_to_values."""
    pos = np.empty((cfg.pos_rows, LN), np.int32)
    for plane, word in _pos_views(cfg, pos, both):
        plane[...] = word
    return pos


def _small_sections(cfg: SeqConfig, h: dict) -> dict:
    """The canonical sections that are S or A long."""
    S, A = cfg.lanes, cfg.accounts
    return {
        "seq": h["seqc"].reshape(-1)[:S].astype(np.int32),
        "book_exists": h["bex"].reshape(-1)[:S] != 0,
        "bal": _j64(h["bal_lo"].reshape(-1)[:A], h["bal_hi"].reshape(-1)[:A]),
        "bal_used": h["bal_u"].reshape(-1)[:A] != 0,
        "err": np.int32(h["err"].reshape(-1)[0]),
    }


def _dense_books(cfg: SeqConfig, h: dict) -> dict:
    S, N, NR = cfg.lanes, cfg.slots, cfg.nr

    def planes2slot(v):
        return v.reshape(S, 2, NR * LN)[:, :, :N]

    slot_size = planes2slot(h["bs"]).astype(np.int32)
    return {
        "slot_oid": _j64(planes2slot(h["bo_lo"]), planes2slot(h["bo_hi"])),
        "slot_aid": planes2slot(h["ba"]).astype(np.int32),
        "slot_price": planes2slot(h["bp"]).astype(np.int32),
        "slot_size": slot_size,
        "slot_seq": planes2slot(h["bq"]).astype(np.int32),
        "slot_used": slot_size > 0,
    }


def _dense_positions(cfg: SeqConfig, h: dict) -> dict:
    pos_amt, pos_avail = (v[:, :cfg.accounts].reshape(-1)
                          for v in pos_to_values(cfg, h["pos"]))
    return {"pos_amt": pos_amt, "pos_avail": pos_avail}


def export_canonical(cfg: SeqConfig, state) -> dict:
    """Device planes -> the canonical snapshot layout (slot_* (S,2,N)
    i64/i32/bool, flat positions s64, bal s64), independent of the
    device's planes and tiles. Fixed mode only:
    java-mode state has its OWN canonical form (128-bit position keys,
    direction-tagged merged books) in runtime/javasnap.py."""
    if cfg.compat != "fixed":
        raise ValueError(
            "java-mode state has no fixed-layout canonical export — "
            "snapshot via runtime/javasnap.export_seqjava")
    h = {k: np.asarray(state[k]) for k in _STATE_KEYS}
    return {
        **_dense_books(cfg, h),
        **_small_sections(cfg, h),
        **_dense_positions(cfg, h),
        "metrics": None,  # counters are host-accumulated in SeqSession
    }


# the two sections of the canonical layout that scale with capacity,
# and the keys each holds when it is written by its live entries: an
# index into the dense section's flat space, then one value per entry
SPARSE_SECTIONS = {
    "books": ("slot_idx", "slot_oid", "slot_aid", "slot_price",
              "slot_size", "slot_seq"),
    "positions": ("pos_idx", "pos_amt", "pos_avail"),
}
# bytes of one entry, dense and live (an i64 index more, no `used` flag)
_SLOT_DENSE_B, _SLOT_LIVE_B = 8 + 4 * 4 + 1, 8 + 8 + 4 * 4
_POS_DENSE_B, _POS_LIVE_B = 2 * 8, 8 + 2 * 8


def _scan128(x, reduce, combine, small=False):
    """Inclusive scan of a non-negative i32 vector under sum or max, in
    levels of 128: inside each group of 128 a masked reduction (for a
    sum of `small` values, <= 128, a triangular matmul: bf16 holds them
    exactly and their sums, <= 16,384, accumulate in f32), then the
    groups' last values scanned one level up. (XLA's TPU compiler takes
    3 s over a jnp.cumsum of 131,072 elements and 0.2 s over this.)"""
    n = x.shape[0]
    i = jnp.arange(LN, dtype=I32)
    if n <= LN:
        return reduce(jnp.where(i[None, :n] <= i[:n, None], x[None, :],
                                _i(0)), axis=1)
    G = -(-n // LN)
    m = jnp.pad(x, (0, G * LN - n)).reshape(G, LN)
    if small:
        within = jnp.dot(m.astype(jnp.bfloat16),
                         (i[:, None] <= i[None, :]).astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32).astype(I32)
    else:
        within = reduce(jnp.where(i[None, None, :] <= i[None, :, None],
                                  m[:, None, :], _i(0)), axis=2)
    upto = _scan128(within[:, -1], reduce, combine)
    before = jnp.concatenate([jnp.zeros((1,), I32), upto[:-1]])
    return combine(within, before[:, None]).reshape(-1)[:n]


def _prefix_sum(x, small=False):
    """jnp.cumsum(x) of an i32 vector (`small`: every value <= 128)."""
    return _scan128(x, functools.partial(jnp.sum, dtype=I32), jnp.add,
                    small)


def _running_max(x):
    """Inclusive running maximum of a non-negative i32 vector."""
    return _scan128(x, jnp.max, jnp.maximum)


def live_rows_chunk(cfg: SeqConfig) -> int:
    """Rows one call of build_seq_live_rows' program returns: a
    thirty-second of a book plane's rows, in whole sublane tiles of 8
    (4,096 rows = 2 MiB a plane at 1024 lanes x 8192 slots)."""
    return max(8, -(-(2 * cfg.lanes * cfg.nr // 32) // 8) * 8)


@functools.lru_cache(maxsize=None)
def build_seq_live_rows(cfg: SeqConfig):
    """The books' half of export_snapshot's fetch: ONE jitted program,
    (the six book planes, row) -> (live rows in a plane, the ascending
    indices of the first live_rows_chunk(cfg) live rows at or after
    `row`, those rows of each plane), so that a snapshot fetches the
    rows that hold orders and not the planes. A row is live where any
    of its slots has `bs > 0`; past the last live row the indices read
    2 * S * NR and the rows are padding. A resting order takes the
    lowest free slot of its side, so the live rows are the first few of
    each side however deep the books are configured. The program reads
    only: nothing is donated."""
    rows, R = 2 * cfg.lanes * cfg.nr, live_rows_chunk(cfg)

    def live_rows(planes, start):
        live = jnp.any(planes[_BS] > 0, axis=1)
        row = jnp.arange(rows, dtype=I32)
        # rank[r] = live rows in [start, r]: the k-th of them is the
        # first r whose rank reaches k
        rank = _prefix_sum((live & (row >= start)).astype(I32), small=True)
        idx = jnp.searchsorted(rank, jnp.arange(1, R + 1, dtype=I32),
                               side="left").astype(I32)
        take = jnp.minimum(idx, _i(rows - 1))
        return (jnp.sum(live, dtype=I32), idx,
                tuple(p[take] for p in planes))

    return jax.jit(live_rows)


def live_rows_call(cfg: SeqConfig, state, start=0):
    """One call of build_seq_live_rows' program on `state`'s planes,
    not fetched — the one place that says what the program is called
    with (SeqSession.__init__ compiles it by this call)."""
    return build_seq_live_rows(cfg)(
        tuple(state[k] for k in BOOK_KEYS), _i(start))


def live_positions_chunk(cfg: SeqConfig) -> int:
    """Entries one call of build_seq_live_positions' program returns: a
    sixty-fourth of the store's capacity in whole rows of 128, no fewer
    than 8,192 (or all the store can hold) and no more than 262,144 (20
    bytes an entry: 5 MB a call). What a call costs the host is the
    chunk it fetches, live or padding, so the chunk is sized by what a
    store of that capacity is seen to hold (1024 x 2048 holds about
    100,000 positions: 32,768 a call; 3425 x 25,088 holds 80,000 to
    500,000: 262,144 a call) and a fuller store takes more calls, each
    one more pass of the device over the plane."""
    whole = -(-cfg.pos_capacity // LN) * LN
    return min(whole, 262144, max(8192, -(-whole // 64 // LN) * LN))


@functools.lru_cache(maxsize=None)
def build_seq_live_positions(cfg: SeqConfig):
    """The positions' half of export_snapshot's fetch: ONE jitted
    program, (`pos`, start) -> (live entries in the store, the ascending
    flat indices lane * A + account of the first live_positions_chunk
    (cfg) live entries at or after `start`, their four words as (K, 4):
    amount lo | hi, available lo | hi), so that a snapshot fetches the
    positions that are held and not the plane. An entry is live where
    ANY of its four words is non-zero (an amount of 0 with an available
    balance is state); the padding half-tile of a lane whose A is no
    multiple of 256 never is. Past the last live entry the indices read
    S * A and the words are padding. The program reads only: nothing
    is donated.

    `pos` is read in rows of 128 entries (half a tile: four plane rows,
    one a word). Per row a count; over the rows a prefix sum
    (_prefix_sum); the row of the k-th entry by ONE scatter of the
    rows' first ranks and a running maximum; then each result's row
    gathered whole, word by word, from the plane as it lies (512 B a
    word) and the column picked in it by a triangular matmul over the
    row's mask. Why these forms (TPU v5e, 3425 x 25,088, K 262,144:
    PERF.md section 6, PR 48): the rows' ranks by scatter 4.7 ms, by
    binary search 38.6; the words as whole rows 4.3 ms, as scalars
    15-16; the count as `any` over a (H, 4, 128) view 5.1 ms, as an OR
    of its four slices or over the plane's 8-row tiles 21-22. A call
    is 25-30 ms there, 6% of its HBM bound; the column select (about
    8 ms) is its largest piece."""
    S, A, K = cfg.lanes, cfg.accounts, live_positions_chunk(cfg)
    HL = 2 * cfg.pos_tiles_per_lane     # rows of a lane
    H = S * HL

    def live_positions(pos, start):
        h = jnp.arange(H, dtype=I32)
        c = jnp.arange(LN, dtype=I32)
        k = jnp.arange(K, dtype=I32)
        # A % 128 == 0: account a of a lane is (row, column) =
        # (a >> 7, a & 127) of the lane's rows
        hs, cs = (start // A) * HL + (start % A) // LN, start % LN

        def masks(nonzero, row):
            """Of the (n, 128) entries of rows `row`: the live ones (a
            lane's last row is padding where A is no multiple of 256),
            and those of them at or after `start`."""
            live = nonzero & (row % HL < A // LN)[:, None]
            return live, live & ((row[:, None] > hs)
                                 | ((row[:, None] == hs)
                                    & (c[None, :] >= cs)))

        live, ahead = masks(jnp.any(pos.reshape(H, 4, LN) != 0, axis=1), h)
        cnt = jnp.sum(ahead, axis=1, dtype=I32)
        rank = _prefix_sum(cnt, small=True)
        first = rank - cnt              # entries at or after start, before
        # row[k] = the last row that starts at or before entry k and
        # holds one (its first rank is unique among such rows; a row
        # that does not, writes past the end: dropped)
        at = jnp.where((cnt > 0) & (first < K), first, _i(K) + h)
        row = _running_max(jnp.zeros((K,), I32).at[at].set(
            h + 1, mode="drop", unique_indices=True)) - 1
        row = jnp.maximum(row, _i(0))
        got = [pos[4 * row + w] for w in range(4)]
        _, mine = masks((got[0] | got[1] | got[2] | got[3]) != 0, row)
        upto = jnp.dot(mine.astype(jnp.int8),
                       (c[:, None] <= c[None, :]).astype(jnp.int8),
                       preferred_element_type=I32)
        # the (k - first[row])-th live column of the row, from 0: the
        # columns whose count up to and including them is <= that
        # (past the last entry no column is: words 0, idx masked)
        col = jnp.sum(upto <= (k - first[row])[:, None], axis=1, dtype=I32)
        words = jnp.stack(
            [jnp.sum(jnp.where(c[None, :] == col[:, None], g, _i(0)),
                     axis=1, dtype=I32) for g in got], axis=1)
        idx = jnp.where(k < rank[-1],
                        row // HL * A + row % HL * LN + col, _i(S * A))
        return jnp.sum(live, dtype=I32), idx, words

    return jax.jit(live_positions)


def live_positions_call(cfg: SeqConfig, state, start=0):
    """One call of build_seq_live_positions' program on `state`'s
    position store, not fetched (SeqSession.__init__ compiles it by
    this call)."""
    return build_seq_live_positions(cfg)(state["pos"], _i(start))


def _nbytes(tree) -> int:
    return sum(a.nbytes for a in jax.tree_util.tree_leaves(tree))


def export_snapshot(cfg: SeqConfig, state):
    """Device planes -> (canon, layout, fetch): export_canonical's
    state with each SPARSE_SECTIONS section given by its LIVE entries
    where that takes fewer bytes than the dense section, and densely
    (as export_canonical gives it) where it does not — the occupancy
    decides, section by section. `layout` names the sparse sections,
    the dense shapes densify_canonical restores them to, and the live
    counts. A slot is live where `bs > 0` (the rule import_canonical
    and build_seq_occupancy apply; what a freed slot last held is not
    state); a position where ANY of its four words is non-zero (an
    amount of 0 with an available balance is state). No dense canonical
    array is built for a sparse section.

    The books cross by their live ROWS where those are at most a
    quarter of a plane's (build_seq_live_rows: the pass over `bs` runs
    on the device; 16.7 M slots holding 60,000 orders cost the host one
    pass over the ~2,500 rows fetched and six gathers of 60,000), by as
    many calls of that one program as it takes, each from the row after
    the last one returned; a book denser than that crosses whole and
    the host makes the pass. The positions cross by their live ENTRIES
    where the section is written by them (build_seq_live_positions: the
    device finds them, so the host never sees the plane — 1.4 GB at
    3425 lanes x 25,088 accounts), again by as many calls as it takes,
    each from the index after the last; a store dense enough to be
    written whole crosses whole. Either way gives the same arrays. The
    small sections cross whole, with the first calls' chunks. `fetch`
    says what crossed: `snapshot_fetch_bytes` (every section),
    `snapshot_live_rows`, `snapshot_fetch_calls` (calls of the row
    program that fetched the books; 0 = they crossed whole),
    `snapshot_pos_fetch_bytes` (the positions' share of the bytes),
    `snapshot_pos_calls` (calls of the entry program that fetched the
    positions; 0 = they crossed whole)."""
    if cfg.compat != "fixed":
        raise ValueError("java-mode state snapshots via "
                         "runtime/javasnap.export_seqjava")
    S, N, A = cfg.lanes, cfg.slots, cfg.accounts
    R = live_rows_chunk(cfg)
    h = jax.device_get({
        "rows": live_rows_call(cfg, state),
        "entries": live_positions_call(cfg, state),
        **{k: state[k] for k in _STATE_KEYS
           if k not in BOOK_KEYS + ("dep", "pos")}})
    crossed, pos_crossed = _nbytes(h), _nbytes(h["entries"])
    live_pos, *entries = h.pop("entries")
    live_rows, *chunk = h.pop("rows")
    live_rows, chunks = int(live_rows), []
    canon = _small_sections(cfg, h)
    layout = {"slot_shape": [S, 2, N], "pos_size": S * A, "sparse": []}

    # books: slots % 128 == 0, so a plane's flat order (lane, side, row,
    # column) IS the canonical (S, 2, N) flat order and no row is padding
    if live_rows * 4 <= 2 * S * cfg.nr:
        chunks.append(chunk)
        while len(chunks) * R < live_rows:
            chunks.append(jax.device_get(live_rows_call(
                cfg, state, chunks[-1][0][-1] + 1)[1:]))
        crossed += _nbytes(chunks[1:])
        idx, vals = [], []
        for n, (row, got) in enumerate(chunks):
            # (what a call returns past the last live row is padding)
            r, c = np.nonzero(got[_BS][:live_rows - n * R] > 0)
            idx.append(row[r].astype(np.int64) * LN + c)
            vals.append([p[r, c] for p in got])
        idx = np.concatenate(idx)
        at = {k: np.concatenate(v)
              for k, v in zip(BOOK_KEYS, zip(*vals))}.get
    else:
        h.update(jax.device_get({k: state[k] for k in BOOK_KEYS}))
        crossed += _nbytes([h[k] for k in BOOK_KEYS])
        idx = np.flatnonzero(h["bs"].reshape(-1) > 0)

        def at(k):
            return h[k].reshape(-1)[idx]

    calls = len(chunks)
    layout["live_slots"] = int(idx.size)
    # (live rows <= a quarter of the rows puts the live slots under a
    # quarter of the slots: books fetched by their rows are written by
    # their live entries)
    if idx.size * _SLOT_LIVE_B < S * 2 * N * _SLOT_DENSE_B:
        canon.update(slot_idx=idx, slot_oid=_j64(at("bo_lo"), at("bo_hi")),
                     slot_aid=at("ba"), slot_price=at("bp"),
                     slot_size=at("bs"), slot_seq=at("bq"))
        layout["sparse"].append("books")
    else:
        canon.update(_dense_books(cfg, h))

    # positions: the device counted them (and skipped a lane's padding
    # half-tile where A is no multiple of a tile's 256)
    live_pos, K = int(live_pos), live_positions_chunk(cfg)
    layout["live_positions"] = live_pos
    if live_pos * _POS_LIVE_B < S * A * _POS_DENSE_B:
        parts = [entries]
        while len(parts) * K < live_pos:
            parts.append(jax.device_get(live_positions_call(
                cfg, state, parts[-1][0][-1] + 1)[1:]))
        more = _nbytes(parts[1:])
        # (what the last call returns past the last live entry is padding)
        idx, words = (np.concatenate(v)[:live_pos] for v in zip(*parts))
        canon.update(pos_idx=idx.astype(np.int64),
                     pos_amt=_j64(words[:, 0], words[:, 1]),
                     pos_avail=_j64(words[:, 2], words[:, 3]))
        layout["sparse"].append("positions")
    else:
        parts = []
        h["pos"] = jax.device_get(state["pos"])
        more = h["pos"].nbytes
        canon.update(_dense_positions(cfg, h))
    fetch = {"snapshot_fetch_bytes": crossed + more,
             "snapshot_live_rows": live_rows,
             "snapshot_fetch_calls": calls,
             "snapshot_pos_fetch_bytes": pos_crossed + more,
             "snapshot_pos_calls": len(parts)}
    return canon, layout, fetch


def densify_canonical(canon: dict, layout: dict) -> dict:
    """Inverse of export_snapshot's encoding: every section `layout`
    names as sparse scattered into zeros of its dense shape (and
    `slot_used` set at the live slots), so that what comes out is the
    canonical dict import_canonical reads."""
    out = {k: v for k, v in canon.items()
           if k not in ("slot_idx", "pos_idx")}

    def scatter(idx, size, key, shape):
        full = np.zeros(size, canon[key].dtype)
        full[idx] = canon[key]
        return full.reshape(shape)

    if "books" in layout["sparse"]:
        shape = tuple(layout["slot_shape"])
        size, idx = int(np.prod(shape)), np.asarray(canon["slot_idx"])
        for key in SPARSE_SECTIONS["books"][1:]:
            out[key] = scatter(idx, size, key, shape)
        used = np.zeros(size, bool)
        used[idx] = True
        out["slot_used"] = used.reshape(shape)
    if "positions" in layout["sparse"]:
        size, idx = int(layout["pos_size"]), np.asarray(canon["pos_idx"])
        for key in SPARSE_SECTIONS["positions"][1:]:
            out[key] = scatter(idx, size, key, (size,))
    return out


# what build_seq_occupancy's vector holds, in order
OCCUPANCY_NAMES = ("open_orders", "books", "accounts", "positions",
                   "max_book_depth")


@functools.lru_cache(maxsize=None)
def build_seq_occupancy(cfg: SeqConfig):
    """The narrow read of SeqSession.metrics(): ONE jitted reduction,
    device state -> the (5,) i32 vector of OCCUPANCY_NAMES, so that a
    refresh fetches 20 bytes and not every plane. Each count is taken
    on the plane and by the rule the whole-state exports above use
    (tests/test_spans.py holds them equal): a slot is used where
    `bs > 0`; a position counts where export_canonical gives a
    non-zero amount (fixed) or export_java keeps the entry (java)."""
    S, A, NR = cfg.lanes, cfg.accounts, cfg.nr

    def count(mask):
        return jnp.sum(mask, dtype=I32)

    def occupancy(state):
        # rows of a book plane run (lane, side, NR): planes2slot's
        # view, counted row by row first — a (S, 2, NR * 128) view of
        # the plane itself makes XLA lay all of it out again.
        # slots % 128 == 0, so no lane of a row is padding
        used = jnp.sum(state["bs"] > 0, axis=1, dtype=I32)
        depth = jnp.sum(used.reshape(2 * S, NR), axis=1, dtype=I32)
        if cfg.compat == "java":
            positions = count(state["hstate"] == 1)
        else:
            # whole tiles (a free reshape): amt lo | hi of each half
            t = state["pos"].reshape(-1, POS_TILE_ROWS, LN)
            positions = (count((t[:, 0] | t[:, 1]) != 0)
                         + count((t[:, 4] | t[:, 5]) != 0))
        return jnp.stack([
            jnp.sum(depth, dtype=I32),
            count(state["bex"].reshape(-1)[:S] != 0),
            count(state["bal_u"].reshape(-1)[:A] != 0),
            positions,
            jnp.max(depth),
        ])

    return jax.jit(occupancy)


# the replicated balance planes (account a -> row a>>7, lane a&127):
# the only cross-shard-coupled state the seqmesh async dispatcher
# forwards point-to-point and select-merges at barriers
BAL_KEYS = ("bal_lo", "bal_hi", "bal_u")


def select_balances(planes_by_shard, sel) -> dict:
    """Merge per-shard copies of the replicated balance planes by
    per-account OWNER SELECTION: sel[a] names the shard whose copy of
    account a is authoritative. Exact by construction — under the
    seqmesh window invariant an account's balance only ever advances on
    the shard it is currently bound to, so a select needs no arithmetic
    merge (and trivially preserves Java-long wrap).

    planes_by_shard: per-shard dicts of BAL_KEYS -> (arows, 128) i32.
    sel: (arows*128,) int shard index per flat account slot.
    Returns merged (arows, 128) planes."""
    stacked = {k: np.stack([p[k] for p in planes_by_shard])
               for k in BAL_KEYS}
    arows, lanes = stacked[BAL_KEYS[0]].shape[1:]
    idx = sel.reshape(arows, lanes)
    r = np.arange(arows, dtype=np.int64)[:, None]
    c = np.arange(lanes, dtype=np.int64)[None, :]
    return {k: stacked[k][idx, r, c] for k in BAL_KEYS}


def import_canonical(cfg: SeqConfig, canon: dict):
    """Inverse of export_canonical (numpy -> device plane dict). The
    snapshot's slot depth and account capacity may be SMALLER than the
    config's (elastic restore into deeper books / wider account space —
    positions are laid out again at the new stride); shrinking either
    is a state migration, not a restore, and raises."""
    S, N, A, NR = cfg.lanes, cfg.slots, cfg.accounts, cfg.nr
    S0 = np.asarray(canon["slot_oid"]).shape[0]
    if S0 != S:
        raise ValueError(
            f"snapshot has {S0} lanes, cfg.lanes={S} — lane-count "
            f"changes need a state migration, not a restore")
    N0 = np.asarray(canon["slot_oid"]).shape[2]
    if N0 > N:
        raise ValueError(
            f"snapshot books are {N0} slots deep; cfg.slots={N} cannot "
            f"hold them — restore into slots >= {N0}")
    A0 = np.asarray(canon["pos_amt"]).reshape(-1).size // S
    if A0 > A:
        raise ValueError(
            f"snapshot has {A0} account slots; cfg.accounts={A} cannot "
            f"hold them — restore into accounts >= {A0}")

    def slot2planes(v, split=False):
        full = np.zeros((S, 2, NR * LN), np.int64)
        full[:, :, :N0] = np.asarray(v).reshape(S, 2, N0)
        flat = full.reshape(2 * S * NR, LN)
        if split:
            lo = (flat & 0xFFFFFFFF).astype(np.uint32).astype(np.int32)
            hi = (flat >> 32).astype(np.int32)
            return lo, hi
        return flat.astype(np.int32)

    lo, hi = slot2planes(canon["slot_oid"], split=True)
    used = np.asarray(canon["slot_used"])
    sizes = np.where(used, np.asarray(canon["slot_size"]), 0)

    def padplane(v, rows):
        a = np.zeros(rows * LN, np.int32)
        a[:len(v)] = v
        return a.reshape(rows, LN)

    # at the CONFIG's account stride (A may exceed A0)
    both = np.zeros((2, S, cfg.pos_tiles_per_lane * POS_TILE_ACCOUNTS),
                    np.int64)
    both[0, :, :A0] = np.asarray(canon["pos_amt"]).reshape(S, A0)
    both[1, :, :A0] = np.asarray(canon["pos_avail"]).reshape(S, A0)
    pos = values_to_pos(cfg, both)

    bal = np.asarray(canon["bal"]).reshape(-1)
    return {
        "bo_lo": jnp.asarray(lo), "bo_hi": jnp.asarray(hi),
        "ba": jnp.asarray(slot2planes(canon["slot_aid"])),
        "bp": jnp.asarray(slot2planes(canon["slot_price"])),
        "bs": jnp.asarray(slot2planes(sizes)),
        "bq": jnp.asarray(slot2planes(canon["slot_seq"])),
        "seqc": jnp.asarray(padplane(np.asarray(canon["seq"]), cfg.srows)),
        "bex": jnp.asarray(padplane(
            np.asarray(canon["book_exists"]).astype(np.int32), cfg.srows)),
        "bal_lo": jnp.asarray(padplane(
            (bal & 0xFFFFFFFF).astype(np.uint32).astype(np.int32),
            cfg.arows)),
        "bal_hi": jnp.asarray(padplane((bal >> 32).astype(np.int32),
                                       cfg.arows)),
        "bal_u": jnp.asarray(padplane(
            np.asarray(canon["bal_used"]).astype(np.int32), cfg.arows)),
        "pos": jnp.asarray(pos),
        # dep is derived state (occupied slots per lane, both sides) —
        # recomputed here so canonical snapshots stay engine-agnostic
        "dep": jnp.asarray(padplane(
            (sizes.reshape(S, -1) > 0).sum(axis=1).astype(np.int32),
            cfg.srows)),
        "err": jnp.asarray(padplane(
            np.array([int(canon.get("err", 0))], np.int32), 1)),
    }
