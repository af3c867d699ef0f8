"""Workload generation — a seeded port of the reference's e2e driver
(/root/reference/exchange_test.js).

The reference drives the engine with an unseeded Math.random() stream, so
its exact event sequence is irreproducible; this port keeps the exact
*distribution* and sequencing semantics but is deterministic under a seed
(the parity strategy of SURVEY.md §4: golden traces come from replaying
one seeded stream through both the oracle and the TPU engine).

Faithful details:
  - seeding preamble: per account CREATE_BALANCE + TRANSFER of
    N(50000, 25000) (exchange_test.js:23-28, amounts are price-units*100),
    then `i < numSymbols/2+1` ADD_SYMBOLs — note the float loop bound
    creates 3 symbols for numSymbols=3 but only 3 for numSymbols=4 as
    well, leaving high sids unadded (exchange_test.js:29-32)
  - event mix per mille (exchange_test.js:106-117): 1 add-symbol,
    1 payout, 2 transfer N(0, 12500), 332 buy, 332 sell, ~334 cancel
  - prices and sizes are floor(N(50, 10)) — occasionally zero or negative
    (the Q2 trigger)
  - payouts are sent with action=4 (CANCEL) — the reference harness's
    opcode bug, Q5 (exchange_test.js:78 `createOrder(4, ...)`); pass
    payout_opcode_bug=False to emit the real PAYOUT opcode (200)
  - cancels pick a uniformly random previously-submitted oid and remove
    it from the pool whether or not the cancel succeeds
    (exchange_test.js:97-104); an empty pool yields the oid=0 cancel
  - oids are uniform in [0, 2^53) (exchange_test.js:82,88)
"""

from __future__ import annotations

import bisect
import math
import random
from typing import (Callable, Iterator, List, NamedTuple, Optional,
                    Sequence, Tuple)

from kme_tpu import opcodes as op
from kme_tpu.wire import OrderMsg


class WorkloadGen:
    """Deterministic re-implementation of exchange_test.js's generator."""

    def __init__(
        self,
        num_accounts: int = 10,
        num_symbols: int = 3,
        rake: int = 3,
        seed: int = 0,
        payout_opcode_bug: bool = True,
        validate: bool = False,
    ) -> None:
        self.num_accounts = num_accounts
        self.num_symbols = num_symbols
        self.rake = rake
        self.rng = random.Random(seed)
        self.payout_opcode_bug = payout_opcode_bug
        # validate=True clamps prices/sizes into the fixed-mode domain
        # (price 0..125, size >= 1) for clean-semantics workloads.
        self.validate = validate
        self.open_orders: dict[int, int] = {}  # oid -> aid (exchange_test.js:21)
        # sorted oid pool kept in lockstep with open_orders: cancels
        # select by SORTED position, and re-sorting the whole pool per
        # cancel is O(n^2 log n) over a long stream (the 400k soak spent
        # >20 min of host CPU there). bisect keeps the identical order
        # at O(n) memmove per op — the generated streams are UNCHANGED.
        self._pool: list[int] = []

    # -- primitive distributions (exchange_test.js:48-61) --

    def _random_normal(self) -> float:
        u = 0.0
        v = 0.0
        while u == 0.0:
            u = self.rng.random()
        while v == 0.0:
            v = self.rng.random()
        return math.sqrt(-2.0 * math.log(u)) * math.cos(2.0 * math.pi * v)

    def _uniform(self, rng_range: int) -> int:
        return math.floor(self.rng.random() * rng_range)

    def _normal_param(self, mean: float, std: float) -> int:
        return math.floor(self._random_normal() * std + mean)

    def _clamp_price(self, p: int) -> int:
        return min(125, max(0, p)) if self.validate else p

    def _clamp_size(self, s: int) -> int:
        return max(1, s) if self.validate else s

    # -- message constructors (exchange_test.js:63-104) --

    def create_account(self, aid: int) -> OrderMsg:
        return OrderMsg(action=op.CREATE_BALANCE, aid=aid)

    def create_symbol(self, sid: int) -> OrderMsg:
        return OrderMsg(action=op.ADD_SYMBOL, sid=sid)

    def create_transfer(self, aid: int, amount: int) -> OrderMsg:
        return OrderMsg(action=op.TRANSFER, aid=aid, size=amount)

    def create_payout(self, sid: int, success: bool) -> OrderMsg:
        action = op.CANCEL if self.payout_opcode_bug else op.PAYOUT
        return OrderMsg(
            action=action, sid=sid * (1 if success else -1),
            size=100 - self.rake)

    def create_buy(self, aid: int, sid: int, price: int, size: int) -> OrderMsg:
        oid = math.floor(self.rng.random() * (2 ** 53 - 1))
        if oid not in self.open_orders:
            bisect.insort(self._pool, oid)
        self.open_orders[oid] = aid
        return OrderMsg(action=op.BUY, oid=oid, aid=aid, sid=sid,
                        price=self._clamp_price(price), size=self._clamp_size(size))

    def create_sell(self, aid: int, sid: int, price: int, size: int) -> OrderMsg:
        oid = math.floor(self.rng.random() * (2 ** 53 - 1))
        if oid not in self.open_orders:
            bisect.insort(self._pool, oid)
        self.open_orders[oid] = aid
        return OrderMsg(action=op.SELL, oid=oid, aid=aid, sid=sid,
                        price=self._clamp_price(price), size=self._clamp_size(size))

    def create_cancel(self) -> OrderMsg:
        if not self.open_orders:
            return OrderMsg(action=op.CANCEL)
        # stable pool ordering under seed (identical to sorting the
        # dict keys per call — _pool IS that sorted sequence)
        i = math.floor(self.rng.random() * len(self._pool))
        oid = self._pool.pop(i)
        aid = self.open_orders.pop(oid)
        return OrderMsg(action=op.CANCEL, oid=oid, aid=aid)

    # -- event stream (exchange_test.js:4-37, 106-117) --

    def preamble(self) -> List[OrderMsg]:
        msgs: List[OrderMsg] = []
        for aid in range(self.num_accounts):
            msgs.append(self.create_account(aid))
            msgs.append(self.create_transfer(
                aid, self._normal_param(500 * 100, 250 * 100)))
        i = 0
        while i < self.num_symbols / 2 + 1:  # float bound, exchange_test.js:29
            msgs.append(self.create_symbol(i))
            i += 1
        return msgs

    def gen_event(self) -> OrderMsg:
        e = self._uniform(1000)
        if e == 0:
            return self.create_symbol(self._uniform(self.num_symbols))
        if e == 1:
            return self.create_payout(
                self._uniform(self.num_symbols), self._uniform(2) == 0)
        if e in (2, 3):
            return self.create_transfer(
                self._uniform(self.num_accounts), self._normal_param(0, 125 * 100))
        if 3 < e <= 335:
            return self.create_buy(
                self._uniform(self.num_accounts), self._uniform(self.num_symbols),
                self._normal_param(50, 10), self._normal_param(50, 10))
        if 335 < e <= 667:
            return self.create_sell(
                self._uniform(self.num_accounts), self._uniform(self.num_symbols),
                self._normal_param(50, 10), self._normal_param(50, 10))
        return self.create_cancel()

    def stream(self, num_events: int, include_preamble: bool = True
               ) -> Iterator[OrderMsg]:
        if include_preamble:
            yield from self.preamble()
        for _ in range(num_events):
            yield self.gen_event()


def harness_stream(num_events: int = 100_000, seed: int = 0,
                   num_accounts: int = 10, num_symbols: int = 3,
                   rake: int = 3, payout_opcode_bug: bool = True,
                   validate: bool = False) -> List[OrderMsg]:
    """The full reference harness workload: preamble + num_events random
    events (exchange_test.js:23-36 with the default knobs :18-20)."""
    gen = WorkloadGen(num_accounts, num_symbols, rake, seed,
                      payout_opcode_bug, validate)
    return list(gen.stream(num_events))


def zipf_symbol_stream(num_events: int, num_symbols: int, num_accounts: int,
                       seed: int = 0, zipf_a: float = 1.2,
                       deposit: int = 10_000_000,
                       payout_per_mille: int = 0) -> List[OrderMsg]:
    """Scale workload for the BASELINE.md throughput configs: Zipf-skewed
    symbol arrival over many symbols/accounts, valid-domain prices/sizes.
    payout_per_mille > 0 mixes in real PAYOUT barriers (each immediately
    followed by a re-ADD of the settled symbol so its lane stays live)."""
    gen = WorkloadGen(num_accounts, num_symbols, seed=seed, validate=True,
                      payout_opcode_bug=False)
    msgs: List[OrderMsg] = []
    for aid in range(num_accounts):
        msgs.append(gen.create_account(aid))
        msgs.append(gen.create_transfer(aid, deposit))
    for sid in range(num_symbols):
        msgs.append(gen.create_symbol(sid))
    # Zipf ranks over symbols, uniform accounts
    weights = [1.0 / (r + 1) ** zipf_a for r in range(num_symbols)]
    total = sum(weights)
    cdf = []
    acc = 0.0
    for w in weights:
        acc += w / total
        cdf.append(acc)
    import bisect
    for _ in range(num_events):
        u = gen.rng.random()
        sid = bisect.bisect_left(cdf, u)
        aid = gen._uniform(num_accounts)
        e = gen._uniform(1000)
        if e < payout_per_mille:
            msgs.append(gen.create_payout(sid, gen.rng.random() < 0.5))
            msgs.append(gen.create_symbol(sid))
        elif e < 450:
            msgs.append(gen.create_buy(aid, sid, gen._normal_param(50, 10),
                                       gen._normal_param(50, 10)))
        elif e < 900:
            msgs.append(gen.create_sell(aid, sid, gen._normal_param(50, 10),
                                        gen._normal_param(50, 10)))
        else:
            msgs.append(gen.create_cancel())
    return msgs


def zipf_hot_stream(num_events: int, num_symbols: int, num_accounts: int,
                    seed: int = 0, hot_frac: float = 0.7,
                    zipf_a: float = 1.2,
                    deposit: int = 10_000_000) -> List[OrderMsg]:
    """Adversarial profile for static sharding: ONE hot book. Symbol 0
    takes `hot_frac` of all events outright; the remainder is
    Zipf-distributed over symbols 1..n-1, so there is a distinctly WARM
    second-ranked book — the shape that defeats `lane % shards`
    placement twice over (the hot symbol saturates its shard AND the
    static hash co-locates the warm book with it, which an elastic
    planner migrates away). Seed-deterministic like every profile here
    (same stream for the same arguments — asserted in
    tests/test_workload.py)."""
    if num_symbols < 2:
        raise ValueError("zipf-hot needs >= 2 symbols (hot + cold set)")
    gen = WorkloadGen(num_accounts, num_symbols, seed=seed, validate=True,
                      payout_opcode_bug=False)
    msgs: List[OrderMsg] = []
    for aid in range(num_accounts):
        msgs.append(gen.create_account(aid))
        msgs.append(gen.create_transfer(aid, deposit))
    for sid in range(num_symbols):
        msgs.append(gen.create_symbol(sid))
    cold = num_symbols - 1
    weights = [1.0 / (r + 1) ** zipf_a for r in range(cold)]
    total = sum(weights)
    cdf = []
    acc = 0.0
    for w in weights:
        acc += w / total
        cdf.append(acc)
    for _ in range(num_events):
        if gen.rng.random() < hot_frac:
            sid = 0
        else:
            sid = 1 + bisect.bisect_left(cdf, gen.rng.random())
        aid = gen._uniform(num_accounts)
        e = gen._uniform(1000)
        if e < 450:
            msgs.append(gen.create_buy(aid, sid, gen._normal_param(50, 10),
                                       gen._normal_param(50, 10)))
        elif e < 900:
            msgs.append(gen.create_sell(aid, sid, gen._normal_param(50, 10),
                                        gen._normal_param(50, 10)))
        else:
            msgs.append(gen.create_cancel())
    return msgs


def payout_storm_stream(num_events: int, num_symbols: int,
                        num_accounts: int, seed: int = 0,
                        storms: int = 3,
                        deposit: int = 10_000_000) -> List[OrderMsg]:
    """Mass-settlement burst profile: steady Zipf trading punctuated by
    `storms` evenly-spaced bursts in which EVERY symbol is paid out
    (real PAYOUT opcode) and immediately re-ADDed. Each payout is a
    barrier window in the mesh planner, so the profile stresses the
    flush/rebind path and collapses then rebuilds every book at once.
    Seed-deterministic."""
    if storms < 1:
        raise ValueError("payout-storm needs storms >= 1")
    gen = WorkloadGen(num_accounts, num_symbols, seed=seed, validate=True,
                      payout_opcode_bug=False)
    msgs: List[OrderMsg] = []
    for aid in range(num_accounts):
        msgs.append(gen.create_account(aid))
        msgs.append(gen.create_transfer(aid, deposit))
    for sid in range(num_symbols):
        msgs.append(gen.create_symbol(sid))
    weights = [1.0 / (r + 1) ** 1.2 for r in range(num_symbols)]
    total = sum(weights)
    cdf = []
    acc = 0.0
    for w in weights:
        acc += w / total
        cdf.append(acc)
    storm_at = {max(1, (i + 1) * num_events // (storms + 1))
                for i in range(storms)}
    for k in range(num_events):
        if k in storm_at:
            for sid in range(num_symbols):
                msgs.append(gen.create_payout(sid,
                                              gen.rng.random() < 0.5))
                msgs.append(gen.create_symbol(sid))
            continue
        sid = bisect.bisect_left(cdf, gen.rng.random())
        aid = gen._uniform(num_accounts)
        e = gen._uniform(1000)
        if e < 450:
            msgs.append(gen.create_buy(aid, sid, gen._normal_param(50, 10),
                                       gen._normal_param(50, 10)))
        elif e < 900:
            msgs.append(gen.create_sell(aid, sid, gen._normal_param(50, 10),
                                        gen._normal_param(50, 10)))
        else:
            msgs.append(gen.create_cancel())
    return msgs


def market_lifecycle_stream(num_events: int, num_symbols: int,
                            num_accounts: int, seed: int = 0,
                            zipf_a: float = 1.2,
                            deposit: int = 10_000_000
                            ) -> Iterator[OrderMsg]:
    """A prediction market that lists, trades, settles and is never
    relisted: `num_symbols` binary contracts are open at any time, and
    every settlement is followed at once by the listing of the next
    contract under a FRESH symbol id (a settled contract names an event
    that has happened; ids only grow, so a long run names many more ids
    than are ever listed together).

    Preamble as zipf_symbol_stream (accounts created and funded, ids
    0..num_symbols-1 listed, rank r <-> id r). Then per event the
    upstream's draw e = uniform(1000) and its mix (exchange_test.js:
    106-117) with one slot added:
      e == 0      settlement: rank r ~ Zipf(zipf_a) -- the law trades
                  follow: a market is busiest as it resolves --
                  PAYOUT(+-sid[r], 100 - rake), YES/NO by a coin as
                  create_payout, then ADD_SYMBOL(next id) into rank r
      e == 1      a late order: a BUY/SELL (coin) naming the id most
                  recently paid out (it raced its market's close);
                  before the first settlement an ordinary one
      e in 2, 3   TRANSFER floor(N(0, 12500)) to a uniform account
      4..335      BUY,  336..667 SELL on sid[rank ~ Zipf], uniform
                  account, price and size floor(N(50, 10)) clamped
                  into the device domain
      668..999    create_cancel()
    Lazy (a generator): a long stream's preamble can be served while
    the rest is drawn. Seed-deterministic."""
    gen = WorkloadGen(num_accounts, num_symbols, seed=seed, validate=True,
                      payout_opcode_bug=False)
    yield from _storm_preamble(gen, num_accounts, num_symbols, deposit)
    cdf = _zipf_cdf(num_symbols, zipf_a)
    sid_of = list(range(num_symbols))       # rank -> the id listed there
    next_id = num_symbols
    settled = None                          # the id paid out last

    def trade(buy: bool, sid: int) -> OrderMsg:
        make = gen.create_buy if buy else gen.create_sell
        return make(gen._uniform(num_accounts), sid,
                    gen._normal_param(50, 10), gen._normal_param(50, 10))

    def rank() -> int:
        return bisect.bisect_left(cdf, gen.rng.random())

    for _ in range(num_events):
        e = gen._uniform(1000)
        if e == 0:
            r = rank()
            settled = sid_of[r]
            yield gen.create_payout(settled, gen._uniform(2) == 0)
            yield gen.create_symbol(next_id)
            sid_of[r] = next_id
            next_id += 1
        elif e == 1:
            buy = gen._uniform(2) == 0
            yield trade(buy, sid_of[rank()] if settled is None else settled)
        elif e <= 3:
            yield gen.create_transfer(gen._uniform(num_accounts),
                                      gen._normal_param(0, 125 * 100))
        elif e <= 667:
            yield trade(e <= 335, sid_of[rank()])
        else:
            yield gen.create_cancel()


def cancel_heavy_stream(num_events: int, num_symbols: int, num_accounts: int,
                        seed: int = 0, cancel_ratio: float = 0.8,
                        deposit: int = 10_000_000) -> List[OrderMsg]:
    """BASELINE.md's bursty cancel/replace config: attempts a cancel with
    probability cancel_ratio whenever the open-order pool is non-empty.
    Steady-state cancels are structurally bounded near 50% of events (each
    cancel consumes one prior resting submit), matching the reference
    harness's own cancel-vs-submit equilibrium (exchange_test.js:106-117)."""
    gen = WorkloadGen(num_accounts, num_symbols, seed=seed, validate=True)
    msgs: List[OrderMsg] = []
    for aid in range(num_accounts):
        msgs.append(gen.create_account(aid))
        msgs.append(gen.create_transfer(aid, deposit))
    for sid in range(num_symbols):
        msgs.append(gen.create_symbol(sid))
    for _ in range(num_events):
        if gen.rng.random() < cancel_ratio and gen.open_orders:
            msgs.append(gen.create_cancel())
        else:
            aid = gen._uniform(num_accounts)
            sid = gen._uniform(num_symbols)
            if gen.rng.random() < 0.5:
                msgs.append(gen.create_buy(aid, sid, gen._normal_param(50, 10),
                                           gen._normal_param(50, 10)))
            else:
                msgs.append(gen.create_sell(aid, sid, gen._normal_param(50, 10),
                                            gen._normal_param(50, 10)))
    return msgs


def quote_churn_stream(num_events: int, num_symbols: int,
                       num_accounts: int, seed: int = 0,
                       zipf_a: float = 1.2, cancel_ratio: float = 0.8,
                       standing: int = 32768, take: float = 0.05,
                       deposit: int = 10_000_000) -> Iterator[OrderMsg]:
    """A quote-driven market (BASELINE.json config 4, "80% cancels"):
    market makers place quotes either side of a mid of 50, pull most of
    them and have a few lifted, so that about four quotes in five end
    by an accepted cancel and one by a fill.

    Preamble as zipf_symbol_stream (accounts created and funded, ids
    0..num_symbols-1 listed). Then cancel_heavy_stream's law with three
    departures:
      (a) a cancel is drawn (with probability cancel_ratio) only while
          the pool of submitted orders holds more than `standing`, else
          the event is a submit: about `standing` orders are open at any
          time (cancel_heavy_stream's pool drains to nothing at 0.8, so
          its books stay empty) and the mix settles near one cancel a
          submit. The cancel takes a uniformly drawn member of the pool
          out of it, whether or not that order still rests
          (exchange_test.js:97-104: the pool never learns of fills);
      (b) a submit's symbol ~ Zipf(zipf_a) over the ranks, its account
          uniform, its side a coin;
      (c) with probability 1 - take it is a passive quote, BUY at
          49 - floor(|N(0, 4)|) or SELL at 51 + floor(|N(0, 4)|), size
          floor(N(50, 10)); with probability take a taker on the far
          side of the mid, BUY at 51 + floor(|N(0, 4)|) or SELL at
          49 - floor(|N(0, 4)|), size floor(N(200, 40)): it lifts about
          four quotes. Prices and sizes clamped into the device domain.
    Oids are uniform in [0, 2^53) as create_buy draws them. Lazy (a
    generator): the preamble can be served while the rest is drawn; the
    pool is a list with O(1) removal, so a long stream draws at the
    pace of its random numbers. Seed-deterministic."""
    gen = WorkloadGen(num_accounts, num_symbols, seed=seed, validate=True,
                      payout_opcode_bug=False)
    yield from _storm_preamble(gen, num_accounts, num_symbols, deposit)
    cdf = _zipf_cdf(num_symbols, zipf_a)
    rnd, normal, floor = gen.rng.random, gen._random_normal, math.floor
    pool: List[Tuple[int, int]] = []        # (oid, aid), in no order
    for _ in range(num_events):
        if len(pool) > standing and rnd() < cancel_ratio:
            i = floor(rnd() * len(pool))
            oid, aid = pool[i]
            last = pool.pop()
            if i < len(pool):
                pool[i] = last
            yield OrderMsg(action=op.CANCEL, oid=oid, aid=aid)
            continue
        sid = bisect.bisect_left(cdf, rnd())
        aid = floor(rnd() * num_accounts)
        buy = rnd() < 0.5
        away = floor(abs(normal()) * 4)
        if rnd() < take:
            price = 51 + away if buy else 49 - away
            size = floor(normal() * 40 + 200)
        else:
            price = 49 - away if buy else 51 + away
            size = floor(normal() * 10 + 50)
        oid = floor(rnd() * (2 ** 53 - 1))
        pool.append((oid, aid))
        yield OrderMsg(action=op.BUY if buy else op.SELL, oid=oid,
                       aid=aid, sid=sid, price=min(125, max(0, price)),
                       size=max(1, size))


def brokerage_stream(num_events: int, num_symbols: int,
                     num_accounts: int, seed: int = 0,
                     zipf_a: float = 1.2, account_zipf: float = 0.99,
                     take: float = 0.6, cancel_share: float = 0.1,
                     standing: int = 512, take_size: int = 150,
                     deposit: int = 1_000_000_000) -> Iterator[OrderMsg]:
    """A retail brokerage (TPC-E's populations and order mix, YCSB's
    senders): accounts outnumber contracts several times over, a few
    hundred of them send most of the flow, and most orders are
    marketable.

    Preamble as quote_churn_stream (accounts created and funded, ids
    0..num_symbols-1 listed). Then, an event at a time:
      (a) with probability cancel_share, while the pool has a member, a
          cancel of a uniformly drawn member. The pool holds only the
          last `standing` PASSIVE quotes: an older one falls out
          uncancelled, a marketable order never enters, and the pool
          never learns of fills (exchange_test.js:97-104), so a cancel
          may name an order already filled and be rejected;
      (b) else a submit: its symbol ~ Zipf(zipf_a) over the ranks, its
          account ~ Zipf(account_zipf) over ranks that a permutation
          drawn from the seed maps to account ids (YCSB's scrambled
          zipfian: the hot accounts are no neighbours), its side a coin;
      (c) with probability take it is marketable (TPC-E Trade-Order's
          market order, as a limit on the far side of the mid: BUY at
          51 + floor(|N(0, 4)|), SELL at 49 - floor(|N(0, 4)|), size
          floor(N(take_size, take_size / 5))), else a passive quote,
          BUY at 49 - floor(|N(0, 4)|) or SELL at 51 + floor(|N(0, 4)|),
          size floor(N(50, 10)). Prices and sizes clamped into the
          device domain. What a marketable order does not fill rests.
    take_size 150 against quotes of 50 keeps the hot books shallow (a
    taker sweeps what its limit reaches; at 30 the levels takers seldom
    reach fill up and the hottest side meets 8,192 slots after 700,000
    events); standing 512 keeps the pool young enough that over half of
    the cancels find their quote resting (PERF.md section 4 has the
    counts). The deposit covers the hottest account's margin.
    Oids are uniform in [0, 2^53). Lazy (a generator); every draw is
    O(1) but the two bisections (the pool is a ring of `standing`
    places, a cancel redraws until it hits a place still held, about
    1.2 draws). Seed-deterministic."""
    gen = WorkloadGen(num_accounts, num_symbols, seed=seed, validate=True,
                      payout_opcode_bug=False)
    yield from _storm_preamble(gen, num_accounts, num_symbols, deposit)
    sym_cdf = _zipf_cdf(num_symbols, zipf_a)
    acct_cdf = _zipf_cdf(num_accounts, account_zipf)
    sym_cdf[-1] = acct_cdf[-1] = 1.0    # (the sums stop an ulp short)
    rnd, normal, floor = gen.rng.random, gen._random_normal, math.floor
    acct_of_rank = list(range(num_accounts))
    gen.rng.shuffle(acct_of_rank)
    ring: List[Optional[Tuple[int, int]]] = [None] * standing
    quotes = held = 0       # passive quotes so far; places still held
    for _ in range(num_events):
        if held and rnd() < cancel_share:
            while True:
                i = floor(rnd() * min(quotes, standing))
                if ring[i] is not None:
                    break
            oid, aid = ring[i]
            ring[i] = None
            held -= 1
            yield OrderMsg(action=op.CANCEL, oid=oid, aid=aid)
            continue
        sid = bisect.bisect_left(sym_cdf, rnd())
        aid = acct_of_rank[bisect.bisect_left(acct_cdf, rnd())]
        buy = rnd() < 0.5
        away = floor(abs(normal()) * 4)
        marketable = rnd() < take
        if marketable:
            price = 51 + away if buy else 49 - away
            size = floor(normal() * (take_size / 5) + take_size)
        else:
            price = 49 - away if buy else 51 + away
            size = floor(normal() * 10 + 50)
        oid = floor(rnd() * (2 ** 53 - 1))
        if not marketable:
            i = quotes % standing
            held += ring[i] is None
            ring[i] = (oid, aid)
            quotes += 1
        yield OrderMsg(action=op.BUY if buy else op.SELL, oid=oid,
                       aid=aid, sid=sid, price=min(125, max(0, price)),
                       size=max(1, size))


def cross_account_stream(num_events: int, num_symbols: int,
                         num_accounts: int, ngroups: int,
                         seed: int = 0, cross_frac: float = 0.5,
                         zipf_a: float = 1.2,
                         deposit: int = 10_000_000) -> List[OrderMsg]:
    """Transfer-path sizing profile for the multi-leader topology
    (bridge/front.py): Zipf-skewed symbol arrival where a configurable
    fraction of orders is FORCED onto a non-home account — an account
    whose home group (rendezvous hash of aid) differs from the order's
    symbol group — so every such order costs the front door a
    reserve->settle transfer pair. cross_frac=1.0 is the degenerate
    worst case (100% cross-shard, the bench tail). Seed-deterministic;
    with ngroups=1 there are no non-home accounts and the stream
    degenerates to plain Zipf traffic."""
    from kme_tpu.bridge.front import account_group, symbol_group

    gen = WorkloadGen(num_accounts, num_symbols, seed=seed, validate=True,
                      payout_opcode_bug=False)
    msgs: List[OrderMsg] = []
    for aid in range(num_accounts):
        msgs.append(gen.create_account(aid))
        msgs.append(gen.create_transfer(aid, deposit))
    for sid in range(num_symbols):
        msgs.append(gen.create_symbol(sid))
    # account pools keyed by home group: same[g] lives on g, cross[g]
    # anywhere else (empty pools fall back to the full range)
    same = {g: [] for g in range(ngroups)}
    cross = {g: [] for g in range(ngroups)}
    for aid in range(num_accounts):
        h = account_group(aid, ngroups)
        for g in range(ngroups):
            (same if g == h else cross)[g].append(aid)
    weights = [1.0 / (r + 1) ** zipf_a for r in range(num_symbols)]
    total = sum(weights)
    cdf = []
    acc = 0.0
    for w in weights:
        acc += w / total
        cdf.append(acc)
    for _ in range(num_events):
        sid = bisect.bisect_left(cdf, gen.rng.random())
        g = symbol_group(sid, ngroups)
        pool = cross[g] if gen.rng.random() < cross_frac else same[g]
        aid = (pool[gen._uniform(len(pool))] if pool
               else gen._uniform(num_accounts))
        e = gen._uniform(1000)
        if e < 450:
            msgs.append(gen.create_buy(aid, sid, gen._normal_param(50, 10),
                                       gen._normal_param(50, 10)))
        elif e < 900:
            msgs.append(gen.create_sell(aid, sid, gen._normal_param(50, 10),
                                        gen._normal_param(50, 10)))
        else:
            msgs.append(gen.create_cancel())
    return msgs


# ---------------------------------------------------------------------------
# Adversarial storm suite (ROADMAP item 4): five named profiles that model
# how prediction markets actually die — at event boundaries, not in the zipf
# steady state. Every profile is seed-deterministic (same arguments, same
# stream — tests/test_workload.py) and exposes exact BURST WINDOWS: message
# index ranges [lo, hi) a producer should offer at `mult` times the base
# pacing, which is what turns a stored stream into an arrival-rate storm
# (wire messages carry no timestamps, so rate lives in the producer).
# kme-chaos paces with these windows; the overload controller's
# deterministic simulation (bridge/broker.py simulate_overload) replays the
# same windows for the gated shed_frac metrics.


def _zipf_cdf(n: int, a: float = 1.2) -> List[float]:
    weights = [1.0 / (r + 1) ** a for r in range(n)]
    total = sum(weights)
    cdf: List[float] = []
    acc = 0.0
    for w in weights:
        acc += w / total
        cdf.append(acc)
    return cdf


def _storm_preamble(gen: WorkloadGen, num_accounts: int, num_symbols: int,
                    deposit: int) -> List[OrderMsg]:
    """Flat funding preamble: 2*accounts + symbols messages, so burst
    windows can be computed exactly from the profile arguments."""
    msgs: List[OrderMsg] = []
    for aid in range(num_accounts):
        msgs.append(gen.create_account(aid))
        msgs.append(gen.create_transfer(aid, deposit))
    for sid in range(num_symbols):
        msgs.append(gen.create_symbol(sid))
    return msgs


def _preamble_len(num_accounts: int, num_symbols: int) -> int:
    return 2 * num_accounts + num_symbols


def _burst_ranges(num_events: int, bursts: int,
                  frac: float) -> List[Tuple[int, int]]:
    """`bursts` evenly-spaced event-index ranges, each ~frac of the
    stream (the same arithmetic shape as payout_storm_stream's
    storm_at, so window placement is deterministic)."""
    width = max(1, int(num_events * frac))
    out: List[Tuple[int, int]] = []
    for i in range(bursts):
        c = (i + 1) * num_events // (bursts + 1)
        lo = max(0, c - width // 2)
        out.append((lo, min(num_events, lo + width)))
    return out


def payout_storm_wide_stream(num_events: int, num_symbols: int,
                             num_accounts: int, seed: int = 0,
                             deposit: int = 10_000_000) -> List[OrderMsg]:
    """The event boundary itself: steady Zipf trading until ONE contiguous
    burst settles the ENTIRE symbol space (real PAYOUT per symbol, each
    immediately re-ADDed). At full scale that is ~1k symbols' worth of
    barrier ops arriving back-to-back — the all-at-once settlement shape
    KProcessor.java:148-165 implies but the reference harness never
    generates. One message per steady event, so the storm block sits at
    exactly preamble + num_events//2."""
    gen = WorkloadGen(num_accounts, num_symbols, seed=seed, validate=True,
                      payout_opcode_bug=False)
    msgs = _storm_preamble(gen, num_accounts, num_symbols, deposit)
    cdf = _zipf_cdf(num_symbols)
    storm_k = max(1, num_events // 2)
    for k in range(num_events):
        if k == storm_k:
            for sid in range(num_symbols):
                msgs.append(gen.create_payout(sid, gen.rng.random() < 0.5))
                msgs.append(gen.create_symbol(sid))
        sid = bisect.bisect_left(cdf, gen.rng.random())
        aid = gen._uniform(num_accounts)
        e = gen._uniform(1000)
        if e < 450:
            msgs.append(gen.create_buy(aid, sid, gen._normal_param(50, 10),
                                       gen._normal_param(50, 10)))
        elif e < 900:
            msgs.append(gen.create_sell(aid, sid, gen._normal_param(50, 10),
                                        gen._normal_param(50, 10)))
        else:
            msgs.append(gen.create_cancel())
    return msgs


def flash_crowd_stream(num_events: int, num_symbols: int,
                       num_accounts: int, seed: int = 0,
                       bursts: int = 3, burst_frac: float = 0.08,
                       hot_frac: float = 0.9,
                       deposit: int = 10_000_000) -> List[OrderMsg]:
    """Flash crowd: a breaking-news spike. Outside the burst windows the
    stream is ordinary Zipf trading; inside them everyone piles onto
    symbol 0 (probability hot_frac), the order mix collapses to pure
    buy/sell (nobody cancels during a rush), and the flow comes from a
    small flooder clique (num_accounts//8 accounts) — the per-account
    fairness adversary. The producer offers these windows at ~100x
    pacing (storm_windows), which is what makes it a rate storm."""
    gen = WorkloadGen(num_accounts, num_symbols, seed=seed, validate=True,
                      payout_opcode_bug=False)
    msgs = _storm_preamble(gen, num_accounts, num_symbols, deposit)
    cdf = _zipf_cdf(num_symbols)
    ranges = _burst_ranges(num_events, bursts, burst_frac)
    flooders = max(1, num_accounts // 8)
    for k in range(num_events):
        burst = any(lo <= k < hi for lo, hi in ranges)
        if burst:
            sid = (0 if gen.rng.random() < hot_frac
                   else bisect.bisect_left(cdf, gen.rng.random()))
            aid = gen._uniform(flooders)
            if gen.rng.random() < 0.5:
                msgs.append(gen.create_buy(aid, sid,
                                           gen._normal_param(50, 10),
                                           gen._normal_param(50, 10)))
            else:
                msgs.append(gen.create_sell(aid, sid,
                                            gen._normal_param(50, 10),
                                            gen._normal_param(50, 10)))
            continue
        sid = bisect.bisect_left(cdf, gen.rng.random())
        aid = gen._uniform(num_accounts)
        e = gen._uniform(1000)
        if e < 450:
            msgs.append(gen.create_buy(aid, sid, gen._normal_param(50, 10),
                                       gen._normal_param(50, 10)))
        elif e < 900:
            msgs.append(gen.create_sell(aid, sid, gen._normal_param(50, 10),
                                        gen._normal_param(50, 10)))
        else:
            msgs.append(gen.create_cancel())
    return msgs


def cancel_storm_stream(num_events: int, num_symbols: int,
                        num_accounts: int, seed: int = 0,
                        cancel_ratio: float = 0.75,
                        bogus_frac: float = 0.85,
                        deposit: int = 10_000_000) -> List[OrderMsg]:
    """Cancel blizzard (HFT quote-stuffing shape): ~3/4 of events are
    cancels, and most of those target oids that were never submitted —
    driving the engine's rej_cancel ratio to ~10x the reference
    harness's steady state (~7k/105k on the zipf stream). The remaining
    events are fresh buy/sell flow, so cancels and new orders arrive
    interleaved — the stream the priority-aware shedder must split
    (cancels drain the book: admit; new orders grow it: shed)."""
    gen = WorkloadGen(num_accounts, num_symbols, seed=seed, validate=True,
                      payout_opcode_bug=False)
    msgs = _storm_preamble(gen, num_accounts, num_symbols, deposit)
    for _ in range(num_events):
        if gen.rng.random() < cancel_ratio:
            if gen.rng.random() < bogus_frac or not gen.open_orders:
                # a cancel for an oid nobody submitted: always rej_cancel
                msgs.append(OrderMsg(
                    action=op.CANCEL,
                    oid=math.floor(gen.rng.random() * (2 ** 53 - 1)),
                    aid=gen._uniform(num_accounts)))
            else:
                msgs.append(gen.create_cancel())
            continue
        aid = gen._uniform(num_accounts)
        sid = gen._uniform(num_symbols)
        if gen.rng.random() < 0.5:
            msgs.append(gen.create_buy(aid, sid, gen._normal_param(50, 10),
                                       gen._normal_param(50, 10)))
        else:
            msgs.append(gen.create_sell(aid, sid, gen._normal_param(50, 10),
                                        gen._normal_param(50, 10)))
    return msgs


def hot_book_stream(num_events: int, num_symbols: int,
                    num_accounts: int, seed: int = 0,
                    hot_frac: float = 0.97,
                    deposit: int = 10_000_000) -> List[OrderMsg]:
    """One-symbol pathology: hot_frac of ALL flow lands on symbol 0 with
    a tight price band (N(50, 3) — nearly every arrival crosses), and
    cancels are rare so the book only deepens. Unlike zipf-hot there is
    no warm cold-set for a rebalancer to migrate: a single book takes
    the whole storm, which no symbol-sharding layout can split — the
    overload controller is the only defense left."""
    if num_symbols < 2:
        raise ValueError("hot-book needs >= 2 symbols (hot + background)")
    gen = WorkloadGen(num_accounts, num_symbols, seed=seed, validate=True,
                      payout_opcode_bug=False)
    msgs = _storm_preamble(gen, num_accounts, num_symbols, deposit)
    for _ in range(num_events):
        sid = (0 if gen.rng.random() < hot_frac
               else 1 + gen._uniform(num_symbols - 1))
        aid = gen._uniform(num_accounts)
        e = gen._uniform(1000)
        if e < 475:
            msgs.append(gen.create_buy(aid, sid, gen._normal_param(50, 3),
                                       gen._normal_param(50, 10)))
        elif e < 950:
            msgs.append(gen.create_sell(aid, sid, gen._normal_param(50, 3),
                                        gen._normal_param(50, 10)))
        else:
            msgs.append(gen.create_cancel())
    return msgs


def liquidation_cascade_stream(num_events: int, num_symbols: int,
                               num_accounts: int, seed: int = 0,
                               cascades: int = 2,
                               deposit: int = 40_000) -> List[OrderMsg]:
    """Balance-exhaustion cascade: accounts are funded thinly (~16
    orders' margin), the mix is buy-heavy so margin locks up fast, and
    at each cascade point EVERY symbol is settled long-side (PAYOUT
    success=True, then re-ADDed) while orders are still resting — the
    mass-liquidation-against-open-interest interaction. Losers come out
    of each cascade with exhausted balances, so the post-cascade flow
    turns into a rej_risk wave. One message per steady event: cascade
    block c sits at exactly preamble + (c+1)*num_events//(cascades+1)
    + 2*num_symbols*c."""
    if cascades < 1:
        raise ValueError("liquidation-cascade needs cascades >= 1")
    gen = WorkloadGen(num_accounts, num_symbols, seed=seed, validate=True,
                      payout_opcode_bug=False)
    msgs = _storm_preamble(gen, num_accounts, num_symbols, deposit)
    cdf = _zipf_cdf(num_symbols)
    cascade_at = {max(1, (i + 1) * num_events // (cascades + 1))
                  for i in range(cascades)}
    for k in range(num_events):
        if k in cascade_at:
            for sid in range(num_symbols):
                msgs.append(gen.create_payout(sid, True))
                msgs.append(gen.create_symbol(sid))
        sid = bisect.bisect_left(cdf, gen.rng.random())
        aid = gen._uniform(num_accounts)
        e = gen._uniform(1000)
        if e < 650:
            msgs.append(gen.create_buy(aid, sid, gen._normal_param(50, 10),
                                       gen._normal_param(50, 10)))
        elif e < 900:
            msgs.append(gen.create_sell(aid, sid, gen._normal_param(50, 10),
                                        gen._normal_param(50, 10)))
        else:
            msgs.append(gen.create_cancel())
    return msgs


class StormProfile(NamedTuple):
    """Registry row: generator + full-scale defaults + burst windows.

    windows(num_events, num_symbols, num_accounts) returns absolute
    message-index ranges [(lo, hi, mult), ...]: offer messages in
    [lo, hi) at mult x the base pacing."""

    name: str
    summary: str
    symbols: int
    accounts: int
    fn: Callable[..., List[OrderMsg]]
    windows: Callable[[int, int, int], List[Tuple[int, int, int]]]


def _w_payout_wide(ev: int, sy: int, ac: int) -> List[Tuple[int, int, int]]:
    lo = _preamble_len(ac, sy) + max(1, ev // 2)
    return [(lo, lo + 2 * sy, 100)]


def _w_flash_crowd(ev: int, sy: int, ac: int) -> List[Tuple[int, int, int]]:
    pre = _preamble_len(ac, sy)
    return [(pre + lo, pre + hi, 100)
            for lo, hi in _burst_ranges(ev, 3, 0.08)]


def _w_cancel_storm(ev: int, sy: int, ac: int) -> List[Tuple[int, int, int]]:
    pre = _preamble_len(ac, sy)
    return [(pre + lo, pre + hi, 20)
            for lo, hi in _burst_ranges(ev, 2, 0.10)]


def _w_hot_book(ev: int, sy: int, ac: int) -> List[Tuple[int, int, int]]:
    pre = _preamble_len(ac, sy)
    return [(pre + lo, pre + hi, 10)
            for lo, hi in _burst_ranges(ev, 1, 0.20)]


def _w_cascade(ev: int, sy: int, ac: int) -> List[Tuple[int, int, int]]:
    pre = _preamble_len(ac, sy)
    out = []
    for c in range(2):
        lo = pre + max(1, (c + 1) * ev // 3) + 2 * sy * c
        out.append((lo, lo + 2 * sy + max(1, ev // 20), 50))
    return out


STORM_PROFILES = {
    "payout-storm-wide": StormProfile(
        "payout-storm-wide",
        "settle the entire symbol space (~1k symbols) in one contiguous "
        "PAYOUT+re-ADD burst mid-stream",
        1000, 64, payout_storm_wide_stream, _w_payout_wide),
    "flash-crowd": StormProfile(
        "flash-crowd",
        "100x-rate burst windows where a small flooder clique piles "
        "onto one symbol (per-account fairness adversary)",
        64, 64, flash_crowd_stream, _w_flash_crowd),
    "cancel-storm": StormProfile(
        "cancel-storm",
        "~75% cancels, mostly for never-submitted oids: rej_cancel at "
        "~10x the reference harness ratio, interleaved with fresh flow",
        16, 32, cancel_storm_stream, _w_cancel_storm),
    "hot-book": StormProfile(
        "hot-book",
        "97% of flow on ONE tight-priced symbol — the pathology no "
        "symbol-sharding layout can split",
        8, 32, hot_book_stream, _w_hot_book),
    "liquidation-cascade": StormProfile(
        "liquidation-cascade",
        "thin funding + buy-heavy flow, then mass long-side settlement "
        "against open interest: a rej_risk exhaustion wave",
        32, 48, liquidation_cascade_stream, _w_cascade),
}


def storm_stream(name: str, num_events: int, *, num_symbols: int = None,
                 num_accounts: int = None, seed: int = 0) -> List[OrderMsg]:
    """Generate a named storm profile (registry defaults unless the
    caller scales symbols/accounts down, e.g. for CI)."""
    p = STORM_PROFILES[name]
    return p.fn(num_events,
                p.symbols if num_symbols is None else num_symbols,
                p.accounts if num_accounts is None else num_accounts,
                seed=seed)


def storm_windows(name: str, num_events: int, num_symbols: int = None,
                  num_accounts: int = None) -> List[Tuple[int, int, int]]:
    """Burst windows for a named profile at the given scale: absolute
    message-index ranges [(lo, hi, mult), ...]."""
    p = STORM_PROFILES[name]
    return p.windows(num_events,
                     p.symbols if num_symbols is None else num_symbols,
                     p.accounts if num_accounts is None else num_accounts)


def spliced_stream(num_events: int, seed: int = 0,
                   splices: Sequence[Tuple[int, str, int]] = (),
                   num_accounts: int = 10,
                   num_symbols: int = 3,
                   prefund_cash: int = 0) -> List[OrderMsg]:
    """Generative scenario composition (kme-sim, kme_tpu/sim/): the
    reference harness baseline with named storm bursts spliced in at
    stream positions. `splices` is [(at, profile, n), ...] — insert an
    `n`-event `profile` burst (STORM_PROFILES) before baseline position
    `at`. Bursts keep their registry symbol/account spaces, so a spliced
    storm brings its own preamble and collides with the baseline's id
    space only where the registry says it does; everything stays a pure
    function of (num_events, seed, splices), which is what lets a
    shrunk fault schedule regenerate its input byte-identically.

    `prefund_cash` > 0 prepends a CREATE_BALANCE + TRANSFER(cash) pair
    for every account the composed stream can touch (baseline space ∪
    spliced profiles' registry spaces). Grouped serving's parity
    contract requires the funded envelope — the front's shadow-cash
    margin bound is a conservative LOWER bound that never models
    releases, so a depleted account can see a cross-shard grant fall
    short and the group engine reject what the single oracle accepts
    (`transfer_shortfall_total`; test_front pins shortfall == 0 for
    exactly this reason). The deposits ride IN the stream, seen
    identically by the oracle and the cluster."""
    base = harness_stream(num_events, seed=seed,
                          num_accounts=num_accounts,
                          num_symbols=num_symbols)
    # apply back-to-front so earlier positions stay valid
    for at, name, n in sorted(splices, key=lambda s: s[0], reverse=True):
        burst = storm_stream(name, n, seed=seed ^ 0x5EED)
        at = max(0, min(len(base), int(at)))
        base[at:at] = burst
    if prefund_cash > 0:
        space = max([num_accounts]
                    + [STORM_PROFILES[name].accounts
                       for _, name, _ in splices])
        base[0:0] = [m for aid in range(space)
                     for m in (OrderMsg(action=op.CREATE_BALANCE,
                                        aid=aid),
                               OrderMsg(action=op.TRANSFER, aid=aid,
                                        size=int(prefund_cash)))]
    return base
