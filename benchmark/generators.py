"""The benchmark's own traffic generators — a copy of the two the cells
use from `kme_tpu/workload.py` (`WorkloadGen`, `harness_stream`,
`zipf_symbol_stream`), kept here so that a later PR can change the
program's generators without changing the yardstick's traffic.

They yield lazily: the harness sends the preamble while the rest of the
stream is still being drawn. Same seed, same messages as the originals
(checked by benchmark/test_yardstick.py). A traffic file names a
generator either by its name here or as `module:function` (any
generator of the program that takes `num_events`, `seed` and keyword
parameters and returns the messages)."""

from __future__ import annotations

import bisect
import importlib
import math
import random
from typing import Iterator

from kme_tpu import opcodes as op
from kme_tpu.wire import OrderMsg


class WorkloadGen:
    """Seeded port of the upstream exchange_test.js generator."""

    def __init__(self, num_accounts=10, num_symbols=3, rake=3, seed=0,
                 payout_opcode_bug=True, validate=False):
        self.num_accounts = num_accounts
        self.num_symbols = num_symbols
        self.rake = rake
        self.rng = random.Random(seed)
        self.payout_opcode_bug = payout_opcode_bug
        # validate clamps a trade's price and size into the device
        # domain (fixed mode's, and java mode's on the chip)
        self.validate = validate
        self.open_orders: dict[int, int] = {}
        # sorted oid pool: cancels select by sorted position
        self._pool: list[int] = []

    def _random_normal(self) -> float:
        u = v = 0.0
        while u == 0.0:
            u = self.rng.random()
        while v == 0.0:
            v = self.rng.random()
        return math.sqrt(-2.0 * math.log(u)) * math.cos(2.0 * math.pi * v)

    def uniform(self, n: int) -> int:
        return math.floor(self.rng.random() * n)

    def normal_param(self, mean: float, std: float) -> int:
        return math.floor(self._random_normal() * std + mean)

    def create_account(self, aid):
        return OrderMsg(action=op.CREATE_BALANCE, aid=aid)

    def create_symbol(self, sid):
        return OrderMsg(action=op.ADD_SYMBOL, sid=sid)

    def create_transfer(self, aid, amount):
        return OrderMsg(action=op.TRANSFER, aid=aid, size=amount)

    def create_payout(self, sid, success):
        action = op.CANCEL if self.payout_opcode_bug else op.PAYOUT
        return OrderMsg(action=action, sid=sid * (1 if success else -1),
                        size=100 - self.rake)

    def create_order(self, action, aid, sid, price, size):
        oid = math.floor(self.rng.random() * (2 ** 53 - 1))
        if oid not in self.open_orders:
            bisect.insort(self._pool, oid)
        self.open_orders[oid] = aid
        if self.validate:
            price, size = min(125, max(0, price)), max(1, size)
        return OrderMsg(action=action, oid=oid, aid=aid, sid=sid,
                        price=price, size=size)

    def create_cancel(self):
        if not self.open_orders:
            return OrderMsg(action=op.CANCEL)
        oid = self._pool.pop(math.floor(self.rng.random()
                                        * len(self._pool)))
        return OrderMsg(action=op.CANCEL, oid=oid,
                        aid=self.open_orders.pop(oid))

    def gen_event(self):
        e = self.uniform(1000)
        if e == 0:
            return self.create_symbol(self.uniform(self.num_symbols))
        if e == 1:
            return self.create_payout(self.uniform(self.num_symbols),
                                      self.uniform(2) == 0)
        if e in (2, 3):
            return self.create_transfer(self.uniform(self.num_accounts),
                                        self.normal_param(0, 125 * 100))
        if e <= 667:
            return self.create_order(
                op.BUY if e <= 335 else op.SELL,
                self.uniform(self.num_accounts),
                self.uniform(self.num_symbols),
                self.normal_param(50, 10), self.normal_param(50, 10))
        return self.create_cancel()


def harness_stream(num_events, seed=0, num_accounts=10, num_symbols=3,
                   rake=3, validate=False) -> Iterator[OrderMsg]:
    """The upstream harness workload (exchange_test.js:18-36): preamble,
    then `num_events` random events with its mix per mille. `validate`
    is `kme-loadgen --validate`: a trade's price goes to min(125,
    max(0, p)) and its size to max(1, s) after every draw has been made,
    so the random sequence, every oid and every other message are the
    stock stream's, and a trade inside the java device domain (0 <=
    price < 126, size > 0) is untouched."""
    gen = WorkloadGen(num_accounts, num_symbols, rake, seed,
                      validate=validate)
    for aid in range(num_accounts):
        yield gen.create_account(aid)
        yield gen.create_transfer(aid, gen.normal_param(500 * 100,
                                                        250 * 100))
    i = 0
    while i < num_symbols / 2 + 1:      # float bound, exchange_test.js:29
        yield gen.create_symbol(i)
        i += 1
    for _ in range(num_events):
        yield gen.gen_event()


def zipf_symbol_stream(num_events, num_symbols, num_accounts, seed=0,
                       zipf_a=1.2, deposit=10_000_000
                       ) -> Iterator[OrderMsg]:
    """BASELINE.json's scale workload: Zipf-skewed symbol arrival,
    uniform accounts, valid-domain prices and sizes; 45% buys, 45%
    sells, 10% cancels of a random open order."""
    gen = WorkloadGen(num_accounts, num_symbols, seed=seed, validate=True,
                      payout_opcode_bug=False)
    for aid in range(num_accounts):
        yield gen.create_account(aid)
        yield gen.create_transfer(aid, deposit)
    for sid in range(num_symbols):
        yield gen.create_symbol(sid)
    weights = [1.0 / (r + 1) ** zipf_a for r in range(num_symbols)]
    total = sum(weights)
    cdf, acc = [], 0.0
    for w in weights:
        acc += w / total
        cdf.append(acc)
    for _ in range(num_events):
        sid = bisect.bisect_left(cdf, gen.rng.random())
        aid = gen.uniform(num_accounts)
        e = gen.uniform(1000)
        if e < 900:
            yield gen.create_order(op.BUY if e < 450 else op.SELL, aid,
                                   sid, gen.normal_param(50, 10),
                                   gen.normal_param(50, 10))
        else:
            yield gen.create_cancel()


def open_stream(name: str, events: int, seed: int,
                params: dict) -> Iterator[OrderMsg]:
    """The message iterator a traffic file asks for."""
    if ":" in name:
        mod, _, fn = name.partition(":")
        return iter(getattr(importlib.import_module(mod), fn)(
            events, seed=seed, **params))
    if name not in ("harness_stream", "zipf_symbol_stream"):
        raise ValueError(f"unknown generator {name!r} (use module:function "
                         f"for one of the program's)")
    return globals()[name](events, seed=seed, **params)
