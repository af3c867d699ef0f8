"""Bisect the cost of lax.while_loop body constructs in Mosaic.

Each variant runs a sequential outer fori32 over B messages; per
message a while_loop executes exactly ITERS iterations of a candidate
body. Reports ns per message. Run on the real chip.
"""

import sys
import time

sys.path.insert(0, "/root/repo")
import kme_tpu._jaxsetup
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

I32 = jnp.int32
_i = np.int32
MIN32 = _i(-(1 << 31))
BIG = _i(1 << 30)
LN = 128
B = 1 << 18
ITERS = 1


def build(variant: str):
    def kernel(data_ref, out_ref, sm, vr):
        ci = jax.lax.broadcasted_iota(I32, (1, LN), 1)

        def one(m, carry):
            lane = m & _i(127)

            def body(c):
                k, acc, done = c
                row = data_ref[pl.ds(lane, 1), :]
                hit = jnp.min(jnp.where(row == acc, ci, BIG))
                emp = jnp.min(jnp.where(row == _i(0), ci, BIG))
                acc = acc + jnp.where(hit < emp, _i(1), _i(2))
                if variant in ("rmw", "branch"):
                    take = acc > _i(0)
                    if variant == "branch":
                        @pl.when(take)
                        def _():
                            r = vr[0:1, :]
                            vr[0:1, :] = jnp.where(ci == k, acc, r)
                    else:
                        r = vr[0:1, :]
                        vr[0:1, :] = jnp.where(
                            take & (ci == k), acc, r)
                if variant == "carry2":
                    pass
                return k + _i(1), acc, k + _i(1) >= _i(ITERS)

            if variant.startswith("sweep"):
                limit = m & _i(63)
                sgn = jnp.where((m & _i(1)) == _i(0), _i(1), _i(-1))

                def bodys(c):
                    remaining, e, ovf, emptied, done = c
                    fi2 = (jax.lax.broadcasted_iota(I32, (1, LN), 0)
                           * _i(LN)
                           + jax.lax.broadcasted_iota(I32, (1, LN), 1))
                    ci2 = jax.lax.broadcasted_iota(I32, (1, LN), 1)
                    p_blk = data_ref[pl.ds(lane * _i(2), 1), :]
                    q_blk = data_ref[pl.ds(lane * _i(2) + _i(1), 1), :]
                    wsize = vr[0:1, :]
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
                    exceed = anyc & (e >= _i(16))
                    take = anyc & ~exceed

                    @pl.when(take)
                    def _():
                        vr[0:1, :] = jnp.where(fi2 == flat,
                                               wsize - fill, wsize)

                    remaining = remaining - jnp.where(take, fill, _i(0))
                    e = e + jnp.where(take, _i(1), _i(0))
                    ovf = ovf | exceed
                    emptied = jnp.where(take, have - fill == _i(0),
                                        emptied)
                    done = ((~anyc) | exceed | (remaining == _i(0))
                            | (e >= _i(ITERS)))
                    return remaining, e, ovf, emptied, done

                vr[0:1, :] = data_ref[pl.ds(lane, 1), :]
                want = _i(0) if variant == "sweep0" else (m & _i(31))
                (res, e, _o, _em, _d) = jax.lax.while_loop(
                    lambda c: ~c[4], bodys,
                    (want, _i(0), False, False, want == _i(0)))
                sm[0] = sm[0] + res + e
                return carry
            if variant == "carryvec":
                def bodyv(c):
                    k, accv, done = c
                    row = data_ref[pl.ds(lane, 1), :]
                    hit = jnp.min(jnp.where(row == k, ci, BIG))
                    accv = jnp.where(ci == hit, accv + _i(1), accv)
                    return k + _i(1), accv, k + _i(1) >= _i(ITERS)

                _, accv, _ = jax.lax.while_loop(
                    lambda c: ~c[2], bodyv,
                    (_i(0), jnp.zeros((1, LN), I32), ITERS <= 0))
                res = jnp.max(accv)
            else:
                _, res, _ = jax.lax.while_loop(
                    lambda c: ~c[2], body, (_i(0), m, ITERS <= 0))
            sm[0] = sm[0] + res
            return carry

        def cond(c):
            return c[0] < _i(B)

        def step(c):
            i, x = c
            return i + _i(1), one(i, x)

        sm[0] = _i(0)
        jax.lax.while_loop(cond, step, (_i(0), _i(0)))
        out_ref[0:1, :] = jnp.where(ci == _i(0), sm[0], _i(0))

    def call(data):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((1, LN), I32),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.SMEM((4,), I32),
                            pltpu.VMEM((2, LN), I32)],
            interpret=kme_tpu._jaxsetup.interpret(),
        )(data)

    return jax.jit(call)


def main():
    global ITERS
    data = jnp.asarray(np.random.default_rng(0)
                       .integers(1, 99, (256, LN)).astype(np.int32))
    for variant in ("sweep0", "sweep1"):
        for it in (1, 2):
            ITERS = it
            fn = build(f"{variant}")
            c = fn.lower(data).compile()
            t0 = time.perf_counter()
            np.asarray(c(data))
            _ = time.perf_counter() - t0
            ts = []
            for _r in range(3):
                t0 = time.perf_counter()
                np.asarray(c(data))
                ts.append(time.perf_counter() - t0)
            print(f"{variant:9s} iters={it}: {min(ts)/B*1e9:7.0f} ns/msg",
                  file=sys.stderr)


if __name__ == "__main__":
    main()
