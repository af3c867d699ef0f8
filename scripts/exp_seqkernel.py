"""De-risk experiment for the sequential Pallas mega-kernel (round 4).

Question: can a Pallas TPU kernel process a micro-batch of B messages
STRICTLY SEQUENTIALLY (the reference's own semantics,
KProcessor.java:95-126) fast enough to beat the vectorized sweep engine
— i.e. what does one message cost in device time when the hot state is
VMEM-resident and the per-message work is scalar-driven row ops?

This is NOT the engine: it runs a simplified trade-only core (match
sweep against the opposite side + rest of the residual) with none of the
balance/position/i64 machinery. What it shares with the real kernel is
the *cost model*: SMEM scalar message reads driving dynamic (1, N) row
loads/stores, masked vector reductions for best-maker search, predicated
fill iterations, and per-message output row RMW.

Usage: python scripts/exp_seqkernel.py [B] [E] [S]
Prints us/msg for the kernel and a numpy replica check.

RESULTS (v5e chip, 2026-07-30): with the correctness phase's np.asarray
fetch removed from the process, the bare sweep body runs at **~64 ns/msg
(15.5M msg/s)** at B=2048, S=1024 — the sequential-kernel design beats
the vectorized sweep engine's per-step op-count floor by ~2 orders of
magnitude. CAVEAT: in the environment that figure was taken in, every
dispatch after an output fetch cost a fixed ~100-160ms, so THIS
script's timed numbers (which run after the correctness fetch) were
not kernel-bound. Mosaic constraints discovered here (i64 fori index, weak
literals, scalar jnp.sum, i1-vector select, aliased-out-ref reads) are
recorded in the engine module's docstring.
"""

import functools
import os
import sys
import time

sys.setrecursionlimit(100_000)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import kme_tpu._jaxsetup
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

I32 = jnp.int32
BIG = np.int32(1 << 30)


def fori32(n, body, init):
    """fori_loop with an int32 induction variable. Under x64,
    lax.fori_loop always carries an i64 counter, which Mosaic cannot
    convert back to i32 (the convert lowering recurses) — so roll the
    loop with while_loop and an explicit np.int32 counter."""
    def cond(c):
        return c[0] < np.int32(n)

    def step(c):
        i, carry = c
        return i + np.int32(1), body(i, carry)

    return jax.lax.while_loop(cond, step, (np.int32(0), init))[1]


def build(B, E, S, N=128):
    """price/size planes are (2S, N): row 2*lane+side. Buy=side 0 rests
    on row 2l+0, sweeps row 2l+1 (asks, min price first); sell mirrors.
    Outputs: residual per message."""

    def kernel(lane_s, isbuy_s, price_s, size_s,
               price_ref, size_ref, oprice_ref, osize_ref, resid_ref):
        # aliased in/out: copy happens via aliasing (same buffers)
        iota = jax.lax.broadcasted_iota(I32, (1, N), 1)
        def one(m, _):
            lane = lane_s[m]
            isbuy = isbuy_s[m]
            limit = price_s[m]
            want = size_s[m]
            opp = lane * 2 + isbuy          # isbuy=1 -> sweep asks row
            own = lane * 2 + (1 - isbuy)

            # state lives in the ALIASED OUTPUT refs: read and write
            # through them only, so message m sees m-1's writes (the
            # input refs are just the aliasing anchors)
            prow = oprice_ref[pl.ds(opp, 1), :]
            srow = osize_ref[pl.ds(opp, 1), :]

            # Mosaic cannot select between i1 vectors: fold the side
            # into an i32 sign so one compare serves both directions
            sgn = np.int32(1) - np.int32(2) * (np.int32(1) - isbuy)

            def fill_iter(e, carry):
                srow, remaining = carry
                live = srow > 0
                cross = live & ((prow - limit) * sgn <= np.int32(0))
                cross = cross & (remaining > 0)
                # best price level (buy: lowest ask; sell: highest bid),
                # then FIFO proxy: lowest slot index at that price
                keyp = jnp.where(cross, prow * sgn, BIG)
                best_p = jnp.min(keyp)
                at = cross & (keyp == best_p)
                idx = jnp.min(jnp.where(at, iota, BIG))
                have = jnp.max(jnp.where(iota == idx, srow, np.int32(0)))
                can = (best_p < BIG).astype(I32)
                fill = jnp.minimum(remaining, have) * can
                srow = jnp.where(iota == idx, srow - fill, srow)
                return srow, remaining - fill

            srow, remaining = fori32(E, fill_iter, (srow, want))
            osize_ref[pl.ds(opp, 1), :] = srow

            # rest the residual on own side at the first free slot
            @pl.when(remaining > 0)
            def _():
                oprow = oprice_ref[pl.ds(own, 1), :]
                osrow = osize_ref[pl.ds(own, 1), :]
                free = jnp.min(jnp.where(osrow == 0, iota, BIG))
                hit = iota == free
                oprice_ref[pl.ds(own, 1), :] = jnp.where(hit, limit, oprow)
                osize_ref[pl.ds(own, 1), :] = jnp.where(hit, remaining, osrow)

            # per-message output: residual -> row RMW
            r = resid_ref[pl.ds(m >> 7, 1), :]
            resid_ref[pl.ds(m >> 7, 1), :] = jnp.where(
                iota == (m & np.int32(127)), remaining, r)
            return np.int32(0)

        fori32(B, one, np.int32(0))

    @jax.jit
    def run(lane, isbuy, price, size, bprice, bsize):
        return pl.pallas_call(
            kernel,
            out_shape=(jax.ShapeDtypeStruct((2 * S, N), jnp.int32),
                       jax.ShapeDtypeStruct((2 * S, N), jnp.int32),
                       jax.ShapeDtypeStruct((B // 128, 128), jnp.int32)),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 4
            + [pl.BlockSpec(memory_space=pltpu.VMEM)] * 2,
            out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                       pl.BlockSpec(memory_space=pltpu.VMEM),
                       pl.BlockSpec(memory_space=pltpu.VMEM)),
            input_output_aliases={4: 0, 5: 1},
            interpret=kme_tpu._jaxsetup.interpret(),
        )(lane, isbuy, price, size, bprice, bsize)

    return run


def replica(lane, isbuy, price, size, bprice, bsize, E):
    bprice = bprice.copy()
    bsize = bsize.copy()
    resid = np.zeros(len(lane), np.int32)
    for m in range(len(lane)):
        l, b, p, want = lane[m], isbuy[m], price[m], size[m]
        opp, own = 2 * l + b, 2 * l + (1 - b)
        remaining = want
        for _ in range(E):
            if remaining <= 0:
                break
            live = bsize[opp] > 0
            cross = live & ((bprice[opp] <= p) if b else (bprice[opp] >= p))
            if not cross.any():
                break
            keyp = np.where(cross, bprice[opp] if b else -bprice[opp], BIG)
            bp = keyp.min()
            idx = np.where(cross & (keyp == bp))[0][0]
            fill = min(remaining, bsize[opp][idx])
            bsize[opp][idx] -= fill
            remaining -= fill
        if remaining > 0:
            free = np.where(bsize[own] == 0)[0]
            if len(free):
                bprice[own][free[0]] = p
                bsize[own][free[0]] = remaining
        resid[m] = remaining
    return bprice, bsize, resid


def main():
    B = int(sys.argv[1]) if len(sys.argv) > 1 else 2048
    E = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    S = int(sys.argv[3]) if len(sys.argv) > 3 else 1024
    N = 128
    rng = np.random.default_rng(0)
    lane = rng.integers(0, S, B).astype(np.int32)
    isbuy = rng.integers(0, 2, B).astype(np.int32)
    price = rng.integers(1, 126, B).astype(np.int32)
    size = rng.integers(1, 100, B).astype(np.int32)
    bprice = np.zeros((2 * S, N), np.int32)
    bsize = np.zeros((2 * S, N), np.int32)

    run = build(B, E, S, N)
    t0 = time.perf_counter()
    out = jax.tree.map(np.asarray, run(lane, isbuy, price, size,
                                       jnp.asarray(bprice),
                                       jnp.asarray(bsize)))
    print(f"compile+first: {time.perf_counter()-t0:.1f}s", file=sys.stderr)

    wp, ws, wr = replica(lane, isbuy, price, size, bprice, bsize, E)
    ok_s = (out[1] == ws).all()
    ok_r = (out[2].reshape(-1)[:B] == wr).all()
    # price plane only meaningful where size>0
    ok_p = (np.where(ws > 0, out[0], 0) == np.where(ws > 0, wp, 0)).all()
    print(f"correct: size={ok_s} resid={ok_r} price={ok_p}", file=sys.stderr)

    # timing: state round-trips through the jit boundary each call
    args = (lane, isbuy, price, size)
    st = (jnp.asarray(bprice), jnp.asarray(bsize))
    for _ in range(2):
        o = run(*args, *st)
        st = (o[0], o[1])
    jax.block_until_ready(st)
    t0 = time.perf_counter()
    reps = 20
    for _ in range(reps):
        o = run(*args, *st)
        st = (o[0], o[1])
    jax.block_until_ready(st)
    dt = (time.perf_counter() - t0) / reps
    print(f"B={B} E={E} S={S}: {dt*1e3:.2f} ms/call, "
          f"{dt/B*1e6:.3f} us/msg, {B/dt/1e6:.2f} M msg/s", file=sys.stderr)


if __name__ == "__main__":
    main()
