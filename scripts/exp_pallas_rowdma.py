"""Chip experiment: Pallas row-DMA gather/scatter vs XLA full-array scatter.

The round-3 profile (artifacts/profile_r03_summary.md) shows ~36us/step
of the ~106us lane step going to two full-array int64 scatters into the
flat (S*A,) position arrays (XLA:TPU scatter rewrites the whole array,
~1us/MB). Replacement design validated here on the real chip:

  K1 gather_rows:  DMA the W active lanes' rows from the HBM-resident
                   flat array into a small (W, R) block.
  K2 scatter_rows: DMA updated rows back IN PLACE (input_output_aliases).

Constraint discovered on this backend: the X64-rewrite pass refuses s64
custom-call operands ("not implemented" for pallas_call), so the arrays
crossing the kernel boundary must be int32. Positions therefore live as
PLANAR lo/hi int32 pairs — flat (S*2A,) with element (lane, comp, acc)
at lane*2A + comp*A + acc — and the small (W, A) blocks are joined to
real s64 for arithmetic in XLA-land, split back before the write DMA.

Checks: parity vs the s64 scatter baseline, aliasing inside lax.scan,
marginal per-step cost via scan-length slope (wall timings carry the
per-dispatch round trip; use the T-slope).

Run: python scripts/exp_pallas_rowdma.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import kme_tpu._jaxsetup  # noqa: F401
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

S, A, W, E = 1025, 2048, 8, 16
R = 2 * A  # row length in i32 lanes: [lo x A | hi x A]
LN = 128
SUB = R // LN  # rows are (SUB, 128) tiles: Mosaic can't slice 1 sublane


def _i32(x):
    return np.int32(x)


def gather_rows_kernel(lanes_ref, flat_ref, out_ref, sem):
    for w in range(W):
        pltpu.make_async_copy(
            flat_ref.at[lanes_ref[_i32(w)]],
            out_ref.at[_i32(w)], sem.at[_i32(w)]).start()
    for w in range(W):
        pltpu.make_async_copy(
            flat_ref.at[lanes_ref[_i32(w)]],
            out_ref.at[_i32(w)], sem.at[_i32(w)]).wait()


def gather_rows(flat, lanes):
    return pl.pallas_call(
        gather_rows_kernel,
        out_shape=jax.ShapeDtypeStruct((W, SUB, LN), jnp.int32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.SemaphoreType.DMA((W,))],
    )(lanes, flat)


def scatter_rows_kernel(lanes_ref, flat_ref, rows_ref, out_ref, sem):
    # out_ref aliases flat_ref; skip the scrap lane S-1 (padding rows,
    # may appear multiple times — real lanes are distinct)
    for w in range(W):
        @pl.when(lanes_ref[_i32(w)] != S - 1)
        def _():
            pltpu.make_async_copy(
                rows_ref.at[_i32(w)],
                out_ref.at[lanes_ref[_i32(w)]],
                sem.at[_i32(w)]).start()
    for w in range(W):
        @pl.when(lanes_ref[_i32(w)] != S - 1)
        def _():
            pltpu.make_async_copy(
                rows_ref.at[_i32(w)],
                out_ref.at[lanes_ref[_i32(w)]],
                sem.at[_i32(w)]).wait()


def scatter_rows(flat, lanes, rows):
    return pl.pallas_call(
        scatter_rows_kernel,
        out_shape=jax.ShapeDtypeStruct(flat.shape, jnp.int32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.ANY),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((W,))],
        input_output_aliases={1: 0},  # flat -> out, in place
    )(lanes, flat, rows)


def join64(lo, hi):
    return (lo.astype(jnp.int64) & 0xFFFFFFFF) | (hi.astype(jnp.int64) << 32)


def split64(v):
    return (v & 0xFFFFFFFF).astype(jnp.int32), (v >> 32).astype(jnp.int32)


def step_dma(carry, msg):
    """One scan step: gather W rows, s64 update on the block, scatter."""
    pa = carry
    lanes, acc, sgn = msg["lanes"], msg["acc"], msg["sgn"]
    rows = gather_rows(pa, lanes).reshape(W, R)        # (W, 2A) i32
    vals = join64(rows[:, :A], rows[:, A:])            # (W, A) s64
    oh = acc[:, :, None] == jnp.arange(A, dtype=jnp.int32)[None, None, :]
    vals = vals + jnp.sum(jnp.where(oh, sgn[:, :, None], 0), axis=1)
    lo, hi = split64(vals)
    pa = scatter_rows(pa, lanes,
                  jnp.concatenate([lo, hi], 1).reshape(W, SUB, LN))
    return pa, ()


def step_scatter(carry, msg):
    """Baseline: the engine's current flat s64 .at[idx].set scatter."""
    pa = carry
    lanes, acc, sgn = msg["lanes"], msg["acc"], msg["sgn"]
    idx = lanes[:, None] * A + acc
    a0 = pa[idx]
    pa = pa.at[idx].set(a0 + sgn)
    return pa, ()


def _msgs(T, seed):
    rng = np.random.default_rng(seed)
    return rng, {
        "lanes": jnp.asarray(
            np.stack([rng.choice(S - 1, W, replace=False)
                      for _ in range(T)]), jnp.int32),
        "acc": jnp.asarray(
            np.stack([np.stack([rng.choice(A, 2 * E, replace=False)
                                for _ in range(W)]) for _ in range(T)]),
            jnp.int32),
        "sgn": jnp.asarray(
            rng.integers(-(1 << 40), 1 << 40, (T, W, 2 * E)), jnp.int64),
    }


def run(kind, T, seed=0):
    rng, msgs = _msgs(T, seed)
    base = rng.integers(-(1 << 50), 1 << 50, S * A)
    if kind == "dma":
        pa_np = np.empty((S, 2, A), np.int32)
        pa_np[:, 0, :] = (base & 0xFFFFFFFF).reshape(S, A).astype(np.uint32).astype(np.int32)
        pa_np[:, 1, :] = (base >> 32).reshape(S, A).astype(np.int32)
        pa0 = jnp.asarray(pa_np.reshape(S, SUB, LN))
        step = step_dma
    else:
        pa0 = jnp.asarray(base, jnp.int64)
        step = step_scatter
    f = jax.jit(lambda pa, m: jax.lax.scan(step, pa, m)[0])
    out = f(pa0, msgs)
    out.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(3):
        out = f(pa0, msgs)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / 3
    out = np.asarray(out)
    if kind == "dma":
        v = out.reshape(S, 2, A)
        out = ((v[:, 0].astype(np.int64) & 0xFFFFFFFF)
               | (v[:, 1].astype(np.int64) << 32)).reshape(-1)
    return out, dt


def main():
    print(f"backend: {jax.devices()[0]}", file=sys.stderr)
    ref, _ = run("scatter", 16)
    got, _ = run("dma", 16)
    ok = np.array_equal(ref, got)
    print(f"i32-pair parity vs s64 scatter (T=16): {ok}", file=sys.stderr)
    if not ok:
        diff = np.nonzero(ref != got)[0]
        print(f"  {len(diff)} diffs, first at {diff[:10]}", file=sys.stderr)
        print(f"  ref {ref[diff[:5]]} got {got[diff[:5]]}", file=sys.stderr)
        return 1
    for kind in ("dma", "scatter"):
        _, t_lo = run(kind, 128)
        _, t_hi = run(kind, 1024)
        slope_us = (t_hi - t_lo) / (1024 - 128) * 1e6
        print(f"{kind}: T=128 {t_lo*1e3:.1f}ms  T=1024 {t_hi*1e3:.1f}ms  "
              f"slope {slope_us:.2f} us/step", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
