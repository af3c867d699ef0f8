"""Pallas row-DMA: in-place per-lane row updates of big HBM state.

The lane engine's position state is (lanes x accounts) — ~16MB at the
bench shapes — but each scan step touches only the W active lanes' rows.
XLA:TPU scatter rewrites the WHOLE array per step (~1us/MB — measured
~24us/step at S=1024, A=2048, the dominant term of the round-3 step
profile, artifacts/profile_r03_summary.md). These kernels replace that
with true in-place row updates:

  gather_lane_rows:  DMA the W rows into a small (W, SUB, 128) block.
  scatter_lane_rows: DMA updated rows back, aliased in place
                     (input_output_aliases), skipping the scrap lane.

Measured on the v5e chip (scripts/exp_pallas_rowdma.py): 2.7us/step for
a full gather+update+scatter round vs 24.1us for the flat scatter —
including the s64 join/split (below) and the one-hot block update.

Backend constraints that shaped the design (all hit on the real chip):
- the X64-rewrite pass refuses s64 pallas_call operands, so everything
  crossing the kernel boundary is int32; 64-bit state is stored as
  PLANAR lo/hi int32 halves and joined to real s64 only on the small
  (W, A) blocks (join64/split64) where XLA's x64 emulation handles it;
- Mosaic memref indices must be 32-bit (np.int32 everywhere);
- a 2D VMEM ref cannot be sliced to one sublane row, so rows are shaped
  (SUB, 128) tiles and the state array is (S, SUB, 128).

On CPU (the test backend) the same kernels run under
``interpret=True`` — the kernel logic itself is what the parity suite
exercises, not a shadow implementation.
"""

from __future__ import annotations

import numpy as np

from kme_tpu import _jaxsetup
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LN = 128  # minor (lane) dim of every row tile


def _i32(x) -> np.int32:
    return np.int32(x)


def row_shape(width: int) -> tuple:
    """(SUB, LN) tile shape for a row of `width` int32 elements."""
    if width % LN != 0:
        raise ValueError(f"row width {width} must be a multiple of {LN}")
    return width // LN, LN


def join64(lo: jax.Array, hi: jax.Array) -> jax.Array:
    """Reassemble s64 from planar int32 halves (small blocks only)."""
    return (lo.astype(jnp.int64) & 0xFFFFFFFF) | (hi.astype(jnp.int64) << 32)


def split64(v: jax.Array) -> tuple:
    """s64 -> (lo, hi) int32 halves."""
    return (v & 0xFFFFFFFF).astype(jnp.int32), (v >> 32).astype(jnp.int32)


def pack64_np(flat64: np.ndarray, lanes: int) -> np.ndarray:
    """Host-side: (lanes, A) or (lanes*A,) s64 -> (lanes, SUB, LN)
    planar i32 [lo | hi] rows (checkpoint restore, state import). THE
    one definition of the planar layout on the host side — keep the
    device kernels, this packer and unpack64_np in lockstep."""
    v = np.asarray(flat64, np.int64).reshape(lanes, -1)
    lo = (v & 0xFFFFFFFF).astype(np.uint32).astype(np.int32)
    hi = (v >> 32).astype(np.int32)
    return np.concatenate([lo, hi], axis=1).reshape(
        (lanes,) + row_shape(2 * v.shape[1]))


def unpack64_np(rows: np.ndarray, lanes: int) -> np.ndarray:
    """Inverse of pack64_np: planar i32 rows -> (lanes, A) s64."""
    v = np.asarray(rows, np.int32).reshape(lanes, -1)
    A = v.shape[1] // 2
    return ((v[:, :A].astype(np.int64) & 0xFFFFFFFF)
            | (v[:, A:].astype(np.int64) << 32))


def join_rows(rows: jax.Array) -> jax.Array:
    """(W, SUB, LN) planar i32 rows -> (W, A) s64 block."""
    W = rows.shape[0]
    v = rows.reshape(W, -1)
    A = v.shape[1] // 2
    return join64(v[:, :A], v[:, A:])


def split_rows(blk: jax.Array) -> jax.Array:
    """(W, A) s64 block -> (W, SUB, LN) planar i32 rows."""
    W, A = blk.shape
    lo, hi = split64(blk)
    return jnp.concatenate([lo, hi], axis=1).reshape(
        (W,) + row_shape(2 * A))


def _gather_kernel(W):
    def kernel(lanes_ref, flat_ref, out_ref, sem):
        for w in range(W):
            pltpu.make_async_copy(
                flat_ref.at[lanes_ref[_i32(w)]],
                out_ref.at[_i32(w)], sem.at[_i32(w)]).start()
        for w in range(W):
            pltpu.make_async_copy(
                flat_ref.at[lanes_ref[_i32(w)]],
                out_ref.at[_i32(w)], sem.at[_i32(w)]).wait()

    return kernel


def _scatter_kernel(W, skip_lane):
    def kernel(lanes_ref, flat_ref, rows_ref, out_ref, sem):
        # out_ref aliases flat_ref in place. The scrap lane (padding
        # slots; may repeat within a step) is skipped outright — real
        # lanes are distinct by the scheduler's one-message-per-lane
        # step invariant, so every started DMA has a private target.
        for w in range(W):
            @pl.when(lanes_ref[_i32(w)] != _i32(skip_lane))
            def _():
                pltpu.make_async_copy(
                    rows_ref.at[_i32(w)],
                    out_ref.at[lanes_ref[_i32(w)]],
                    sem.at[_i32(w)]).start()
        for w in range(W):
            @pl.when(lanes_ref[_i32(w)] != _i32(skip_lane))
            def _():
                pltpu.make_async_copy(
                    rows_ref.at[_i32(w)],
                    out_ref.at[lanes_ref[_i32(w)]],
                    sem.at[_i32(w)]).wait()

    return kernel


def gather_lane_rows(flat: jax.Array, lanes: jax.Array) -> jax.Array:
    """flat: (S, SUB, LN) i32 in HBM; lanes: (W,) i32 -> (W, SUB, LN)."""
    S, SUB, ln = flat.shape
    (W,) = lanes.shape
    return pl.pallas_call(
        _gather_kernel(W),
        out_shape=jax.ShapeDtypeStruct((W, SUB, ln), jnp.int32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.SemaphoreType.DMA((W,))],
        interpret=_jaxsetup.interpret(),
    )(lanes.astype(jnp.int32), flat)


def scatter_lane_rows(flat: jax.Array, lanes: jax.Array,
                      rows: jax.Array, skip_lane: int) -> jax.Array:
    """Write rows back into flat at `lanes`, IN PLACE (aliased); rows of
    `skip_lane` are dropped. Returns the updated flat array."""
    S, SUB, ln = flat.shape
    (W,) = lanes.shape
    return pl.pallas_call(
        _scatter_kernel(W, skip_lane),
        out_shape=jax.ShapeDtypeStruct(flat.shape, jnp.int32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((W,))],
        input_output_aliases={1: 0},
        interpret=_jaxsetup.interpret(),
    )(lanes.astype(jnp.int32), flat, rows.astype(jnp.int32))
