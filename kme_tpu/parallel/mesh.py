"""Sharded engine step: shard_map over the 'symbol' mesh axis.

Layout (SURVEY.md §2.3 "TPU-native equivalent", §7 step 6):
- lane state (books, positions, seq, flags): sharded on the leading
  symbol axis — each device owns S/n contiguous lanes;
- account state (balances): replicated; every step produces a dense
  (A,) delta on each shard which is psum-merged — exact because the
  scheduler guarantees per-step account disjointness (lanes.py
  docstring), so the sum has at most one non-zero contributor per slot;
- the sticky error code: pmax-merged so any shard's envelope error
  surfaces globally;
- barrier ops (payout/remove): the owning shard resolves the global
  lane to its local index, wipes/credits locally, and the balance
  delta rides the same psum.

The same step function works single-device (axis_name=None) — the
sharded build is a thin shard_map wrapper around engine/lanes.py.

Multi-host (DCN): the mesh is built from jax.devices(), so under
`jax.distributed.initialize()` the same code spans hosts — the symbol
axis lays contiguous lane blocks per process, keeping the per-step
balance/metric psum on ICI within a slice and crossing DCN only for the
rare barrier settles and the replicated (A,)-sized merges (the only
cross-shard traffic this design has; fills ride the GSPMD gather in
kme_tpu/engine/lanes.py chunk_compaction). EXECUTED EVIDENCE:
tests/test_multihost.py runs the sharded session SPMD across two OS
processes (4 virtual CPU devices each, one 8-way jax.distributed mesh)
and requires the wire stream bit-identical to a single-process run —
the reference analog of multiple Streams instances joining one group
(KProcessor.java:59-60).
"""

from __future__ import annotations

import functools

import numpy as np

import kme_tpu._jaxsetup  # noqa: F401
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kme_tpu.engine import lanes as L

AXIS = "symbol"


@functools.lru_cache(maxsize=None)
def build_mesh(shards: int) -> Mesh:
    """One Mesh per shard count per process — sessions share it, so the
    jitted sharded builders below cache across sessions exactly like the
    single-device build_lane_chunk lru_cache."""
    devs = jax.devices()
    if len(devs) < shards:
        raise ValueError(
            f"need {shards} devices for {shards} shards, have {len(devs)}")
    return Mesh(np.array(devs[:shards]), axis_names=(AXIS,))


def state_specs(state) -> dict:
    """PartitionSpec pytree for the lane state: lane-major arrays sharded
    on the symbol axis, account/global arrays replicated."""
    specs = {}
    for k, v in state.items():
        if k in ("bal", "bal_used", "err", "metrics", "hist", "fillbuf",
                 "filloff"):
            # the packed fill log is REPLICATED: the chunk wrapper runs
            # under GSPMD, which gathers each window's compact (M, E)
            # fills over the mesh before the append — so every shard
            # holds the identical log and the host fetches one slice
            specs[k] = P()
        else:
            specs[k] = P(AXIS)
    return specs


def build_sharded_step(cfg: L.LaneConfig, mesh: Mesh):
    """(state, batch) -> (state, outs), lanes sharded over `mesh`."""
    assert cfg.lanes % mesh.devices.size == 0, (cfg.lanes, mesh.devices.size)
    local_cfg = L.LaneConfig(
        lanes=cfg.lanes // mesh.devices.size, slots=cfg.slots,
        accounts=cfg.accounts, max_fills=cfg.max_fills, steps=cfg.steps)
    inner = L.build_lane_step(local_cfg, axis_name=AXIS)

    st_specs = state_specs(L.make_lane_state(cfg))
    batch_specs = {k: P(None, AXIS) for k in ("act", "oid", "aid", "price",
                                              "size")}
    out_specs = {
        "ok": P(None, AXIS), "residual": P(None, AXIS),
        "append": P(None, AXIS), "prev_oid": P(None, AXIS),
        "nfill": P(None, AXIS), "cap_reject": P(None, AXIS),
        "fill_oid": P(None, AXIS),
        "fill_aid": P(None, AXIS), "fill_price": P(None, AXIS),
        "fill_size": P(None, AXIS), "err": P(),
    }
    return jax.shard_map(inner, mesh=mesh,
                         in_specs=(st_specs, batch_specs),
                         out_specs=(st_specs, out_specs))


def build_sharded_chunk(cfg: L.LaneConfig, mesh: Mesh, T: int, M: int):
    """Compact-I/O chunk (L.chunk_compaction) around the SHARDED scan:
    the (M,) message vectors stay replicated, the grid scatter and output
    compaction run under GSPMD (with_sharding_constraint pins the grids
    to the symbol axis), and the scan itself is the shard_map step.
    Fills ride the same packed device log as the single-device path:
    GSPMD gathers the per-window compact (M, E) fill outputs over the
    mesh (ICI all-gather of compact data, never dense grids) and the
    append lands identically on every shard's replicated log."""
    sstep = build_sharded_step(cfg, mesh)
    grid_sh = NamedSharding(mesh, P(None, AXIS))

    def pinned_step(state, batch):
        batch = jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, grid_sh), batch)
        return sstep(state, batch)

    return L.chunk_compaction(cfg, T, M, pinned_step)


def build_sharded_settle(cfg: L.LaneConfig, mesh: Mesh):
    """(state, global_lane, credit_size, mode) -> (state, ok), sharded.

    The owning shard computes its local lane index; other shards pass
    lane=-1 (no-op) and contribute zero to the psum'd balance delta."""
    n = mesh.devices.size
    assert cfg.lanes % n == 0
    S_local = cfg.lanes // n
    local_cfg = L.LaneConfig(lanes=S_local, slots=cfg.slots,
                             accounts=cfg.accounts, max_fills=cfg.max_fills,
                             steps=cfg.steps)
    inner = L.build_barrier_ops(local_cfg, axis_name=AXIS)

    def settle(state, global_lane, credit_size, mode):
        shard = jax.lax.axis_index(AXIS).astype(jnp.int32)
        owner = global_lane // S_local == shard
        local = jnp.where(owner, global_lane % S_local, -1).astype(jnp.int32)
        return inner(state, local, credit_size, mode)

    st_specs = state_specs(L.make_lane_state(cfg))
    return jax.shard_map(settle, mesh=mesh,
                         in_specs=(st_specs, P(), P(), P()),
                         out_specs=(st_specs, P()))


@functools.lru_cache(maxsize=None)
def build_sharded_chunk_jit(cfg: L.LaneConfig, shards: int, T: int, M: int):
    """Jitted sharded chunk with state donation, cached per static shape
    at MODULE level — sharded sessions share compiled executables."""
    mesh = build_mesh(shards)
    return jax.jit(build_sharded_chunk(cfg, mesh, T, M), donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def build_sharded_settle_jit(cfg: L.LaneConfig, shards: int):
    mesh = build_mesh(shards)
    return jax.jit(build_sharded_settle(cfg, mesh), donate_argnums=(0,))


def shard_state(state, mesh: Mesh):
    """Place a host-built state pytree onto the mesh with its specs."""
    specs = state_specs(state)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), state, specs,
        is_leaf=lambda x: not isinstance(x, dict))
