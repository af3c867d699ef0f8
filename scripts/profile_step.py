"""Microbenchmark the lane-engine scan step on the active backend.

Times a T-step scan at bench shapes, then times isolated candidate ops at
the same shapes to locate the per-step cost. Details to stderr.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import kme_tpu._jaxsetup  # noqa: F401
import jax
import jax.numpy as jnp
import numpy as np

from kme_tpu.engine import lanes as L


def _force(out):
    """Materialize on host: the fetch is the completion barrier."""
    leaves = jax.tree.leaves(out)
    np.asarray(leaves[0])
    np.asarray(leaves[-1])


def timeit(fn, *args, n=5, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    _force(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
        _force(out)
    return (time.perf_counter() - t0) / n


def main():
    S, N, A, E, T = 1024, 128, 2048, 16, 128
    if len(sys.argv) > 1:
        S, N, A, E, T = map(int, sys.argv[1:6])
    cfg = L.LaneConfig(lanes=S, slots=N, accounts=A, max_fills=E, steps=T)
    print(f"backend={jax.devices()[0].platform} S={S} N={N} A={A} E={E} T={T}",
          file=sys.stderr)

    state = L.make_lane_state(cfg)
    rng = np.random.default_rng(0)
    batch = {
        "act": jnp.asarray(rng.integers(0, 3, (T, S)), jnp.int32),
        "oid": jnp.asarray(rng.integers(1, 1 << 50, (T, S)), jnp.int64),
        "aid": jnp.asarray(rng.integers(0, A, (T, S)), jnp.int32),
        "price": jnp.asarray(rng.integers(0, 126, (T, S)), jnp.int32),
        "size": jnp.asarray(rng.integers(1, 100, (T, S)), jnp.int32),
    }
    step = jax.jit(L.build_lane_step(cfg))
    dt = timeit(step, state, batch)
    print(f"full scan: {dt*1e3:.1f} ms total, {dt/T*1e6:.0f} us/step",
          file=sys.stderr)

    # isolated candidate ops at step shapes
    key64 = jnp.asarray(rng.integers(0, 1 << 60, (S, N)), jnp.int64)
    aid1 = jnp.asarray(rng.integers(0, A, (S,)), jnp.int32)
    delta = jnp.asarray(rng.integers(-5, 5, (S,)), jnp.int64)
    bal = jnp.zeros((A,), jnp.int64)
    acc = jnp.asarray(rng.integers(0, A, (S, 2 * E)), jnp.int32)
    vals = jnp.asarray(rng.integers(0, 9, (S, 2 * E)), jnp.int64)
    posA = jnp.zeros((S, A), jnp.int64)
    sgn = vals
    idx2 = jnp.arange(2 * E, dtype=jnp.int32)

    cands = {
        "argsort(S,N) i64": jax.jit(lambda k: jnp.argsort(k, axis=1)),
        "2x argsort (order+inv)": jax.jit(
            lambda k: jnp.argsort(jnp.argsort(k, axis=1), axis=1)),
        "bal gather bal[aid]": jax.jit(lambda b, a: b[a]),
        "bal scatter .at[aid].add": jax.jit(
            lambda b, a, d: b.at[a].add(d)),
        "pos take_along (S,A)": jax.jit(
            lambda p, a: jnp.take_along_axis(p, a[:, None], axis=1)),
        "pos put_along (S,A)": jax.jit(
            lambda p, a, d: jnp.put_along_axis(
                p, a[:, None], d[:, None], axis=1, inplace=False)),
        "replay eq/le reductions": jax.jit(
            lambda ac, sg: (
                jnp.sum(jnp.where((ac[:, :, None] == ac[:, None, :])
                                  & (idx2[:, None] <= idx2[None, :])[None],
                                  sg[:, :, None], 0), axis=1))),
        "scat put_along (S,A) from (S,2E)": jax.jit(
            lambda p, ac, v: jnp.put_along_axis(
                jnp.concatenate([p, jnp.zeros((S, 1), p.dtype)], axis=1),
                ac, v, axis=1, inplace=False)[:, :A]),
    }
    args = {
        "argsort(S,N) i64": (key64,),
        "2x argsort (order+inv)": (key64,),
        "bal gather bal[aid]": (bal, aid1),
        "bal scatter .at[aid].add": (bal, aid1, delta),
        "pos take_along (S,A)": (posA, aid1),
        "pos put_along (S,A)": (posA, aid1, delta),
        "replay eq/le reductions": (acc, sgn),
        "scat put_along (S,A) from (S,2E)": (posA, acc, vals),
    }
    for name, fn in cands.items():
        dt = timeit(fn, *args[name])
        print(f"{name:38s} {dt*1e6:8.0f} us", file=sys.stderr)


if __name__ == "__main__":
    main()
