"""Scatter/gather strategy shootout at lane-step shapes, measured as
device time via chained fori_loop (carry-dependent indices defeat
hoisting; only a scalar comes back to the host)."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import kme_tpu._jaxsetup  # noqa: F401
import jax
import jax.numpy as jnp
import numpy as np

S, N, A, E = 1024, 128, 2048, 16
K = 64
TWOE = 2 * E


def measure(body, init):
    fn = jax.jit(lambda k, x: jax.lax.fori_loop(0, k, body, x),
                 static_argnums=0)

    def t(k):
        out = fn(k, init)
        np.asarray(jax.tree.leaves(out)[0]).sum()
        t0 = time.perf_counter()
        out = fn(k, init)
        np.asarray(jax.tree.leaves(out)[0]).sum()
        return time.perf_counter() - t0

    t(1)
    return (t(K + 1) - t(1)) / K


def main():
    rng = np.random.default_rng(0)
    pos = jnp.zeros((S, A), jnp.int64)
    pos_w = jnp.zeros((S, A + TWOE), jnp.int64)   # scrap columns baked in
    acc0 = jnp.asarray(rng.integers(0, A, (S, TWOE)), jnp.int32)
    vals = jnp.asarray(rng.integers(1, 9, (S, TWOE)), jnp.int64)

    def perturb(k, ac):
        # carry-dependent indices so nothing hoists; stays in [0, A)
        return (ac + k) % A

    # baseline: put_along dup indices into (S, A)
    def body_base(k, carry):
        p, ac = carry
        ac = perturb(k, ac)
        cur = jnp.take_along_axis(p, ac, axis=1)
        p = jnp.put_along_axis(p, ac, cur + vals, axis=1, inplace=False)
        return (p, ac)

    print(f"base put_along+gather dup   {measure(body_base, (pos, acc0))*1e6:8.0f} us",
          file=sys.stderr)

    # sorted-unique lax.scatter into (S, A+2E)
    dn = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(), inserted_window_dims=(1,),
        scatter_dims_to_operand_dims=(1,),
        operand_batching_dims=(0,), scatter_indices_batching_dims=(0,))

    def body_uniq(k, carry):
        p, ac = carry
        ac = perturb(k, ac)
        ac_s, val_s = jax.lax.sort((ac, vals), num_keys=1, dimension=1)
        dup = jnp.concatenate(
            [jnp.zeros((S, 1), bool), ac_s[:, 1:] == ac_s[:, :-1]], axis=1)
        j = jnp.arange(TWOE, dtype=jnp.int32)[None, :]
        idx = jnp.where(dup, A + j, ac_s)
        upd = jax.lax.scatter(
            p, idx[:, :, None], val_s, dn,
            indices_are_sorted=False, unique_indices=True,
            mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)
        return (upd, ac)

    print(f"uniq lax.scatter (S,A+2E)   {measure(body_uniq, (pos_w, acc0))*1e6:8.0f} us",
          file=sys.stderr)

    # sorted+unique scatter
    def body_sortuniq(k, carry):
        p, ac = carry
        ac = perturb(k, ac)
        ac_s, val_s = jax.lax.sort((ac, vals), num_keys=1, dimension=1)
        dup = jnp.concatenate(
            [jnp.zeros((S, 1), bool), ac_s[:, 1:] == ac_s[:, :-1]], axis=1)
        j = jnp.arange(TWOE, dtype=jnp.int32)[None, :]
        idx = jnp.where(dup, A + j, ac_s)   # NOT sorted once redirected
        # re-sort so indices really are ascending per row
        idx2, val2 = jax.lax.sort((idx, val_s), num_keys=1, dimension=1)
        upd = jax.lax.scatter(
            p, idx2[:, :, None], val2, dn,
            indices_are_sorted=True, unique_indices=True,
            mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)
        return (upd, ac)

    print(f"sorted-uniq scatter         {measure(body_sortuniq, (pos_w, acc0))*1e6:8.0f} us",
          file=sys.stderr)

    # gather with sorted indices
    def body_gsorted(k, carry):
        p, ac = carry
        ac = perturb(k, ac)
        ac_s, inv = jax.lax.sort(
            (ac, jnp.broadcast_to(jnp.arange(TWOE, dtype=jnp.int32),
                                  (S, TWOE))), num_keys=1, dimension=1)
        g = jnp.take_along_axis(p, ac_s, axis=1)
        _, g_back = jax.lax.sort((inv, g), num_keys=1, dimension=1)
        return (p + g_back.sum() * 0, (ac + g_back[:, :TWOE].astype(jnp.int32)) % A)

    print(f"gather via sorted idx       {measure(body_gsorted, (pos, acc0))*1e6:8.0f} us",
          file=sys.stderr)

    # plain gather baseline
    def body_g(k, carry):
        p, ac = carry
        ac = perturb(k, ac)
        g = jnp.take_along_axis(p, ac, axis=1)
        return (p + g.sum() * 0, (ac + g[:, :TWOE].astype(jnp.int32)) % A)

    print(f"gather dup baseline         {measure(body_g, (pos, acc0))*1e6:8.0f} us",
          file=sys.stderr)


if __name__ == "__main__":
    main()
