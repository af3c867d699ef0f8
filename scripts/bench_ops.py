"""Device-time microbenchmarks for candidate hot-op rewrites.

Each candidate is wrapped in a lax.fori_loop of K iterations inside one
jit and only a scalar checksum comes back to the host, so the measurement is
pure device compute: per-iter = (t(K) - t(0)) / K using two calls.
"""

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


def chain(body, init):
    def run(k, x):
        return jax.lax.fori_loop(0, k, body, x)

    fn = jax.jit(run, static_argnums=0)

    def measure():
        out0 = fn(1, init)
        np.asarray(jax.tree.leaves(out0)[0]).sum()
        t0 = time.perf_counter()
        out0 = fn(1, init)
        np.asarray(jax.tree.leaves(out0)[0]).sum()
        t1 = time.perf_counter() - t0
        outk = fn(K + 1, init)
        np.asarray(jax.tree.leaves(outk)[0]).sum()
        t0 = time.perf_counter()
        outk = fn(K + 1, init)
        np.asarray(jax.tree.leaves(outk)[0]).sum()
        tk = time.perf_counter() - t0
        return (tk - t1) / K

    return measure()


def main():
    rng = np.random.default_rng(0)
    key64 = jnp.asarray(rng.integers(0, 1 << 60, (S, N)), jnp.int64)
    m_size = jnp.asarray(rng.integers(1, 100, (S, N)), jnp.int32)
    m_oid = jnp.asarray(rng.integers(1, 1 << 50, (S, N)), jnp.int64)
    m_aid = jnp.asarray(rng.integers(0, A, (S, N)), jnp.int32)
    m_price = jnp.asarray(rng.integers(0, 126, (S, N)), jnp.int32)
    slot_idx = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32), (S, N))

    # A. current design: argsort + payload gathers + inverse-perm gather
    def body_a(_, carry):
        key, sz, oid, aid, price = carry
        order = jnp.argsort(key, axis=1)
        take = lambda a: jnp.take_along_axis(a, order, axis=1)
        sz_s, oid_s, aid_s, price_s = take(sz), take(oid), take(aid), take(price)
        inv = jnp.argsort(order, axis=1)
        back = jnp.take_along_axis(sz_s, inv, axis=1)
        return (key + 1, back, oid_s, aid_s + 1, price_s)

    dt = chain(body_a, (key64, m_size, m_oid, m_aid, m_price))
    print(f"A argsort+6 gathers        {dt*1e6:8.0f} us/iter", file=sys.stderr)

    # B. multi-operand lax.sort + inverse by second sort on slot index
    def body_b(_, carry):
        key, sz, oid, aid, price = carry
        key_s, sz_s, oid_s, aid_s, price_s, idx_s = jax.lax.sort(
            (key, sz, oid, aid, price, slot_idx), num_keys=1)
        new_sz = sz_s - 1
        _, back = jax.lax.sort((idx_s, new_sz), num_keys=1)
        return (key + 1, back, oid_s, aid_s + 1, price_s)

    dt = chain(body_b, (key64, m_size, m_oid, m_aid, m_price))
    print(f"B 2x multi-operand sort    {dt*1e6:8.0f} us/iter", file=sys.stderr)

    posA = jnp.zeros((S, A), jnp.int64)
    acc = jnp.asarray(rng.integers(0, A, (S, 2 * E)), jnp.int32)
    vals = jnp.asarray(rng.integers(0, 9, (S, 2 * E)), jnp.int64)

    # C. current: put_along_axis into (S, A+1) with dup indices
    def body_c(_, carry):
        p, ac = carry
        pad = jnp.concatenate([p, jnp.zeros((S, 1), p.dtype)], axis=1)
        pad = jnp.put_along_axis(pad, ac, vals, axis=1, inplace=False)
        return (pad[:, :A], ac)

    dt = chain(body_c, (posA, acc))
    print(f"C put_along dup (S,A+1)    {dt*1e6:8.0f} us/iter", file=sys.stderr)

    # D. unique-index scatter into (S, A+2E) scrap columns
    j = jnp.arange(2 * E, dtype=jnp.int32)[None, :]
    write = jnp.asarray(rng.random((S, 2 * E)) < 0.4)

    def body_d(_, carry):
        p, ac = carry
        pad = jnp.concatenate([p, jnp.zeros((S, 2 * E), p.dtype)], axis=1)
        idx = jnp.where(write, ac, A + j)
        pad = pad.at[jnp.arange(S)[:, None], idx].set(
            vals, unique_indices=True)
        return (pad[:, :A], ac)

    dt = chain(body_d, (posA, acc))
    print(f"D unique scatter (S,A+2E)  {dt*1e6:8.0f} us/iter", file=sys.stderr)

    # E. one-hot masked rebuild: where over (S, A, 2E) compare
    def body_e(_, carry):
        p, ac = carry
        onehot = ac[:, None, :] == jnp.arange(A, dtype=jnp.int32)[None, :, None]
        onehot = onehot & write[:, None, :]
        hit = jnp.any(onehot, axis=2)
        val = jnp.max(jnp.where(onehot, vals[:, None, :], -(1 << 62)), axis=2)
        return (jnp.where(hit, val, p), ac)

    dt = chain(body_e, (posA, acc))
    print(f"E one-hot where rebuild    {dt*1e6:8.0f} us/iter", file=sys.stderr)

    # F. single-column put_along (S,A) one index per row (the _pa1 form)
    aid1 = jnp.asarray(rng.integers(0, A, (S,)), jnp.int32)
    d1 = jnp.asarray(rng.integers(-5, 5, (S,)), jnp.int64)

    def body_f(_, carry):
        p, a = carry
        p = jnp.put_along_axis(p, a[:, None], d1[:, None], axis=1,
                               inplace=False)
        return (p, a)

    dt = chain(body_f, (posA, aid1))
    print(f"F put_along 1col (S,A)     {dt*1e6:8.0f} us/iter", file=sys.stderr)

    # G. 1-col unique scatter
    def body_g(_, carry):
        p, a = carry
        p = p.at[jnp.arange(S), a].set(d1, unique_indices=True)
        return (p, a)

    dt = chain(body_g, (posA, aid1))
    print(f"G at-set 1col unique       {dt*1e6:8.0f} us/iter", file=sys.stderr)

    # H. balance scatter-add (A,) from (S,) dup indices
    bal = jnp.zeros((A,), jnp.int64)

    def body_h(_, carry):
        b, a = carry
        return (b.at[a].add(d1), a)

    dt = chain(body_h, (bal, aid1))
    print(f"H bal scatter-add (A,)     {dt*1e6:8.0f} us/iter", file=sys.stderr)

    # I. replay reductions (S,2E,2E) masked where+sum
    idx2 = jnp.arange(2 * E, dtype=jnp.int32)
    sgn = vals

    def body_i(_, carry):
        ac, sg = carry
        eq = ac[:, :, None] == ac[:, None, :]
        le = (idx2[:, None] <= idx2[None, :])[None]
        pre = jnp.sum(jnp.where(eq & le, sg[:, :, None], 0), axis=1)
        return (ac + 1, sg + pre)

    dt = chain(body_i, (acc, sgn))
    print(f"I replay eq/le (S,2E,2E)   {dt*1e6:8.0f} us/iter", file=sys.stderr)


if __name__ == "__main__":
    main()
