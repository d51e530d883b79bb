"""Microbenchmark: TPU scatter/gather variants for the tick hot path.

Long fori_loop chains (device time >> dispatch noise) with differential
timing: per-op = (t(2N) - t(N)) / N.  Decides the storage layout for the
bucket table (column scatters vs row-block scatters) and whether XLA's
unique/sorted scatter flags earn anything on this chip.
"""

import time

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
from jax import lax

CAP = 1 << 20
B = 1 << 15
N = 400
NCOLS = 20


def timed(run, carry0):
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        out = run(carry0)
        np.asarray(jax.tree.leaves(out)[0].ravel()[:1])
        best = min(best, time.perf_counter() - t0)
    return best


def diff_time(step, carry0, label, per_iter_elems):
    runs = {}
    for k in (N, 2 * N):
        @jax.jit
        def run(c, k=k):
            return lax.fori_loop(0, k, step, c)

        run(carry0)
        runs[k] = timed(run, carry0)
    per = (runs[2 * N] - runs[N]) / N
    print(f"{label:44s} {per * 1e6:9.1f} us/op "
          f"({per_iter_elems / max(per, 1e-12) / 1e6:8.1f} M elem/s)",
          flush=True)
    return per


def main():
    print(f"devices: {jax.devices()}  B={B} CAP={CAP} N={N}", flush=True)
    rng = np.random.default_rng(0)
    idx_rand = jnp.asarray(rng.permutation(CAP)[:B].astype(np.int32))
    idx_sorted = jnp.sort(idx_rand)
    col = jnp.zeros(CAP, jnp.int32)
    vals = jnp.asarray(rng.integers(0, 1 << 30, B).astype(np.int32))

    def mk_scatter(idx, **kw):
        def step(i, c):
            return c.at[idx].set(vals + i.astype(jnp.int32), **kw)

        return step

    diff_time(mk_scatter(idx_rand, mode="drop"), col,
              "scatter col rand drop (current)", B)
    diff_time(mk_scatter(idx_rand, mode="promise_in_bounds",
                         unique_indices=True), col,
              "scatter col rand inbounds+unique", B)
    diff_time(mk_scatter(idx_sorted, mode="drop"), col,
              "scatter col sorted drop", B)
    diff_time(mk_scatter(idx_sorted, mode="promise_in_bounds",
                         unique_indices=True, indices_are_sorted=True), col,
              "scatter col sorted inbounds+uniq+sort", B)

    def mk_gather(idx, **kw):
        def step(i, c):
            g = c.at[idx].get(**kw) if kw else c[idx]
            return c.at[0].set(g[0] + i.astype(jnp.int32))

        return step

    diff_time(mk_gather(idx_rand), col, "gather col rand (current)", B)
    diff_time(mk_gather(idx_sorted, mode="promise_in_bounds",
                        unique_indices=True, indices_are_sorted=True), col,
              "gather col sorted inbounds+uniq+sort", B)

    # --- NCOLS column ops vs one row-block op -------------------------
    cols = tuple(jnp.zeros(CAP, jnp.int32) for _ in range(NCOLS))

    def step_cols(i, cs):
        v = vals + i.astype(jnp.int32)
        return tuple(c.at[idx_rand].set(v, mode="drop") for c in cs)

    diff_time(step_cols, cols, f"{NCOLS}-col scatter rand drop", NCOLS * B)

    tab2d = jnp.zeros((CAP, NCOLS), jnp.int32)
    upd2d = jnp.tile(vals[:, None], (1, NCOLS))

    def step_rows(i, t):
        return t.at[idx_rand].set(upd2d + i.astype(jnp.int32), mode="drop")

    def step_rows_u(i, t):
        return t.at[idx_sorted].set(
            upd2d + i.astype(jnp.int32),
            mode="promise_in_bounds", unique_indices=True,
            indices_are_sorted=True,
        )

    diff_time(step_rows, tab2d, f"row-block scatter rand drop ({NCOLS}w)",
              NCOLS * B)
    diff_time(step_rows_u, tab2d, f"row-block scatter sorted iub+uniq+sort",
              NCOLS * B)

    def step_cols_gather(i, cs):
        gs = [c[idx_rand] for c in cs]
        return tuple(
            c.at[0].set(g[0] + i.astype(jnp.int32)) for c, g in zip(cs, gs)
        )

    diff_time(step_cols_gather, cols, f"{NCOLS}-col gather rand", NCOLS * B)

    def step_rows_gather(i, t):
        g = t[idx_rand]
        return t.at[0, 0].set(g[0, 0] + i.astype(jnp.int32))

    diff_time(step_rows_gather, tab2d, "row-block gather rand", NCOLS * B)

    # --- scatter-add (hit accumulation alternative) -------------------
    diff_time(
        lambda i, c: c.at[idx_rand].add(vals + i.astype(jnp.int32),
                                        mode="drop"),
        col, "scatter-add col rand drop", B)


if __name__ == "__main__":
    main()
