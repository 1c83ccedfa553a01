"""``ht.regression.Lasso(lam, max_iter=<iterations>, tol=0.0).fit(x, y)`` on a resident design matrix.

Upstream Heat's protocol (``benchmarks/lasso``: ``Lasso.fit`` on the EURAD-IM table, 1 iteration,
wall clock of ``fit``), on rows made on the device: column 0 ones (the intercept column the
estimator expects), the others standard normal, a true coefficient N(0, 2^2) on every fourth
column, ``y = x theta_true + 0.1 N(0, 1)``. ``tol=0.0`` makes every call run its sweeps whatever
the data (the program's test is ``diff >= tol``).
"""
from __future__ import annotations

import functools

import numpy as np

from harness.data import on_mesh, prng_key

# The check's one limit: the largest |theta_j - reference_j| over what one bf16 pass over column j's
# dot would leave in theta_j (``reference``'s ``bf16_error``; about 4.6e-6 at the cell's size). It
# says what the configuration's float32 means: at least as close to float64 as a bf16 pass is far.
# It stands at the geometric middle of two readings at (1e7, 108) on the chip (my chip runs, PR 33):
#   the program, float32, 40 seeds: 0.059 to 0.203, median 0.106 (theta off by at most 9.4e-7). Not
#     input rounding: the reference reads the same float32 x and y. A coefficient is rho / ||x_j||^2
#     with rho a float32 sum of 1e7 float32 products, added in the order the chip takes (many
#     lanes, then a tree), each partial sum rounded to 2^-24 of itself in either direction: rho is
#     off by a few 2^-24 of the partial sums' size, far under the worst case of n 2^-24; the
#     division by ||x_j||^2 ~ 1e7 and theta_j's own rounding add 2^-24 of |theta_j| each (alone,
#     they read 0.017);
#   a float64 descent whose every product has both factors rounded to bfloat16 first, the nearest
#     precision below (``tests/test_lasso_check.py::descent``, beside the rehearsal), 3 seeds: 4.83,
#     6.36, 6.73: each product off by up to 2^-9 of itself, 2^15 times float32's rounding, which
#     over 1e7 products of either sign still leaves 25 to 100 times the program's error.
# The four faults a coordinate descent can hide read, at the cell's size, 1 748 (a residual not
# updated), 12 970 (a regularised intercept), 37 573 (a threshold of lam for lam n) and 110 889 (a
# column skipped); at a rehearsal's 4 096 rows 300 to 7 600, a bf16 descent 1.9 to 3.5, the program
# on the CPU 0.02 to 0.03.
LIMIT = 1.0


def build(config: dict, seed: int, comm) -> dict:
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    s = config["sizes"]
    n, m = s["rows"], s["columns"]
    kx, kt, ke = jax.random.split(prng_key(seed), 3)
    theta_true = jnp.where(jnp.arange(m) % 4 == 0, jax.random.normal(kt, (m,), jnp.float32) * 2.0, 0.0)

    def design(kx):
        ones = jax.lax.broadcasted_iota(jnp.int32, (n, m), 1) == 0
        return jnp.where(ones, 1.0, jax.random.normal(kx, (n, m), jnp.float32))

    def labels(x, theta_true, ke):  # every product a float32 one: no matmul, so no bf16 pass
        return jnp.sum(x * theta_true[None, :], axis=1) + 0.1 * jax.random.normal(ke, (n,), jnp.float32)

    xa = on_mesh(comm, (n, m), design, kx)
    ya = on_mesh(comm, (n,), labels, xa, theta_true, ke)
    return {"x": ht.array(xa, split=0), "y": ht.array(ya, split=0), "lam": s["lam"], "iterations": s["iterations"]}


@functools.lru_cache(maxsize=None)
def _public_fit():
    """``Lasso.fit`` inside the span ``ht.call:Lasso.fit``. Where the program has made it a public
    call, that is the method as it stands and the span is the program's. A program from before that
    (``Lasso.fit`` undecorated: no ``__wrapped__``) gets the same span from the program's own
    ``public_call`` here, around the same call, so that the metrics the benchmark reads from
    ``ht.call:*`` spans in every cell (``call_self_ms.call``, ``fetch_ms.call``, ``exchanges.call``,
    ``exchange_ms.call``) have a call to read on both sides of a comparison."""
    import heat_tpu as ht
    from heat_tpu.core import _hooks

    fit = ht.regression.Lasso.fit
    return fit if hasattr(fit, "__wrapped__") else _hooks.public_call("Lasso.fit")(fit)


def call(state: dict) -> dict:
    import heat_tpu as ht

    est = ht.regression.Lasso(lam=state["lam"], max_iter=state["iterations"], tol=0.0)
    _public_fit()(est, state["x"], state["y"])
    return {"theta": est.theta, "n_iter": est.n_iter}


def reference(column, y, lam: float, sweeps: int, columns: int, seen=None):
    """Cyclic coordinate descent from ``theta = 0`` in NumPy float64, by this file alone: for each
    sweep, for each column ``j`` in order, ``rho = x_j . t`` with ``t = r + x_j theta_j`` and the
    residual ``r = y - x theta`` kept up to date, ``theta_j = rho / ||x_j||^2`` for the intercept
    (j = 0, not regularised) and ``sign(rho) max(|rho| - lam n, 0) / ||x_j||^2`` otherwise.
    ``column(j)`` gives one column of x at a time, so x is never whole on the host. Returns
    ``(theta, bf16_error)``: ``bf16_error[j] = 2^-9 sqrt(sum_i (x_ij t_i)^2) / ||x_j||^2`` at the last
    sweep, what one bf16 pass over the column's dot would leave in the coefficient (each product
    off by up to 2^-9 of itself, in either direction, so the sum by the root of the sum of their
    squares). ``seen(j, x_j)``, if given, is called with each column of the first sweep."""
    y = np.asarray(y, np.float64)
    n = y.shape[0]
    theta, bf16_error, r = np.zeros(columns), np.zeros(columns), y.copy()
    for sweep in range(sweeps):
        for j in range(columns):
            xj = np.asarray(column(j), np.float64)
            if seen is not None and sweep == 0:
                seen(j, xj)
            sq = xj @ xj
            if sq == 0.0:
                continue
            products = xj * (r + xj * theta[j] if theta[j] else r)
            rho = products.sum()
            new = rho / sq if j == 0 else np.sign(rho) * max(abs(rho) - lam * n, 0.0) / sq
            bf16_error[j] = 2.0**-9 * np.sqrt(products @ products) / sq
            if new != theta[j]:
                r = r - xj * (new - theta[j])
            theta[j] = new
    return theta, bf16_error


def check(state: dict, result: dict) -> dict:
    """``n_iter``; theta against ``reference`` on the same float32 x and y (a column fetched at a
    time), each coefficient within ``LIMIT`` of what a bf16 pass would leave in it; the objective
    ``(1/2n) ||x theta - y||^2 + lam ||theta[1:]||_1`` at the program's theta below its value at 0."""
    import jax

    n, m = state["x"].shape
    lam, sweeps = state["lam"], state["iterations"]
    got = result["theta"].numpy().astype(np.float64).ravel()
    if got.shape != (m,):
        return {"ok": False, "theta_shape": list(result["theta"].shape)}
    xa = state["x"].larray
    one_column = jax.jit(lambda x, j: jax.lax.dynamic_index_in_dim(x, j, axis=1, keepdims=False))
    y = state["y"].numpy().astype(np.float64)
    fitted = np.zeros(n)

    def seen(j, xj):
        if got[j]:
            np.add(fitted, xj * got[j], out=fitted)

    want, bf16_error = reference(lambda j: np.asarray(one_column(xa, j))[:n], y, lam, sweeps, m, seen)
    err = np.abs(got - want)
    share = float((err / np.maximum(bf16_error, np.finfo(np.float64).tiny)).max())
    at_zero = float(y @ y) / (2 * n)
    after = float((fitted - y) @ (fitted - y)) / (2 * n) + lam * float(np.abs(got[1:]).sum())
    ran = int(result["n_iter"]) == sweeps
    return {"ok": ran and share <= LIMIT and after < at_zero and bool(np.isfinite(got).all()),
            "n_iter": int(result["n_iter"]), "theta_max_abs_err": float(err.max()),
            "err_over_bf16_error": share, "limit": LIMIT, "bf16_error_median": float(np.median(bf16_error)),
            "objective_at_zero": at_zero, "objective_after": after,
            "nonzero": int(np.count_nonzero(got)), "intercept": float(got[0])}


def work(config: dict) -> dict:
    """Least work of one call, whatever the formulation (a Gram one included): x and y read once
    from HBM; four operations a value of x (a product and a sum for the column's dot, a product
    and a sum for the residual's update)."""
    s = config["sizes"]
    return {"flops": 4 * s["rows"] * s["columns"], "bytes": 4 * s["rows"] * s["columns"] + 4 * s["rows"], "kernels": {}}
