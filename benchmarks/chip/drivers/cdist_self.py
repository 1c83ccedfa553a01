"""``ht.spatial.cdist(x, quadratic_expansion=True)``: all pairwise distances of one array's rows.

Upstream Heat's ``benchmarks/distance_matrix`` protocol on standard-normal rows.
"""
from __future__ import annotations

import numpy as np

from harness.data import on_mesh, prng_key

SAMPLED_ROWS = 64


def build(config: dict, seed: int, comm) -> dict:
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    n, f = config["sizes"]["rows"], config["sizes"]["features"]
    xa = on_mesh(comm, (n, f), lambda k: jax.random.normal(k, (n, f), jnp.float32), prng_key(seed))
    return {"x": ht.array(xa, split=0)}


def call(state: dict):
    import heat_tpu as ht

    return ht.spatial.cdist(state["x"], quadratic_expansion=True)


def check(state: dict, d) -> dict:
    import jax.numpy as jnp

    n = state["x"].shape[0]
    if tuple(d.shape) != (n, n):
        return {"ok": False, "shape": list(d.shape)}
    rows = np.random.default_rng(0).choice(n, min(SAMPLED_ROWS, n), replace=False)
    got = np.asarray(jnp.take(d.larray, jnp.asarray(rows, jnp.int32), axis=0))[:, :n]
    xh = state["x"].numpy().astype(np.float64)
    want2 = ((xh[rows, None, :] - xh[None, :, :]) ** 2).sum(-1)
    # From chip_smoke.py: the error lives in d^2, in the cross term 2·x·y of XLA's one bf16 pass
    # over an f32 dot. Each factor rounded to 8 bits moves a product by at most 2^-8 of itself
    # (2^-7 were it truncated), so d^2 by 2·2^-7·|x|·|y| summed over the features, whatever the
    # data; 1e-3 covers the f32 rest. On the diagonal d^2 is that error alone, and d its root.
    err2 = np.abs(got.astype(np.float64) ** 2 - want2)
    bound = 2.0**-6 * (np.abs(xh[rows]) @ np.abs(xh).T) + 1e-3
    return {"ok": bool((err2 <= bound).all()), "max_abs_err_d2": float(err2.max()),
            "max_err_over_bound": float((err2 / bound).max()), "sampled_rows": len(rows)}


def work(config: dict) -> dict:
    """Least work of one call: write the n x n f32 result once and read x; 2f operations for a
    pair's cross term, and three more to add the norms and take the root."""
    n, f = config["sizes"]["rows"], config["sizes"]["features"]
    return {"flops": n * n * (2 * f + 3), "bytes": 4 * n * n + 4 * n * f, "kernels": {}}
