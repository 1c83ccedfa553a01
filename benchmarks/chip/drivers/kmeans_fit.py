"""``ht.cluster.KMeans(k, init=<given>, max_iter=<iterations>, tol=None).fit(x)`` on blobs.

Upstream Heat's protocol (``benchmarks/kmeans``: k = 8, 30 iterations, wall
clock of ``fit``), on rows made on the device: ``k`` centres ``blob_sigma_apart``
sigma apart, unit-variance rows round-robin around them, and an initial guess
one sigma off the true centres, so that every call runs the same iterations.
"""
from __future__ import annotations

from functools import partial

import numpy as np

from harness.data import on_mesh, prng_key


def make_blobs(comm, key, n: int, f: int, k: int, apart: float):
    """(x, centers): ``n`` rows around ``k`` centres ``apart`` sigma apart, made on the mesh."""
    import jax
    import jax.numpy as jnp

    kc, kx = jax.random.split(key)
    centers = jax.random.normal(kc, (k, f), jnp.float32) * apart

    def gen(kx, centers):
        lab = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) % k
        pick = (lab == jnp.arange(k, dtype=jnp.int32)[None, :]).astype(jnp.float32)
        return jax.random.normal(kx, (n, f), jnp.float32) + pick @ centers

    return on_mesh(comm, (n, f), gen, kx, centers), centers


def build(config: dict, seed: int, comm) -> dict:
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    s = config["sizes"]
    n, f, k = s["rows"], s["features"], s["clusters"]
    kb, ki = jax.random.split(prng_key(seed))
    xa, centers = make_blobs(comm, kb, n, f, k, s["blob_sigma_apart"])
    init = ht.array(centers + jax.random.normal(ki, (k, f), jnp.float32))
    return {"x": ht.array(xa, split=0), "init": init, "clusters": k, "iterations": s["iterations"]}


def _fit(state: dict, iterations: int, init):
    import heat_tpu as ht

    return ht.cluster.KMeans(n_clusters=state["clusters"], init=init, max_iter=iterations, tol=None).fit(state["x"])


def call(state: dict) -> dict:
    m = _fit(state, state["iterations"], state["init"])
    return {"centers": m.cluster_centers_, "labels": m.labels_, "n_iter": m.n_iter_, "inertia": m.inertia_}


# ------------------------------------------------------------------ reference
# The chip keeps a tall (n, f) array with n minor, so the reference works on x.T (a free view
# there) in column blocks: every temporary is (k, block) or (f, block), and nothing is padded from
# f or k to the 128 lanes (a (block, f) view of the whole array was refused at 16 GiB).
def _block_rows(n: int) -> int:
    b = 1 << 18
    while n % b:
        b >>= 1
    return b


def _assign(xt, c):
    """argmin over the centres of the squared distances, for the rows that are ``xt``'s columns."""
    import jax.numpy as jnp

    d2 = jnp.sum(c * c, 1)[:, None] + jnp.sum(xt * xt, 0)[None, :] - 2.0 * (c @ xt)
    return jnp.argmin(jnp.maximum(d2, 0.0), axis=0)


def _reference_lloyd(x, c0, iterations: int, block: int):
    """Plain Lloyd in ``jax.numpy`` at ``"highest"``, over row blocks. A centre moves by the
    mean of its rows' offsets from it: the same mean as sum/count, and the f32 sums stay small."""
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnames=("iterations", "block"))
    def run(x, c0, iterations, block):
        n, f = x.shape
        k = c0.shape[0]
        xt = x.T

        def step(c, _):
            def fold(acc, i):
                rows = jax.lax.dynamic_slice_in_dim(xt, i * block, block, axis=1)  # (f, block)
                pick = (jnp.arange(k)[:, None] == _assign(rows, c)[None, :]).astype(jnp.float32)  # (k, block)
                return (acc[0] + pick @ (rows - c.T @ pick).T, acc[1] + pick.sum(1)), None

            zero = (jnp.zeros((k, f), jnp.float32), jnp.zeros((k,), jnp.float32))
            (offsets, count), _ = jax.lax.scan(fold, zero, jnp.arange(n // block))
            return c + offsets / count[:, None], None

        with jax.default_matmul_precision("highest"):
            return jax.lax.scan(step, c0, None, length=iterations)[0]

    return run(x, c0, iterations, block)


def _labels_differing(x, c, labels, block: int):
    """How many of ``labels`` are not ``argmin`` of the distances to ``c`` at ``"highest"``."""
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnames=("block",))
    def run(x, c, labels, block):
        xt = x.T

        def fold(acc, i):
            rows = jax.lax.dynamic_slice_in_dim(xt, i * block, block, axis=1)
            mine = jax.lax.dynamic_slice_in_dim(labels, i * block, block)
            return acc + jnp.sum(_assign(rows, c) != mine, dtype=jnp.int32), None

        with jax.default_matmul_precision("highest"):
            return jax.lax.scan(fold, jnp.int32(0), jnp.arange(x.shape[0] // block))[0]

    return run(x, c, labels, block)


def check(state: dict, result: dict) -> dict:
    n = state["x"].shape[0]
    xa = state["x"].larray[:n]
    block = _block_rows(n)
    got = result["centers"].numpy()
    want = np.asarray(_reference_lloyd(xa, state["init"].larray, state["iterations"], block))
    err = float(np.abs(got - want).max())
    # The kernel's products are full f32 and each centre is an f32 sum of n/k rows (4 million at the
    # real size) of |x| up to ~40: PR 22 read 4e-5 against f64 on the chip. 1e-3 (+1e-3 relative, as
    # chip_smoke.py holds the kernel to its fallback) is far under what a bf16 accumulation or a
    # skipped iteration gives; the blobs are 8 sigma apart, so no label hangs on rounding.
    centers_ok = bool((np.abs(got - want) <= 1e-3 + 1e-3 * np.abs(want)).all())
    # one iteration from the fitted centres: its labels are the kernel's assignment against exactly
    # those centres, so they are argmin at "highest" up to summation order at exact near-ties
    one = _fit(state, 1, result["centers"])
    moved = int(_labels_differing(xa, result["centers"].larray, one.labels_.larray[:n], block))
    labels_ok = moved <= n // 1_000_000
    ran = int(result["n_iter"]) == state["iterations"]
    return {"ok": centers_ok and labels_ok and ran and bool(np.isfinite(result["inertia"])),
            "centers_max_abs_err": err, "labels_differing": moved, "n_iter": int(result["n_iter"]),
            "labels_dtype": str(result["labels"].larray.dtype)}


def work(config: dict) -> dict:
    """Least work of one call: each iteration reads x once (HBM) and takes 2k+1 operations a
    value: the k distances and the sum into its centre. The kernel's share is one iteration."""
    s = config["sizes"]
    values = s["rows"] * s["features"]
    one = {"flops": values * (2 * s["clusters"] + 1), "bytes": values * 4}
    return {"flops": one["flops"] * s["iterations"], "bytes": one["bytes"] * s["iterations"],
            "kernels": {name: one for name in config["kernels"]},
            "kernel_events_per_call": {name: s["iterations"] for name in config["kernels"]}}
