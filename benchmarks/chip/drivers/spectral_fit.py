"""``ht.cluster.Spectral(n_clusters=2, gamma=1.0, n_lanczos=300, random_state=<seed>).fit(x)`` on a resident table.

Upstream Heat's spectral clustering at its defaults (rbf similarity with gamma 1, a fully connected
graph, the ``norm_sym`` Laplacian, 300 Lanczos steps, KMeans on the embedding) on the shape of
upstream's distance-matrix benchmark, SUSY's 40 000 x 18. Rows made on the device: two Gaussian
classes, a row in class 1 with probability ``class_share``, every feature N(0, spread^2) about its
class's mean, the two means ``apart`` from each other along a unit direction drawn from the seed.
With equal shares (and so equal degrees) the Fiedler vector would be orthogonal to Lanczos' start,
the vector of ones; the unequal shares keep it in the Krylov space.
"""
from __future__ import annotations

import functools

import numpy as np

from harness.data import on_mesh, prng_key

# The check's limits. Each stands at the geometric middle of two readings at the cell's size on a TPU
# v5e (14 seeds): the program's largest error over its seeds,
# and what this file's reference leaves, against itself in float32, when one of its two float32
# stages runs in bfloat16, the precision below the configuration's (``reference(..., bf16=...)``): a
# one-pass bf16 cross term in the similarity, as ``|x|^2 + |y|^2 - 2 x.y`` at default precision
# computes it on a TPU, or L and the Lanczos vector rounded to bf16 at every matvec. Each fault fails
# one limit, not both:
#   the 2 smallest Ritz values, absolute (L's spectrum lies in [0, 2]): the program 0.47e-7 to
#     3.37e-7; a bf16 matvec 2.82e-6 to 3.14e-6 (a bf16 similarity 3.1e-7 to 3.6e-6, not told apart);
#   each embedding column, up to its sign, its largest difference over its largest entry: the program
#     2.8e-6 to 4.99e-5; a bf16 similarity 2.31e-3 to 2.77e-3 (a bf16 matvec 4.5e-5 to 4.8e-5, not
#     told apart).
RITZ_LIMIT = 9.7e-7
EMBEDDING_LIMIT = 3.4e-4
# The reference's own gap between its third and second Ritz values: the Fiedler vector is an
# eigenvector to compare only where its eigenvalue stands apart from the rest (about 0.07 against
# 0.93 at the configuration's spread and separation).
GAP_MIN = 0.5
BLOCK = 4000  # rows of L_ref a block: 640 MB at 40 000 columns


def build(config: dict, seed: int, comm) -> dict:
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    s = config["sizes"]
    n, f = s["rows"], s["features"]
    ku, kx = jax.random.split(prng_key(seed))
    u = jax.random.normal(ku, (f,), jnp.float32)
    u = u / jnp.linalg.norm(u)

    def rows(kx, u):
        kc, kn = jax.random.split(kx)
        cls = (jax.random.uniform(kc, (n, 1)) < s["class_share"]).astype(jnp.float32)
        return jax.random.normal(kn, (n, f), jnp.float32) * s["spread"] + (cls - 0.5) * s["apart"] * u[None, :]

    xa = on_mesh(comm, (n, f), rows, kx, u)
    return {"x": ht.array(xa, split=0), "xa": xa, "random_state": seed, "clusters": s["clusters"],
            "gamma": s["gamma"], "n_lanczos": s["n_lanczos"]}


@functools.lru_cache(maxsize=None)
def _public_fit():
    """``Spectral.fit`` inside the span ``ht.call:Spectral.fit``: the method as it stands where the
    program made it a public call, else wrapped here in the program's own ``public_call``, so that
    the metrics read from ``ht.call:*`` spans have a call to read on both sides of a comparison."""
    import heat_tpu as ht
    from heat_tpu.core import _hooks

    fit = ht.cluster.Spectral.fit
    return fit if hasattr(fit, "__wrapped__") else _hooks.public_call("Spectral.fit")(fit)


def call(state: dict) -> dict:
    import heat_tpu as ht

    est = ht.cluster.Spectral(n_clusters=state["clusters"], gamma=state["gamma"], n_lanczos=state["n_lanczos"],
                              random_state=state["random_state"])
    _public_fit()(est, state["x"])
    return {"labels": est.labels_, "ritz": getattr(est, "ritz_values_", None),
            "embedding": getattr(est, "embedding_", None)}


# ------------------------------------------------------------------ reference
def _weights(xb, x, start, gamma: float, bf16: bool):
    """Rows ``start ...`` of the adjacency: ``exp(-gamma ||xi - xj||^2)``, the diagonal zero. The
    squared distance by its differences in float32, or, with ``bf16``, by the expansion with a
    one-pass bf16 cross term."""
    import jax
    import jax.numpy as jnp

    if bf16:
        cross = jnp.dot(xb.astype(jnp.bfloat16), x.astype(jnp.bfloat16).T, preferred_element_type=jnp.float32)
        d2 = jnp.maximum(jnp.sum(xb * xb, 1)[:, None] + jnp.sum(x * x, 1)[None, :] - 2.0 * cross, 0.0)
    else:  # a feature at a time: no (rows, n, f) array
        d2 = sum((xb[:, c][:, None] - x[:, c][None, :]) ** 2 for c in range(x.shape[1]))
    i = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0) + start
    j = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    return jnp.where(i == j, 0.0, jnp.exp(-gamma * d2))


def _laplacian_blocks(x, gamma: float, bf16: bool) -> list:
    """``L = I - D^-1/2 A D^-1/2`` as row blocks of ``BLOCK`` rows on the device: the degrees from
    one pass over the blocks of A, then each block of L from its block of A made again, so that
    nothing but L's own blocks is whole."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    starts = list(range(0, n, BLOCK))
    row_sums = jax.jit(lambda xb, x, s: jnp.sum(_weights(xb, x, s, gamma, bf16), axis=1))
    d = jnp.concatenate([row_sums(x[s:s + BLOCK], x, s) for s in starts])
    r = 1.0 / jnp.sqrt(d)

    @jax.jit
    def block(xb, x, s, rb, r):
        A = _weights(xb, x, s, gamma, bf16)
        i = jax.lax.broadcasted_iota(jnp.int32, A.shape, 0) + s
        j = jax.lax.broadcasted_iota(jnp.int32, A.shape, 1)
        return jnp.where(i == j, 1.0, -(rb[:, None] * A * r[None, :]))

    return [block(x[s:s + BLOCK], x, s, r[s:s + BLOCK], r) for s in starts]


def reference(x, gamma: float, m: int, k: int, bf16: str | None = None) -> dict:
    """Upstream's pipeline in plain ``jax.numpy`` float32 at ``"highest"``, by this file alone:
    L in row blocks; m Lanczos steps from ``ones / sqrt(n)``, a Python loop, each new vector
    re-orthogonalised twice against all earlier ones; ``eigh`` of T in NumPy float64, ascending;
    the embedding ``V^T evecs[:, :k]`` in float64; Lloyd's algorithm on it in float64 from the
    farthest-point start. ``bf16`` ``"similarity"`` or ``"matvec"`` runs that stage one precision
    lower (the module's comment above ``RITZ_LIMIT``). Returns ``values``, ``embedding``, ``labels``."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    with jax.default_matmul_precision("highest"):
        blocks = _laplacian_blocks(x, gamma, bf16 == "similarity")
        if bf16 == "matvec":
            blocks = [b.astype(jnp.bfloat16) for b in blocks]
            matvec = jax.jit(lambda bs, v: jnp.concatenate(
                [jnp.dot(b, v.astype(jnp.bfloat16), preferred_element_type=jnp.float32) for b in bs]))
        else:
            matvec = jax.jit(lambda bs, v: jnp.concatenate([b @ v for b in bs]))
        put = jax.jit(lambda V, i, v: V.at[i].set(v), donate_argnums=0)
        V = jnp.zeros((m, n), jnp.float32)
        v = jnp.ones(n, jnp.float32) / float(np.sqrt(n))
        V = put(V, 0, v)
        alphas, betas = [], [0.0]
        w = matvec(blocks, v)
        for i in range(m):
            alpha = jnp.dot(w, v)
            alphas.append(float(alpha))
            if i == m - 1:
                break
            w = w - alpha * v - (betas[-1] * V[i - 1] if i else 0.0)
            for _ in range(2):
                w = w - V.T @ (V @ w)
            beta = jnp.linalg.norm(w)
            betas.append(float(beta))
            v = w / beta
            V = put(V, i + 1, v)
            w = matvec(blocks, v)
        del blocks
        Vh = np.asarray(V, np.float64)
    off = np.asarray(betas[1:], np.float64)
    T = np.diag(np.asarray(alphas, np.float64)) + np.diag(off, 1) + np.diag(off, -1)
    values, vectors = np.linalg.eigh(T)
    embedding = Vh.T @ vectors[:, :k]
    return {"values": values, "embedding": embedding, "labels": _lloyd(embedding, k)}


def _lloyd(points, k: int, iterations: int = 100) -> np.ndarray:
    """Lloyd's algorithm in float64 from the farthest-point start (row 0, then each next centre the
    row farthest from those so far)."""
    centres = [points[0]]
    for _ in range(1, k):
        d2 = np.min([np.sum((points - c) ** 2, axis=1) for c in centres], axis=0)
        centres.append(points[int(np.argmax(d2))])
    centres, labels = np.array(centres), None
    for _ in range(iterations):
        new = np.argmin(((points[:, None, :] - centres[None, :, :]) ** 2).sum(-1), axis=1)
        if labels is not None and np.array_equal(new, labels):
            break
        labels = new
        centres = np.array([points[labels == c].mean(0) if np.any(labels == c) else centres[c] for c in range(k)])
    return labels


def same_partition(a, b) -> bool:
    """Two labellings split the rows alike, whatever the labels' names."""
    a, b = np.asarray(a).ravel(), np.asarray(b).ravel()
    pairs = set(zip(a.tolist(), b.tolist()))
    return a.shape == b.shape and len(pairs) == len(set(a.tolist())) == len(set(b.tolist()))


def errors(got_values, got_embedding, want: dict, k: int) -> dict:
    """The two compared errors: of the k smallest Ritz values, absolute; of each embedding column,
    up to its sign, its largest difference over its largest entry (the worst column)."""
    worst = 0.0
    for c in range(k):
        ref = want["embedding"][:, c]
        diff = min(np.abs(got_embedding[:, c] - s * ref).max() for s in (1.0, -1.0))
        worst = max(worst, float(diff / np.abs(ref).max()))
    return {"ritz_max_abs_err": float(np.abs(got_values[:k] - want["values"][:k]).max()),
            "embedding_max_rel_err": worst}


def check(state: dict, result: dict) -> dict:
    """The reference's gap first; then the k smallest Ritz values within ``RITZ_LIMIT``, each
    embedding column within ``EMBEDDING_LIMIT`` up to its sign, the labels the reference's partition,
    and ``lanczos_steps`` the steps asked for where the program keeps the gauge."""
    import heat_tpu as ht

    k, m = max(state["clusters"], 2), state["n_lanczos"]
    if result["ritz"] is None or result["embedding"] is None:
        return {"ok": False, "missing": "the estimator keeps no ritz_values_ or embedding_"}
    got_values = result["ritz"].numpy().astype(np.float64)
    got_embedding = result["embedding"].numpy().astype(np.float64)
    labels = result["labels"].numpy().ravel()
    want = reference(state["xa"], state["gamma"], m, k)
    gap = float(want["values"][2] - want["values"][1])
    out = {"reference_values": [float(v) for v in want["values"][:3]], "gap": gap, "gap_min": GAP_MIN}
    if gap < GAP_MIN:
        return {"ok": False, **out}
    errs = errors(got_values, got_embedding, want, k)
    steps = getattr(ht, "LAYOUT_STATS", {}).get("lanczos_steps")
    same = same_partition(labels, want["labels"])
    ok = (errs["ritz_max_abs_err"] <= RITZ_LIMIT and errs["embedding_max_rel_err"] <= EMBEDDING_LIMIT and same
          and steps in (None, m) and bool(np.isfinite(got_embedding).all()))
    return {"ok": ok, **out, **errs, "ritz_limit": RITZ_LIMIT, "embedding_limit": EMBEDDING_LIMIT,
            "same_partition": same, "cluster_sizes": np.bincount(labels).tolist(), "lanczos_steps": steps}


def work(config: dict) -> dict:
    """Least work of one call: L written once and read at every Lanczos step (float32, n^2 4 B
    each); 2 n^2 f operations for the squared distances, 2 m n^2 for the matvecs, 2 m^2 n for one
    re-orthogonalisation a step against the vectors so far. One ``_lloyd_call`` event reads the
    (n, k) embedding once."""
    s = config["sizes"]
    n, f, m, k = s["rows"], s["features"], s["n_lanczos"], max(s["clusters"], 2)
    return {"flops": 2 * n * n * f + 2 * m * n * n + 2 * m * m * n, "bytes": 4 * n * n + 4 * m * n * n,
            "kernels": {"_lloyd_call": {"flops": 3 * n * k * k, "bytes": 4 * n * k}}}
