"""Plain reference of spectral clustering: upstream Heat's pipeline written out, nothing of the engine.

What the tests (and anyone who doubts a fit) hold :class:`heat_tpu.cluster.Spectral` to.
Straightforward ``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
with no kernel, no fused program, no shard and no import from ``spectral.py``,
``graph/laplacian.py`` or ``linalg/solver.py``; the small eigenproblem and the clustering of
the embedding in NumPy float64 on the host. Four parts, each a function:

* :func:`similarity`: ``A_ij = exp(-gamma ||xi - xj||^2)``, the distances by their differences;
* :func:`laplacian`: ``L = I - D^-1/2 A D^-1/2`` with A's diagonal set to zero (``simple``:
  ``L = D - A``);
* :func:`lanczos`: m steps from the unit vector of ones, a Python loop, every new vector
  re-orthogonalised against all earlier ones, twice;
* :func:`ritz` and :func:`kmeans_labels`: ``eigh`` of T in float64, the embedding
  ``V^T evecs[:, :k]``, and Lloyd's algorithm on it; :func:`same_partition` compares two
  labellings up to a permutation of the labels.

Departures from upstream Heat (``heat/cluster/spectral.py``, ``heat/core/linalg/solver.py``):

* the squared distance is the sum of squared differences, where upstream's ``rbf`` may take the
  expansion ``|x|^2 + |y|^2 - 2 x.y``: the same quantity, without the expansion's cancellation;
* upstream re-orthogonalises a Lanczos vector only when it has lost orthogonality; here every
  vector is, twice, which changes nothing a float64 run would show and keeps the float32 basis
  orthogonal to rounding;
* upstream decomposes T in T's own precision (float32 here) and sorts; here ``numpy.linalg.eigh``
  in float64, ascending;
* upstream clusters the embedding with its own KMeans from a random ``probability_based``
  start; here Lloyd's algorithm from the farthest-point start, deterministic, and only the
  partition is compared, not the labels' names.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["similarity", "laplacian", "lanczos", "ritz", "kmeans_labels", "same_partition", "spectral"]


def similarity(x, gamma: float) -> jnp.ndarray:
    """``exp(-gamma ||xi - xj||^2)`` of every pair of rows, in float32."""
    x = jnp.asarray(x, jnp.float32)
    diff = x[:, None, :] - x[None, :, :]
    return jnp.exp(-gamma * jnp.sum(diff * diff, axis=-1))


def laplacian(A, definition: str = "norm_sym") -> jnp.ndarray:
    """``I - D^-1/2 A D^-1/2`` (``norm_sym``) or ``D - A`` (``simple``) of A with its diagonal
    set to zero, D the diagonal of A's row sums."""
    A = jnp.asarray(A, jnp.float32)
    A = A - jnp.diag(jnp.diag(A))
    d = jnp.sum(A, axis=1)
    if definition == "simple":
        return jnp.diag(d) - A
    s = 1.0 / jnp.sqrt(d)
    return jnp.eye(A.shape[0], dtype=A.dtype) - s[:, None] * A * s[None, :]


def lanczos(matvec, n: int, m: int):
    """``(alphas, betas, V)``: m Lanczos steps of the symmetric operator ``matvec`` from
    ``ones(n) / sqrt(n)``, in float32 at ``"highest"``. ``V`` is (m, n), one vector a row;
    ``betas[i]`` couples vectors ``i - 1`` and ``i`` (``betas[0]`` is 0)."""
    with jax.default_matmul_precision("highest"):
        v = jnp.ones(n, jnp.float32) / float(np.sqrt(n))
        V = [v]
        alphas, betas = [], [0.0]
        w = matvec(v)
        for i in range(m):
            alpha = jnp.dot(w, v)
            alphas.append(float(alpha))
            if i == m - 1:
                break
            w = w - alpha * v - (betas[-1] * V[-2] if i else 0.0)
            basis = jnp.stack(V)
            for _ in range(2):
                w = w - basis.T @ (basis @ w)
            beta = jnp.linalg.norm(w)
            betas.append(float(beta))
            v = w / beta
            V.append(v)
            w = matvec(v)
    return np.array(alphas), np.array(betas), jnp.stack(V)  # graftlint: host-sync - host NumPy by design


def ritz(alphas, betas, V, k: int):
    """``(values, embedding)``: every eigenvalue of the tridiagonal T of ``alphas`` and
    ``betas[1:]``, ascending, from ``numpy.linalg.eigh`` in float64, and the (n, k) Ritz
    vectors of the k smallest, ``V^T evecs[:, :k]``, in float64."""
    # graftlint: host-sync - the small eigenproblem is host NumPy float64 by design
    T = np.diag(np.asarray(alphas, np.float64))
    off = np.asarray(betas[1:], np.float64)  # graftlint: host-sync - host NumPy by design
    T += np.diag(off, 1) + np.diag(off, -1)
    values, vectors = np.linalg.eigh(T)
    return values, np.asarray(V, np.float64).T @ vectors[:, :k]  # graftlint: host-sync - host NumPy by design


def kmeans_labels(points, k: int, iterations: int = 100) -> np.ndarray:
    """Lloyd's algorithm in float64 from the farthest-point start: the first centre is row 0,
    each next one the row farthest from the centres so far."""
    points = np.asarray(points, np.float64)  # graftlint: host-sync - host NumPy by design
    centres = [points[0]]
    for _ in range(1, k):
        d2 = np.min([np.sum((points - c) ** 2, axis=1) for c in centres], axis=0)
        centres.append(points[int(np.argmax(d2))])
    centres = np.array(centres)  # graftlint: host-sync - host NumPy by design
    labels = None
    for _ in range(iterations):
        d2 = ((points[:, None, :] - centres[None, :, :]) ** 2).sum(-1)
        new = np.argmin(d2, axis=1)
        if labels is not None and np.array_equal(new, labels):
            break
        labels = new
        # graftlint: host-sync - host NumPy by design
        centres = np.array([points[labels == c].mean(0) if np.any(labels == c) else centres[c] for c in range(k)])
    return labels


def same_partition(a, b) -> bool:
    """Whether two labellings split the rows alike, whatever the labels' names."""
    a, b = np.asarray(a).ravel(), np.asarray(b).ravel()  # graftlint: host-sync - host NumPy by design
    if a.shape != b.shape:
        return False
    pairs = set(zip(a.tolist(), b.tolist()))
    return len(pairs) == len(set(a.tolist())) == len(set(b.tolist()))


def spectral(x, gamma: float, m: int, k: int) -> dict:
    """The whole pipeline on the rows of ``x``: ``L``, the Ritz ``values``, the (n, k)
    ``embedding`` and the ``labels`` of Lloyd's algorithm on it."""
    L = laplacian(similarity(x, gamma))
    n = L.shape[0]
    alphas, betas, V = lanczos(lambda v: L @ v, n, min(m, n))
    values, embedding = ritz(alphas, betas, V, k)
    return {"L": L, "values": values, "embedding": embedding, "labels": kmeans_labels(embedding, k)}
