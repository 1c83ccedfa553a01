"""Spectral clustering (reference ``heat/cluster/spectral.py``).

Pipeline identical to the reference (``spectral.py:103``): similarity
Laplacian -> Lanczos tridiagonalization (distributed matvecs) -> local
eigendecomposition of the small T -> back-projected eigenvectors ->
KMeans on the spectral embedding. Three compiled programs, one a phase:
``ht.phase:laplacian`` (the similarity and L, written once),
``ht.phase:lanczos`` (every read of L) and ``ht.phase:ritz`` (``eigh`` of
T and the back-projection), all at float32 precision.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import _hooks
from ..core.base import BaseEstimator, ClusteringMixin
from ..core.dndarray import DNDarray
from ..core.linalg.solver import _lanczos_arrays
from ..graph.laplacian import Laplacian, PairwiseSimilarity
from .kmeans import KMeans

__all__ = ["Spectral"]


class Spectral(BaseEstimator, ClusteringMixin):
    """reference ``spectral.py:12``

    Parameters follow the reference: gamma (rbf width), metric, laplacian
    mode, threshold/boundary for eNeighbour graphs, n_lanczos iterations,
    assign_labels (only 'kmeans').

    The embedding is clustered by KMeans (``init="probability_based"``, 30
    iterations and no tolerance test unless ``params`` say otherwise).

    Fitted attributes: ``labels_``; ``ritz_values_``, the (m,) Ritz values
    of the last fit's Laplacian in ascending order (m = min(n_lanczos,
    n)), and ``embedding_``, its (n, k) spectral embedding: the Ritz
    vectors of the k smallest, k = max(n_clusters, 2), each up to sign.
    """

    def __init__(
        self,
        n_clusters: Optional[int] = None,
        gamma: float = 1.0,
        metric: str = "rbf",
        laplacian: str = "fully_connected",
        threshold: float = 1.0,
        boundary: str = "upper",
        n_lanczos: int = 300,
        assign_labels: str = "kmeans",
        **params,
    ):
        self.n_clusters = n_clusters
        self.gamma = gamma
        self.metric = metric
        self.laplacian = laplacian
        self.threshold = threshold
        self.boundary = boundary
        self.n_lanczos = n_lanczos
        self.assign_labels = assign_labels

        self._laplacian = Laplacian(
            similarity=PairwiseSimilarity(metric, gamma),
            definition="norm_sym",
            mode=laplacian,
            threshold_key=boundary,
            threshold_value=threshold,
        )
        if assign_labels != "kmeans":
            raise NotImplementedError(f"assign_labels {assign_labels} not supported")
        # as upstream builds it: a fixed 30 Lloyd iterations, no tolerance test. The embedding's
        # entries are about 1/sqrt(n), so KMeans' absolute tol (1e-4 on the centres' shift) would
        # stop at the first assignment at n = 40 000, a wrong one where both starts share a class
        kmeans = {"n_clusters": n_clusters or 8, "init": "probability_based", "max_iter": 30, "tol": None}
        self._cluster = KMeans(**{**kmeans, **params})
        self._labels = None
        self.ritz_values_ = None
        self.embedding_ = None

    @property
    def labels_(self) -> DNDarray:
        return self._labels

    def _spectral_embedding(self, x: DNDarray, k: Optional[int]):
        """Ritz values and the Ritz vectors of the k smallest (all m where k
        is None) of the Laplacian of ``x`` (reference ``spectral.py:103``)."""
        L = self._laplacian.construct(x)
        m = min(self.n_lanczos, L.shape[0])
        with jax.default_matmul_precision("highest"):
            V, T, n = _lanczos_arrays(L, m)
            evals, vectors = _ritz(V, T, m if k is None else k, n)
        return evals, vectors

    def _embedding(self, vectors: jnp.ndarray, x: DNDarray) -> DNDarray:
        return DNDarray(vectors, split=x.split, device=x.device, comm=x.comm)

    @_hooks.public_call("Spectral.fit")
    def fit(self, x: DNDarray) -> "Spectral":
        """reference ``spectral.py``"""
        if not isinstance(x, DNDarray):
            raise TypeError(f"input needs to be a DNDarray, but was {type(x)}")
        k = None if self.n_clusters is None else max(self.n_clusters, 2)
        evals, vectors = self._spectral_embedding(x, k)
        if k is None:
            # eigengap heuristic on sorted eigenvalues
            ev = _hooks.fetch(evals, "spectral.eigengap")
            self.n_clusters = int(np.argmax(np.diff(ev[: min(len(ev), 20)]))) + 1
            self._cluster.n_clusters = max(self.n_clusters, 2)
            k = max(self.n_clusters, 2)
            vectors = vectors[:, :k]
        self.ritz_values_ = DNDarray(evals, split=None, device=x.device, comm=x.comm)
        self.embedding_ = self._embedding(vectors, x)
        self._cluster.fit(self.embedding_)
        self._labels = self._cluster.labels_
        return self

    @_hooks.public_call("Spectral.predict")
    def predict(self, x: DNDarray) -> DNDarray:
        """Recompute the spectral embedding of ``x`` and predict with the
        fitted KMeans (reference ``spectral.py:190-215``)."""
        if self._labels is None:
            raise RuntimeError("fit needs to be called before predict")
        _, vectors = self._spectral_embedding(x, max(self.n_clusters, 2))
        return self._cluster.predict(self._embedding(vectors, x))


@partial(jax.jit, static_argnames=("k", "n"))
def _ritz(V, T, k: int, n: int):
    """Ritz values of T in ascending order, and the Ritz vectors of the k
    smallest back-projected onto the Lanczos basis: ``V^T evecs[:, :k]``
    over the n logical columns of V."""
    with _hooks.phase("ritz"):
        evals, evecs = jnp.linalg.eigh(T)
        return evals, V[:, :n].T @ evecs[:, :k]
