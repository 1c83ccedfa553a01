"""Shared k-clustering machinery (reference ``heat/cluster/_kcluster.py``).

The reference's per-centroid Bcast initialization and cdist/argmin
assignment (``_kcluster.py:101-196``) become jitted global programs: one
``jax.random.choice`` for random init, an iterative D²-sampling loop for
kmeans++ (``probability_based``), and a fused distance+argmin kernel for
assignment — all sharded over the data axis, reductions psum'd on ICI.
"""
from __future__ import annotations

from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core import random as ht_random
from ..core import _hooks, types
from ..core._cache import ExecutableCache
from ..core.base import BaseEstimator, ClusteringMixin
from ..core.dndarray import DNDarray
from ..spatial.distance import _quadratic_expand

__all__ = ["_KCluster", "_whole_fit", "_block_fit"]

# jitted bounded-chunk fit programs, keyed (estimator kind, k): supervised
# fits re-dispatch the same executable every chunk, so a long fit costs one
# trace regardless of how many checkpoint boundaries it crosses
_BLOCK_PROGRAMS = ExecutableCache(maxsize=32)


def _whole_fit(step_fn: Callable, xa: jnp.ndarray, centers: jnp.ndarray, max_iter, tol):
    """Shared whole-fit harness: ``lax.while_loop`` over fused iterations
    with the shift test ON DEVICE, so a full fit is a single dispatch
    (per-iteration host fetches would put a device→host sync under every
    step). ``step_fn(xa, centers) -> (centers, labels,
    shift)``; runs while ``i < max_iter and shift > tol``. Returns
    ``(centers, labels, n_iter)``. Callers jit this (closing over their
    step) — KMedians/KMedoids here; KMeans keeps its specialized variant
    (extra valid-count masking state) with the same discipline.
    """

    def cond(state):
        i, _, _, shift = state
        return jnp.logical_and(i < max_iter, shift > tol)

    def body(state):
        i, c, _, _ = state
        nc, labels, shift = step_fn(xa, c)
        return (i + 1, nc, labels, shift)

    n = xa.shape[0]
    state0 = (
        jnp.int32(0),
        centers,
        jnp.zeros((n,), jnp.int64 if jax.config.jax_enable_x64 else jnp.int32),
        jnp.asarray(jnp.inf, centers.dtype),
    )
    i, c, labels, _ = jax.lax.while_loop(cond, body, state0)
    return c, labels, i


def _block_fit(step_fn, xa: jnp.ndarray, centers: jnp.ndarray, budget, tol, shift0):
    """One bounded chunk of the :func:`_whole_fit` loop: up to ``budget``
    fused iterations, stopping early once ``shift <= tol``. The shift is
    carried ACROSS chunks (``shift0`` seeds it with the previous chunk's
    final value), so a chain of chunks executes exactly the iteration
    sequence of one long while-loop — which is what makes a supervised fit
    checkpointable at chunk boundaries without changing the math. Returns
    ``(centers, labels, iters_done, shift)``.
    """

    def cond(state):
        i, _, _, shift = state
        return jnp.logical_and(i < budget, shift > tol)

    def body(state):
        i, c, _, _ = state
        nc, labels, shift = step_fn(xa, c)
        return (i + 1, nc, labels, shift)

    n = xa.shape[0]
    state0 = (
        jnp.int32(0),
        centers,
        jnp.zeros((n,), jnp.int64 if jax.config.jax_enable_x64 else jnp.int32),
        shift0,
    )
    i, c, labels, shift = jax.lax.while_loop(cond, body, state0)
    return c, labels, i, shift


def _wrap_labels(labels: jnp.ndarray, x: DNDarray) -> DNDarray:
    """Labels array -> DNDarray on ``x``'s mesh (padded-buffer aware)."""
    labels = labels.astype(jnp.int64)
    n = x.gshape[0]
    if x.split is not None and labels.shape[0] != n:
        # buffer tail padding produced dead labels past n
        return DNDarray._from_buffer(labels, (n,), types.int64, 0, x.device, x.comm)
    return DNDarray(
        labels[:n], dtype=types.int64, split=x.split, device=x.device, comm=x.comm
    )


class _KCluster(BaseEstimator, ClusteringMixin):
    """Base class for KMeans/KMedians/KMedoids (reference ``_kcluster.py:10``).

    Parameters
    ----------
    metric : callable
        Tile metric used for assignment, (n, f) x (k, f) -> (n, k).
    n_clusters, init, max_iter, tol, random_state : see reference.
    """

    def __init__(self, metric: Callable, n_clusters: int, init, max_iter: int, tol: float, random_state: Optional[int]):
        self.n_clusters = n_clusters
        self.init = init
        self.max_iter = max_iter
        self.tol = tol
        self.random_state = random_state

        self._metric = metric
        self._cluster_centers = None
        self._labels = None
        self._inertia = None
        self._n_iter = None

    @property
    def cluster_centers_(self) -> DNDarray:
        return self._cluster_centers

    @property
    def labels_(self) -> DNDarray:
        return self._labels

    @property
    def inertia_(self) -> float:
        return self._inertia

    @property
    def n_iter_(self) -> int:
        return self._n_iter

    def _initialize_cluster_centers(self, x: DNDarray) -> jnp.ndarray:
        """Pick initial centroids (reference ``_kcluster.py:87-187``).

        'random' samples k rows; 'probability_based'/'kmeans++' performs
        D²-weighted sampling. Either way the centroids end replicated, the
        analogue of the reference's Bcast.
        """
        k = self.n_clusters
        xa = x.larray
        n = x.gshape[0]  # logical sample count; the buffer may carry padding
        if k > n:
            raise ValueError(f"n_clusters ({k}) cannot exceed the number of samples ({n})")
        if isinstance(self.init, DNDarray):
            if self.init.shape != (k, x.shape[1]):
                raise ValueError(f"passed centroids have wrong shape {self.init.shape}")
            # logical view: a split init's buffer may carry pad rows, which
            # would otherwise enter the fit as phantom centroids
            return self.init._logical().astype(xa.dtype)
        if self.random_state is not None:
            ht_random.seed(self.random_state)
        if self.init == "random":
            key = ht_random._next_key(k)
            idx = jax.random.choice(key, n, shape=(k,), replace=False)
            return jnp.take(xa, idx, axis=0)
        if self.init in ("probability_based", "kmeans++", "k-means++"):
            key = ht_random._next_key(k * n)

            first = jax.random.randint(jax.random.fold_in(key, 0), (), 0, n)
            centers = jnp.zeros((k, xa.shape[1]), dtype=xa.dtype)
            centers = centers.at[0].set(xa[first])
            # D^2 over the logical rows only (drop any buffer tail padding)
            d2 = _quadratic_expand(xa, centers[:1]).ravel()[:n]
            for i in range(1, k):
                probs = d2 / jnp.sum(d2)
                nxt = jax.random.choice(jax.random.fold_in(key, i), n, p=probs)
                centers = centers.at[i].set(xa[nxt])
                d2 = jnp.minimum(d2, _quadratic_expand(xa, centers[i : i + 1]).ravel()[:n])
            return centers
        raise ValueError(f"Initialization method {self.init!r} not supported")

    # ----------------------------------------------------- supervised fit
    def _prep_fit(self, x: DNDarray) -> jnp.ndarray:
        """The fit-time device view of ``x`` (KMeans overrides: it keeps
        the padded buffer and masks with a valid count instead)."""
        return x._logical().astype(jnp.promote_types(x.larray.dtype, jnp.float32))

    def _supervised_step(self, xa, centers, budget, tol, shift0, x):
        """Run one bounded chunk of this estimator's fit loop; returns
        ``(centers, labels, iters_done, shift)`` as device arrays."""
        raise NotImplementedError

    def _finalize_supervised(self, result) -> None:
        """Post-fit hook (KMeans computes inertia on the final mesh)."""

    def _fit_supervised(self, x: DNDarray, supervisor, block_iters: int, label: str):
        """Drive the fit as a supervised step loop: each step is one
        jitted chunk of up to ``block_iters`` iterations, and the chunk
        boundary is where the supervisor checkpoints, detects divergence,
        and recovers. Chained chunks carry (centers, shift) so the math is
        the iteration-for-iteration sequence of the unsupervised fused
        fit; a fit that loses a device mid-way finishes on the shrunken
        mesh with equivalent results.
        """
        if block_iters < 1:
            raise ValueError(f"block_iters must be >= 1, got {block_iters}")
        tol = -1.0 if self.tol is None else float(self.tol)
        max_iter = self.max_iter
        xa0 = self._prep_fit(x)
        centers0 = self._initialize_cluster_centers(x).astype(xa0.dtype)
        state = {
            "centers": DNDarray(centers0, split=None, device=x.device, comm=x.comm),
            "labels": _wrap_labels(jnp.zeros((xa0.shape[0],), jnp.int32), x),
            "shift": float("inf"),
            "n_iter": 0,
        }

        def step_fn(st, data, step):
            xd = data[0]
            xa = self._prep_fit(xd)
            centers = st["centers"].larray.astype(xa.dtype)
            budget = min(block_iters, max_iter - st["n_iter"])
            c, labels, iters, shift = self._supervised_step(
                xa,
                centers,
                jnp.int32(budget),
                jnp.asarray(tol, xa.dtype),
                jnp.asarray(st["shift"], xa.dtype),
                xd,
            )
            # the one host round-trip per chunk: the convergence decision
            shift_val = float(_hooks.fetch(shift, "kcluster.shift"))
            new = dict(st)
            new["centers"] = DNDarray(c, split=None, device=xd.device, comm=xd.comm)
            new["labels"] = _wrap_labels(labels, xd)
            new["shift"] = shift_val
            new["n_iter"] = st["n_iter"] + int(_hooks.fetch(iters, "kcluster.iters"))
            return new, shift_val <= tol or new["n_iter"] >= max_iter

        result = supervisor.run(step_fn, state, data=(x,), label=label)
        final = result.state
        self._cluster_centers = final["centers"]
        self._labels = final["labels"]
        self._n_iter = int(final["n_iter"])
        self._finalize_supervised(result)
        return self

    # --------------------------------------------------- state round-trip
    def state_dict(self) -> dict:
        """Fitted + hyper state as plain host values (numpy / scalars),
        suitable for a supervisor checkpoint or any serializer."""
        d = {
            "n_clusters": self.n_clusters,
            "max_iter": self.max_iter,
            "tol": self.tol,
            "random_state": self.random_state,
            "n_iter": self._n_iter,
            "inertia": self._inertia,
        }
        if self._cluster_centers is not None:
            d["cluster_centers"] = self._cluster_centers.numpy()
        if self._labels is not None:
            d["labels"] = self._labels.numpy()
            d["labels_split"] = self._labels.split
        return d

    def load_state_dict(self, d: dict, comm=None):
        """Restore :meth:`state_dict` output onto the CURRENT mesh — the
        arrays are rebuilt on ``comm`` (default communicator when None),
        which is what lets a fit resume on a shrunken mesh."""
        self.n_clusters = int(d["n_clusters"])
        self.max_iter = int(d["max_iter"])
        self.tol = d["tol"]
        self.random_state = d["random_state"]
        self._n_iter = d.get("n_iter")
        self._inertia = d.get("inertia")
        cc = d.get("cluster_centers")
        self._cluster_centers = (
            None if cc is None else DNDarray(cc, split=None, comm=comm)
        )
        lab = d.get("labels")
        self._labels = (
            None
            if lab is None
            else DNDarray(
                lab, dtype=types.int64, split=d.get("labels_split"), comm=comm
            )
        )
        return self

    def _assign_to_cluster(self, x: DNDarray) -> DNDarray:
        """Cluster index of every sample (reference ``_kcluster.py:196``)."""
        if self._cluster_centers is None:
            raise RuntimeError("fit needs to be called before predict")
        labels = jnp.argmin(self._metric(x.larray, self._cluster_centers.larray), axis=1)
        labels = labels.astype(jnp.int64)
        n = x.gshape[0]
        if x.split is not None and labels.shape[0] != n:
            # padded buffer rows produced dead labels in the tail
            return DNDarray._from_buffer(labels, (n,), types.int64, 0, x.device, x.comm)
        return DNDarray(
            labels[:n], dtype=types.int64, split=x.split, device=x.device, comm=x.comm
        )

    def predict(self, x: DNDarray) -> DNDarray:
        """Labels for new data (reference ``_kcluster.py``)."""
        if not isinstance(x, DNDarray):
            raise TypeError(f"input needs to be a DNDarray, but was {type(x)}")
        return self._assign_to_cluster(x)
