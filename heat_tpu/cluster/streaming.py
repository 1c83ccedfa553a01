"""Streaming K-Means: Lloyd over chunks, single-pass or multi-epoch.

Two algorithms, both driving the SAME fused assignment kernel as the
in-memory :class:`~heat_tpu.cluster.kmeans.KMeans`
(:func:`~heat_tpu.cluster.kmeans._assign_stats` — distance+argmin on the
sharded chunk, one-hot MXU matmul for per-cluster sums, psum over ICI):

- ``algorithm="global"`` (default): each epoch accumulates raw
  sums/counts across ALL chunks with the centers held fixed, then
  applies ONE exact Lloyd update. An epoch is mathematically identical
  to one in-memory Lloyd iteration (partial per-chunk sums re-associate
  the same reduction), so a fit with the same init/max_iter/tol matches
  ``KMeans`` to float32 re-association tolerance — the oracle property
  ``tests/test_stream.py`` asserts. Needs a RE-ITERABLE chunk source
  (e.g. a :class:`~heat_tpu.stream.chunked.ChunkIterator`).
- ``algorithm="minibatch"``: sklearn-style online updates — each chunk
  moves its assigned centers toward the chunk means with per-center
  learning rate ``counts_chunk / counts_total`` (Sculley 2010). One pass
  over the data suffices; :meth:`partial_fit` exposes single-chunk steps
  for open-ended streams.

Compile-once discipline: one jitted per-chunk program per (algorithm,
k) in the bounded ``_BLOCK_PROGRAMS`` cache; a warm chunk loop is
0 traces / 0 compiles per chunk.
"""
from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp

from ..core.communication import collective_lockstep
from ..core.dndarray import DNDarray
from ..spatial.distance import _quadratic_expand
from ._kcluster import _BLOCK_PROGRAMS, _KCluster
from ..stream.prefetch import Prefetcher
from .kmeans import _assign_choice, _assign_stats_dispatch

__all__ = ["StreamingKMeans"]


def _accum_program(k: int, mode: str = "fallback", mesh=None):
    """Cached per-chunk accumulator: fold one chunk's assignment stats
    into the epoch's running (sums, counts, inertia)."""
    key = ("streaming_kmeans_accum", k, mode, mesh)
    prog = _BLOCK_PROGRAMS.get(key)
    if prog is None:

        def block(xa, centers, n_valid, sums, counts, inertia):
            s, c, _, i = _assign_stats_dispatch(xa, centers, k, n_valid, mode, mesh)
            return sums + s, counts + c, inertia + i

        _BLOCK_PROGRAMS[key] = jax.jit(block)
        prog = _BLOCK_PROGRAMS[key]
    return prog


def _minibatch_program(k: int, mode: str = "fallback", mesh=None):
    """Cached per-chunk minibatch step: move each assigned center toward
    its chunk mean with learning rate ``counts / new_totals``."""
    key = ("streaming_kmeans_minibatch", k, mode, mesh)
    prog = _BLOCK_PROGRAMS.get(key)
    if prog is None:

        def block(xa, centers, totals, n_valid):
            sums, counts, _, inertia = _assign_stats_dispatch(xa, centers, k, n_valid, mode, mesh)
            new_totals = totals + counts
            eta = (counts / jnp.maximum(new_totals, 1.0))[:, None]
            target = sums / jnp.maximum(counts, 1.0)[:, None]
            new_centers = jnp.where(
                counts[:, None] > 0, centers * (1.0 - eta) + target * eta, centers
            )
            return new_centers, new_totals, inertia

        _BLOCK_PROGRAMS[key] = jax.jit(block)
        prog = _BLOCK_PROGRAMS[key]
    return prog


class StreamingKMeans(_KCluster):
    """K-Means over a chunked stream (see module docstring).

    Parameters follow :class:`~heat_tpu.cluster.kmeans.KMeans`
    (``n_clusters``, ``init``, ``max_iter``, ``tol``, ``random_state``)
    plus ``algorithm`` ('global' | 'minibatch'). With a non-DNDarray
    ``init`` the initial centroids are sampled from the FIRST chunk (a
    stream cannot be sampled globally before it is read); pass explicit
    centroids for deterministic cross-implementation comparisons.

    Notes: ``labels_`` stays ``None`` (a single-pass fit does not retain
    per-row assignments — use :meth:`predict`); ``inertia_`` is the last
    epoch's accumulated inertia, measured against that epoch's STARTING
    centers ('global') or the evolving centers ('minibatch').
    """

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, DNDarray] = "random",
        max_iter: int = 10,
        tol: float = 1e-4,
        random_state: Optional[int] = None,
        algorithm: str = "global",
    ):
        if algorithm not in ("global", "minibatch"):
            raise ValueError(f"algorithm must be 'global' or 'minibatch', got {algorithm!r}")
        super().__init__(
            metric=_quadratic_expand,
            n_clusters=n_clusters,
            init=init,
            max_iter=max_iter,
            tol=tol,
            random_state=random_state,
        )
        self.algorithm = algorithm
        self._centers_dev = None  # replicated jnp array between chunks
        self._totals = None  # minibatch per-center sample counts
        self._placement = None  # (device, comm) from the first chunk
        self._choice = ("fallback", None)  # assignment (mode, mesh) per chunk

    def _chunk_view(self, chunk: DNDarray):
        """Padded device buffer + valid count, float32-promoted (the
        KMeans fit-time view: tail padding masked inside the kernel)."""
        if not isinstance(chunk, DNDarray):
            raise TypeError(f"chunks must be DNDarrays, got {type(chunk)}")
        if chunk.ndim != 2:
            raise ValueError(f"chunks must be 2D, got {chunk.ndim}D")
        xa = chunk.larray
        xa = xa.astype(jnp.promote_types(xa.dtype, jnp.float32))
        if self._centers_dev is None:
            self._placement = (chunk.device, chunk.comm)
            self._centers_dev = self._initialize_cluster_centers(chunk).astype(xa.dtype)
        from ..core.kernels import record_dispatch

        # per-chunk call boundary: pick (and count) the assignment mode
        self._choice = _assign_choice(chunk, xa, self.n_clusters)
        record_dispatch("lloyd_fused", self._choice[0])
        return xa, jnp.int32(chunk.gshape[0])

    def _publish(self) -> None:
        device, comm = self._placement
        self._cluster_centers = DNDarray(
            self._centers_dev, split=None, device=device, comm=comm
        )

    # ------------------------------------------------------------ minibatch
    def partial_fit(self, chunk: DNDarray) -> "StreamingKMeans":
        """One online minibatch step on ``chunk`` (any ``algorithm``
        setting — this IS the minibatch update)."""
        xa, nv = self._chunk_view(chunk)
        k = self.n_clusters
        if self._totals is None:
            self._totals = jnp.zeros((k,), xa.dtype)
        self._centers_dev, self._totals, inertia = collective_lockstep(
            _minibatch_program(k, *self._choice)(xa, self._centers_dev, self._totals, nv)
        )
        self._inertia = float(inertia)
        self._n_iter = (self._n_iter or 0) + 1
        self._publish()
        return self

    # --------------------------------------------------------------- epochs
    def fit(self, chunks, prefetch_depth: Optional[int] = None) -> "StreamingKMeans":
        """Fit over a re-iterable chunk source, up to ``max_iter`` epochs
        or until the centroid shift drops to ``tol``. 'global' epochs are
        exact Lloyd iterations; 'minibatch' usually converges in one.

        With ``prefetch_depth`` each epoch's pass is wrapped in a fresh
        :class:`~heat_tpu.stream.prefetch.Prefetcher` (a Prefetcher itself
        is single-use, so pass the underlying re-iterable source here
        rather than a pre-wrapped one when ``max_iter > 1``).
        """
        if self.max_iter < 1:
            raise ValueError(f"max_iter must be >= 1, got {self.max_iter}")
        k = self.n_clusters
        tol = -1.0 if self.tol is None else float(self.tol)
        epoch = 0
        shift = float("inf")
        while epoch < self.max_iter and shift > tol:
            sums = counts = None
            inertia = None
            seen = False
            old = self._centers_dev
            src = chunks if prefetch_depth is None else Prefetcher(chunks, depth=prefetch_depth)
            for chunk in src:
                seen = True
                xa, nv = self._chunk_view(chunk)
                if self.algorithm == "minibatch":
                    if self._totals is None:
                        self._totals = jnp.zeros((k,), xa.dtype)
                    self._centers_dev, self._totals, inertia = collective_lockstep(
                        _minibatch_program(k, *self._choice)(
                            xa, self._centers_dev, self._totals, nv
                        )
                    )
                    continue
                if sums is None:
                    f = xa.shape[1]
                    sums = jnp.zeros((k, f), xa.dtype)
                    counts = jnp.zeros((k,), xa.dtype)
                    inertia = jnp.zeros((), xa.dtype)
                sums, counts, inertia = collective_lockstep(
                    _accum_program(k, *self._choice)(
                        xa, self._centers_dev, nv, sums, counts, inertia
                    )
                )
            if not seen:
                if epoch == 0:
                    raise ValueError("chunk source yielded no chunks")
                raise ValueError(
                    "chunk source exhausted after one epoch; multi-epoch fits "
                    "need a re-iterable source (e.g. a ChunkIterator, not a "
                    "pre-wrapped Prefetcher — use the prefetch_depth argument)"
                )
            old = old if old is not None else self._centers_dev
            if self.algorithm == "global":
                # the exact Lloyd update over the epoch's global stats
                self._centers_dev = jnp.where(
                    counts[:, None] > 0,
                    sums / jnp.maximum(counts, 1.0)[:, None],
                    self._centers_dev,
                )
            shift = float(jnp.sum((self._centers_dev - old) ** 2))
            self._inertia = float(inertia)
            epoch += 1
        self._n_iter = epoch
        self._publish()
        return self
