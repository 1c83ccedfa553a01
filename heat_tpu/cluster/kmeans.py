"""K-Means clustering (reference ``heat/cluster/kmeans.py``).

The reference's fit loop (``kmeans.py:122-135``) issues k+1 small
Allreduces per iteration (one masked-mean per cluster + convergence check).
Here one Lloyd iteration is a **single jitted XLA program**: fused
distance+argmin on the sharded data, a one-hot matmul on the MXU for the
per-cluster sums (psum over ICI), and the centroid shift — so each
iteration is exactly one all-reduce of a (k, f+1) buffer, independent of k.
psum reduction order is deterministic, so centroids are bit-identical
across runs on the same mesh.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp

from ..core import _hooks, types
from ..core.dndarray import DNDarray
from ..spatial.distance import _quadratic_expand
from ._kcluster import _BLOCK_PROGRAMS, _KCluster

__all__ = ["KMeans"]


def _assign_choice(x: DNDarray, xa: jnp.ndarray, k: int):
    """(mode, mesh) for the Lloyd assignment at this call boundary.

    The fused pallas kernel (``kernels.lloyd``) needs a single-device
    buffer or even split-0 shards (its shard_map derives each shard's
    validity window from its rank) and a shape inside what it was
    compiled for (``lloyd.kernel_fits``); anything else — feature split,
    uneven shards, wide rows, more than a thousand centers — stays on the
    fused-XLA ``_assign_stats`` path. ``interpret`` only ever arrives via
    ``kernels.forced_mode`` (parity tests)."""
    from ..core.kernels import dispatch_mode
    from ..core.kernels.lloyd import kernel_fits

    mode = dispatch_mode("lloyd_fused")
    mesh = None
    p = x.comm.size
    if mode in ("pallas", "interpret"):
        if not kernel_fits(xa.shape[1], k):
            mode = "fallback"
        elif x.split == 0 and p > 1:
            if xa.shape[0] % p == 0:
                mesh = x.comm.mesh
            else:
                mode = "fallback"
        elif x.split is not None and p > 1:
            mode = "fallback"
    return mode, mesh


def _assign_stats(xa: jnp.ndarray, centers: jnp.ndarray, k: int, n_valid):
    """Assignment sufficient statistics, fused: per-cluster ``sums``
    (k, f) and ``counts`` (k,) plus per-row ``labels`` and the summed
    min-distance ``inertia``.

    The distance+argmin runs on the sharded data; the one-hot update is an
    MXU matmul whose reduction XLA psums over ICI. Rows past ``n_valid``
    are buffer tail padding: their one-hot weight is zeroed so they never
    touch counts, sums or inertia (labels in the padded rows are dead
    values). This is THE assignment kernel: the eager Lloyd body below
    consumes it whole (XLA dead-code-eliminates the unused inertia), and
    the streaming per-chunk programs (:mod:`heat_tpu.cluster.streaming`)
    accumulate its raw sums/counts across chunks.
    """
    d2 = _quadratic_expand(xa, centers)  # (n, k), sharded on n
    labels = jnp.argmin(d2, axis=1).astype(jnp.int32)
    onehot = jax.nn.one_hot(labels, k, dtype=xa.dtype)  # (n, k)
    valid = jnp.arange(xa.shape[0], dtype=jnp.int32) < n_valid
    onehot = onehot * valid[:, None].astype(xa.dtype)
    # zero the padded rows themselves too: 0-weight x inf-garbage is nan
    xa_safe = jnp.where(valid[:, None], xa, 0.0)
    counts = jnp.sum(onehot, axis=0)  # (k,)
    sums = onehot.T @ xa_safe  # (k, f) — MXU matmul + psum
    inertia = jnp.sum(jnp.where(valid, jnp.min(d2, axis=1), 0.0))
    return sums, counts, labels, inertia


def _assign_stats_dispatch(xa, centers, k: int, n_valid, mode: str, mesh):
    """The :func:`_assign_stats` contract via the mode chosen at the call
    boundary: the fused pallas kernel (one HBM pass, compiled or
    interpreted) or the fused-XLA fallback. ``mode``/``mesh`` are static
    under jit — the choice is baked into the compiled program."""
    if mode in ("pallas", "interpret"):
        from ..core.kernels import lloyd_local, lloyd_sharded

        interpret = mode != "pallas"
        nv = xa.shape[0] if n_valid is None else n_valid
        if mesh is not None:
            return lloyd_sharded(xa, centers, nv, mesh, interpret=interpret)
        return lloyd_local(xa, centers, nv, interpret=interpret)
    return _assign_stats(xa, centers, k, n_valid)


def _lloyd_body(xa: jnp.ndarray, centers: jnp.ndarray, k: int, n_valid,
                mode: str = "fallback", mesh=None):
    """One Lloyd iteration: (assign, update, shift) fused into one program
    over the shared :func:`_assign_stats` kernel (or its pallas twin)."""
    sums, counts, labels, _ = _assign_stats_dispatch(xa, centers, k, n_valid, mode, mesh)
    new_centers = jnp.where(
        counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], centers
    )
    shift = jnp.sum((new_centers - centers) ** 2)
    return new_centers, labels, shift


@partial(jax.jit, static_argnames=("k",))
def _inertia(xa: jnp.ndarray, centers: jnp.ndarray, k: int, n_valid=None) -> jnp.ndarray:
    d2 = _quadratic_expand(xa, centers)
    per_row = jnp.min(d2, axis=1)
    if n_valid is None:
        return jnp.sum(per_row)
    valid = jnp.arange(xa.shape[0], dtype=jnp.int32) < n_valid
    return jnp.sum(jnp.where(valid, per_row, 0.0))


@partial(jax.jit, static_argnames=("k", "max_iter", "mode", "mesh"))
def _lloyd_fit(xa: jnp.ndarray, centers: jnp.ndarray, k: int, max_iter: int, tol: float,
               n_valid=None, mode: str = "fallback", mesh=None):
    """The whole fit as ONE device program: a ``lax.while_loop`` over fused
    Lloyd iterations with the tol check on device. A full fit is a single
    dispatch: a host-side loop would pay a device→host sync per step."""

    def cond(state):
        i, _, _, shift = state
        return jnp.logical_and(i < max_iter, shift > tol)

    def body(state):
        i, c, _, _ = state
        new_c, labels, shift = _lloyd_body(xa, c, k, nv, mode, mesh)
        return (i + 1, new_c, labels, shift)

    n = xa.shape[0]
    nv = n if n_valid is None else n_valid
    state0 = (0, centers, jnp.zeros((n,), dtype=jnp.int32), jnp.asarray(jnp.inf, xa.dtype))
    i, c, labels, _ = jax.lax.while_loop(cond, body, state0)
    return c, labels, i


def _lloyd_block_program(k: int, mode: str = "fallback", mesh=None):
    """Cached jitted bounded-chunk Lloyd loop (supervised fits): like
    :func:`_lloyd_fit` but with a dynamic iteration budget and the shift
    carried in/out, so chained chunks reproduce the whole-fit sequence."""
    key = ("kmeans", k, mode, mesh)
    prog = _BLOCK_PROGRAMS.get(key)
    if prog is None:

        def block(xa, centers, budget, tol, n_valid, shift0):
            def cond(state):
                i, _, _, shift = state
                return jnp.logical_and(i < budget, shift > tol)

            def body(state):
                i, c, _, _ = state
                new_c, labels, shift = _lloyd_body(xa, c, k, n_valid, mode, mesh)
                return (i + 1, new_c, labels, shift)

            n = xa.shape[0]
            state0 = (jnp.int32(0), centers, jnp.zeros((n,), jnp.int32), shift0)
            i, c, labels, shift = jax.lax.while_loop(cond, body, state0)
            return c, labels, i, shift

        _BLOCK_PROGRAMS[key] = jax.jit(block)
        prog = _BLOCK_PROGRAMS[key]
    return prog


class KMeans(_KCluster):
    """K-Means with Lloyd's algorithm (reference ``kmeans.py:21``).

    Parameters follow the reference: ``n_clusters``, ``init``
    ('random' | 'probability_based' | DNDarray), ``max_iter``, ``tol``,
    ``random_state``.
    """

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, DNDarray] = "random",
        max_iter: int = 300,
        tol: float = 1e-4,
        random_state: Optional[int] = None,
    ):
        super().__init__(
            metric=_quadratic_expand,
            n_clusters=n_clusters,
            init=init,
            max_iter=max_iter,
            tol=tol,
            random_state=random_state,
        )

    def _prep_fit(self, x: DNDarray) -> jnp.ndarray:
        # keep the padded buffer; _lloyd_body masks with the valid count
        return x.larray.astype(jnp.promote_types(x.larray.dtype, jnp.float32))

    def _supervised_step(self, xa, centers, budget, tol, shift0, x):
        from ..core.kernels import record_dispatch

        mode, mesh = _assign_choice(x, xa, self.n_clusters)
        record_dispatch("lloyd_fused", mode)
        prog = _lloyd_block_program(self.n_clusters, mode, mesh)
        return prog(xa, centers, budget, tol, jnp.int32(x.gshape[0]), shift0)

    def _finalize_supervised(self, result) -> None:
        x = result.data[0]  # on the final (possibly shrunken) mesh
        xa = self._prep_fit(x)
        self._inertia = float(_hooks.fetch(
            _inertia(xa, self._cluster_centers.larray.astype(xa.dtype),
                     self.n_clusters, x.gshape[0]),
            "kmeans.inertia",
        ))

    @_hooks.public_call("KMeans.fit")
    def fit(self, x: DNDarray, supervisor=None, block_iters: int = 16) -> "KMeans":
        """Lloyd iterations until the centroid shift drops below tol
        (reference ``kmeans.py:102-135``). With ``supervisor`` the fit
        runs as a self-healing supervised step loop (one step = one
        jitted chunk of up to ``block_iters`` iterations)."""
        if not isinstance(x, DNDarray):
            raise TypeError(f"input needs to be a DNDarray, but was {type(x)}")
        if x.ndim != 2:
            raise ValueError(f"input needs to be 2D, but was {x.ndim}D")
        if self.max_iter < 1:
            raise ValueError(f"max_iter must be >= 1, got {self.max_iter}")
        if supervisor is not None:
            return self._fit_supervised(x, supervisor, block_iters, "kmeans.fit")
        k = self.n_clusters
        xa = x.larray.astype(jnp.promote_types(x.larray.dtype, jnp.float32))
        n = x.gshape[0]
        centers = self._initialize_cluster_centers(x).astype(xa.dtype)

        tol = -1.0 if self.tol is None else float(self.tol)
        from ..core.kernels import record_dispatch

        mode, mesh = _assign_choice(x, xa, k)
        record_dispatch("lloyd_fused", mode)  # call boundary: once per fit
        centers, labels, n_iter = _lloyd_fit(
            xa, centers, k, self.max_iter, tol, n, mode=mode, mesh=mesh
        )

        self._cluster_centers = DNDarray(centers, split=None, device=x.device, comm=x.comm)
        labels = labels.astype(jnp.int64)
        if x.split is not None and labels.shape[0] != n:
            self._labels = DNDarray._from_buffer(
                labels, (n,), types.int64, 0, x.device, x.comm
            )
        else:
            self._labels = DNDarray(
                labels[:n], dtype=types.int64, split=x.split, device=x.device, comm=x.comm
            )
        self._inertia = float(_hooks.fetch(_inertia(xa, centers, k, n), "kmeans.inertia"))
        self._n_iter = int(_hooks.fetch(n_iter, "kmeans.n_iter"))
        return self
