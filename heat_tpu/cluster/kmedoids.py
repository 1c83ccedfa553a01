"""K-Medoids clustering (reference ``heat/cluster/kmedoids.py``).

Reference semantics: the new centroid is the actual data point closest to
the cluster median ("snap to point"). The snap is a masked argmin over the
sharded distance column — one fused program per iteration.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp

from ..core import _hooks, types
from ..core.dndarray import DNDarray
from ..spatial.distance import _manhattan as _l1_distance
from ._kcluster import _BLOCK_PROGRAMS, _KCluster, _block_fit

__all__ = ["KMedoids"]


@partial(jax.jit, static_argnames=("k",))
def _medoid_step(xa: jnp.ndarray, centers: jnp.ndarray, k: int):
    d = _l1_distance(xa, centers)
    labels = jnp.argmin(d, axis=1)
    member = labels[:, None] == jnp.arange(k)[None, :]  # (n, k)
    masked = jnp.where(member[:, :, None], xa[:, None, :], jnp.nan)
    medians = jnp.nanmedian(masked, axis=0)  # (k, f)
    medians = jnp.where(jnp.isnan(medians), centers, medians)
    # snap each median to the nearest member point (L1, like the assignment)
    dist_to_med = _l1_distance(xa, medians)  # (n, k)
    dist_to_med = jnp.where(member, dist_to_med, jnp.inf)
    snap_idx = jnp.argmin(dist_to_med, axis=0)  # (k,)
    snapped = jnp.take(xa, snap_idx, axis=0)
    has_member = jnp.any(member, axis=0)
    new_centers = jnp.where(has_member[:, None], snapped, centers)
    shift = jnp.sum((new_centers - centers) ** 2)
    return new_centers, labels, shift


@partial(jax.jit, static_argnames=("k",))
def _medoid_fit(xa: jnp.ndarray, centers: jnp.ndarray, k: int, max_iter):
    """Whole fit as ONE device program (medoids converge when no center
    moves: shift == 0); shared harness — the eager loop fetched shift to
    host per step."""
    from ._kcluster import _whole_fit

    return _whole_fit(
        lambda x, c: _medoid_step(x, c, k), xa, centers, max_iter, jnp.asarray(0.0, xa.dtype)
    )


def _medoid_block_program(k: int):
    """Cached jitted bounded-chunk medoid loop (supervised fits)."""
    key = ("kmedoids", k)
    prog = _BLOCK_PROGRAMS.get(key)
    if prog is None:

        def block(xa, centers, budget, tol, shift0):
            return _block_fit(
                lambda x, c: _medoid_step(x, c, k), xa, centers, budget, tol, shift0
            )

        _BLOCK_PROGRAMS[key] = jax.jit(block)
        prog = _BLOCK_PROGRAMS[key]
    return prog


class KMedoids(_KCluster):
    """K-Medoids with snap-to-point update (reference ``kmedoids.py:12``)."""

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, DNDarray] = "random",
        max_iter: int = 300,
        random_state: Optional[int] = None,
    ):
        super().__init__(
            metric=_l1_distance,
            n_clusters=n_clusters,
            init=init,
            max_iter=max_iter,
            tol=0.0,
            random_state=random_state,
        )

    def _supervised_step(self, xa, centers, budget, tol, shift0, x):
        prog = _medoid_block_program(self.n_clusters)
        return prog(xa, centers, budget, tol, shift0)

    def fit(self, x: DNDarray, supervisor=None, block_iters: int = 16) -> "KMedoids":
        """reference ``kmedoids.py``; with ``supervisor`` the fit runs as
        a self-healing supervised step loop."""
        if not isinstance(x, DNDarray):
            raise TypeError(f"input needs to be a DNDarray, but was {type(x)}")
        if self.max_iter < 1:
            raise ValueError(f"max_iter must be >= 1, got {self.max_iter}")
        if supervisor is not None:
            return self._fit_supervised(x, supervisor, block_iters, "kmedoids.fit")
        k = self.n_clusters
        xa = x._logical().astype(jnp.promote_types(x.larray.dtype, jnp.float32))
        centers = self._initialize_cluster_centers(x).astype(xa.dtype)

        centers, labels, n_iter = _medoid_fit(xa, centers, k, jnp.int32(self.max_iter))
        n_iter = int(_hooks.fetch(n_iter, "kmedoids.n_iter"))

        self._cluster_centers = DNDarray(centers, split=None, device=x.device, comm=x.comm)
        self._labels = DNDarray(
            labels.astype(jnp.int64), dtype=types.int64, split=x.split, device=x.device, comm=x.comm
        )
        self._n_iter = n_iter
        return self
