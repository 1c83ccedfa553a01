"""K-Medians clustering (reference ``heat/cluster/kmedians.py``).

Same fused-iteration structure as :class:`KMeans`; the centroid update is a
masked per-cluster median (non-members NaN'd out, ``nanmedian`` reduced
over the sharded data axis).
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

__all__ = ["KMedians"]


@partial(jax.jit, static_argnames=("k",))
def _median_step(xa: jnp.ndarray, centers: jnp.ndarray, k: int):
    # reference kmedians assigns by Manhattan distance (kmedians.py:49),
    # matching the L1-optimal median update
    d = _l1_distance(xa, centers)
    labels = jnp.argmin(d, axis=1)
    member = labels[:, None] == jnp.arange(k)[None, :]  # (n, k)
    masked = jnp.where(member[:, :, None], xa[:, None, :], jnp.nan)  # (n, k, f)
    new_centers = jnp.nanmedian(masked, axis=0)  # (k, f)
    new_centers = jnp.where(jnp.isnan(new_centers), centers, new_centers)
    shift = jnp.sum((new_centers - centers) ** 2)
    return new_centers, labels, shift


@partial(jax.jit, static_argnames=("k",))
def _median_fit(xa: jnp.ndarray, centers: jnp.ndarray, k: int, max_iter, tol):
    """Whole fit as ONE device program (shared harness; the eager loop
    paid a host round-trip per iteration)."""
    from ._kcluster import _whole_fit

    return _whole_fit(lambda x, c: _median_step(x, c, k), xa, centers, max_iter, tol)


def _median_block_program(k: int):
    """Cached jitted bounded-chunk median loop (supervised fits)."""
    key = ("kmedians", k)
    prog = _BLOCK_PROGRAMS.get(key)
    if prog is None:

        def block(xa, centers, budget, tol, shift0):
            return _block_fit(
                lambda x, c: _median_step(x, c, k), xa, centers, budget, tol, shift0
            )

        _BLOCK_PROGRAMS[key] = jax.jit(block)
        prog = _BLOCK_PROGRAMS[key]
    return prog


class KMedians(_KCluster):
    """K-Medians (reference ``kmedians.py:12``)."""

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, DNDarray] = "random",
        max_iter: int = 300,
        tol: float = 1e-4,
        random_state: Optional[int] = None,
    ):
        super().__init__(
            metric=_l1_distance,
            n_clusters=n_clusters,
            init=init,
            max_iter=max_iter,
            tol=tol,
            random_state=random_state,
        )

    def _supervised_step(self, xa, centers, budget, tol, shift0, x):
        prog = _median_block_program(self.n_clusters)
        return prog(xa, centers, budget, tol, shift0)

    def fit(self, x: DNDarray, supervisor=None, block_iters: int = 16) -> "KMedians":
        """reference ``kmedians.py``; with ``supervisor`` the fit runs as
        a self-healing supervised step loop."""
        if not isinstance(x, DNDarray):
            raise TypeError(f"input needs to be a DNDarray, but was {type(x)}")
        if self.max_iter < 1:
            raise ValueError(f"max_iter must be >= 1, got {self.max_iter}")
        if supervisor is not None:
            return self._fit_supervised(x, supervisor, block_iters, "kmedians.fit")
        k = self.n_clusters
        xa = x._logical().astype(jnp.promote_types(x.larray.dtype, jnp.float32))
        centers = self._initialize_cluster_centers(x).astype(xa.dtype)

        tol = -1.0 if self.tol is None else float(self.tol)
        centers, labels, n_iter = _median_fit(
            xa, centers, k, jnp.int32(self.max_iter), jnp.asarray(tol, xa.dtype)
        )
        n_iter = int(_hooks.fetch(n_iter, "kmedians.n_iter"))

        self._cluster_centers = DNDarray(centers, split=None, device=x.device, comm=x.comm)
        self._labels = DNDarray(
            labels.astype(jnp.int64), dtype=types.int64, split=x.split, device=x.device, comm=x.comm
        )
        self._n_iter = n_iter
        return self
