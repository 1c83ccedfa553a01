"""Graph Laplacians (reference ``heat/graph/laplacian.py``).

Similarity, adjacency thresholding (eNeighbour / fully_connected) and the
simple / symmetrically normalized Laplacian in ONE compiled program. Where
the similarity is a :class:`PairwiseSimilarity` the program computes it
from the rows itself, twice — once inside the degrees' row sums, once where
L is written — so the only (n, n) array it ever writes is L. A similarity
given as a callable of DNDarrays runs first, and the program reads its
result.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from ..core import _hooks, types
from ..core.dndarray import DNDarray

__all__ = ["Laplacian", "PairwiseSimilarity"]


class PairwiseSimilarity:
    """A similarity of rows that the Laplacian's program computes where it
    reads it: ``"rbf"``, ``exp(-gamma ||xi - xj||^2)``, or ``"euclidean"``,
    ``||xi - xj||``.

    ``||xi - xj||^2`` is the sum over the features of squared differences,
    in float32 or wider: elementwise, so the compiler fuses it into the
    operation that reads it, and free of a matmul, so no one-pass bf16
    cross term (the expansion ``|x|^2 + |y|^2 - 2 x.y`` at default precision
    is off by about ``2^-8 |x||y|`` on a TPU)."""

    def __init__(self, metric: str = "rbf", gamma: float = 1.0):
        if metric not in ("rbf", "euclidean"):
            raise NotImplementedError(f"Metric {metric} not supported")
        self.metric = metric
        self.gamma = float(gamma)

    # a static argument of the Laplacian's program: equal similarities share one executable
    def _key(self):
        return (self.metric, self.gamma)

    def __eq__(self, other):
        return isinstance(other, PairwiseSimilarity) and self._key() == other._key()

    def __hash__(self):
        return hash(self._key())

    def __repr__(self):
        return f"PairwiseSimilarity({self.metric!r}, gamma={self.gamma})"

    def of_rows(self, x: jnp.ndarray) -> jnp.ndarray:
        """The (n, n) similarity of the rows of ``x``, as an expression."""
        d2 = None
        for k in range(x.shape[1]):
            c = x[:, k]
            t = c[:, None] - c[None, :]
            d2 = t * t if d2 is None else d2 + t * t
        if d2 is None:
            d2 = jnp.zeros((x.shape[0], x.shape[0]), x.dtype)
        if self.metric == "rbf":
            return jnp.exp(-self.gamma * d2)
        return jnp.sqrt(d2)


class Laplacian:
    """reference ``laplacian.py:12``

    Parameters
    ----------
    similarity : PairwiseSimilarity or callable
        A :class:`PairwiseSimilarity`, computed inside the Laplacian's
        program, or a DNDarray -> DNDarray similarity matrix (e.g.
        ``lambda x: ht.spatial.rbf(x, sigma=1.0)``), computed first.
    definition : 'simple' | 'norm_sym'
    mode : 'fully_connected' | 'eNeighbour'
    threshold_key : 'upper' | 'lower'  (for eNeighbour)
    threshold_value : float
    """

    def __init__(
        self,
        similarity: Callable,
        weighted: bool = True,
        definition: str = "norm_sym",
        mode: str = "fully_connected",
        threshold_key: str = "upper",
        threshold_value: float = 1.0,
        neighbours: int = 10,
    ):
        self.similarity_metric = similarity
        self.weighted = weighted
        if definition not in ("simple", "norm_sym"):
            raise NotImplementedError("Only simple and norm_sym Laplacians are supported")
        if mode not in ("eNeighbour", "fully_connected"):
            raise NotImplementedError("Only eNeighbour and fully_connected modes are supported")
        self.definition = definition
        self.mode = mode
        self.epsilon = (threshold_key, threshold_value)
        self.neighbours = neighbours

    def construct(self, x: DNDarray) -> DNDarray:
        """Build the Laplacian of the dataset (reference ``laplacian.py``):
        ``norm_sym`` ``L = I - D^-1/2 A D^-1/2``, ``simple`` ``L = D - A``,
        A the (thresholded) similarity with its diagonal zeroed."""
        sim = self.similarity_metric
        if isinstance(sim, PairwiseSimilarity):
            src = x._logical()
            src = src.astype(jnp.promote_types(src.dtype, jnp.float32))
            split = None if x.split is None else 0
        else:
            S = sim(x)
            if not isinstance(S, DNDarray):
                raise TypeError("similarity metric must return a DNDarray")
            src, sim, split = S._logical(), None, S.split
        n = src.shape[0]
        shape = x.comm.padded_shape((n, n), split)
        sharding = x.comm.array_sharding(shape, split)
        key, val = self.epsilon
        threshold = None if self.mode == "fully_connected" else (key, float(val), bool(self.weighted))
        L = _laplacian_program(src, sim, self.definition, threshold, shape[0], sharding)
        return DNDarray._from_buffer(
            L, (n, n), types.canonical_heat_type(L.dtype), split, x.device, x.comm
        )


def _adjacency(src, sim, threshold, rows: int):
    """(rows, n) adjacency: the similarity (of ``src``'s rows, or ``src``
    itself), thresholded, with the self-loops and the rows past n zeroed by
    index comparisons, so no identity matrix is made."""
    n = src.shape[0]
    S = src if sim is None else sim.of_rows(src)
    if threshold is not None:
        key, val, weighted = threshold
        keep = S < val if key == "upper" else S > val
        S = jnp.where(keep, S if weighted else 1.0, 0.0).astype(S.dtype)
    if rows != n:
        S = jnp.pad(S, ((0, rows - n), (0, 0)))
    return jnp.where(_diagonal(S.shape), 0.0, S).astype(S.dtype)


def _diagonal(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0) == jax.lax.broadcasted_iota(jnp.int32, shape, 1)


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _laplacian_program(src, sim, definition: str, threshold, rows: int, sharding):
    """L of ``src`` (rows of data, or a similarity matrix where ``sim`` is
    None) as a (rows, n) buffer, the rows past n zero.

    With ``sim`` the adjacency is recomputed from the rows for L after the
    degrees: the ``optimization_barrier`` gives the second computation
    inputs of its own, so the compiler cannot share the first one's (n, n)
    result between the row sums and L, and fuses each into its consumer."""
    with _hooks.phase("laplacian"):
        A = _adjacency(src, sim, threshold, rows)
        deg = jnp.sum(A, axis=1)
        if sim is not None:
            src, deg = jax.lax.optimization_barrier((src, deg))
            A = _adjacency(src, sim, threshold, rows)
        if definition == "simple":
            L = jnp.where(_diagonal(A.shape), deg[:, None], -A)
        else:
            d_inv_sqrt = jnp.where(deg > 0, jax.lax.rsqrt(jnp.maximum(deg, 1e-30)), 0.0)
            L = jnp.where(_diagonal(A.shape), 1.0, -(d_inv_sqrt[:, None] * A * d_inv_sqrt[None, : A.shape[1]]))
        return jax.lax.with_sharding_constraint(L.astype(A.dtype), sharding)
