"""k-nearest-neighbors classifier (reference
``heat/classification/kneighborsclassifier.py``).

fit stores the training set; predict is a fused sharded program: distance
matrix on the MXU -> ``lax.top_k`` of the negated distances -> one-hot
vote (reference ``kneighborsclassifier.py:10-136``).
"""
from __future__ import annotations

import jax.numpy as jnp

from ..core import types
from ..core.base import BaseEstimator, ClassificationMixin
from ..core.dndarray import DNDarray

__all__ = ["KNeighborsClassifier"]


def one_hot_encoding(y: jnp.ndarray, classes: jnp.ndarray) -> jnp.ndarray:
    """One-hot over an arbitrary class alphabet (reference
    ``kneighborsclassifier.py:45``)."""
    return (y[:, None] == classes[None, :]).astype(jnp.float32)


class KNeighborsClassifier(BaseEstimator, ClassificationMixin):
    """reference ``kneighborsclassifier.py:10``"""

    def __init__(self, n_neighbors: int = 5):
        self.n_neighbors = n_neighbors
        self.x = None
        self.y = None
        self.classes_ = None

    def fit(self, x: DNDarray, y: DNDarray) -> "KNeighborsClassifier":
        """Store the training set (reference ``kneighborsclassifier.py``)."""
        if not isinstance(x, DNDarray) or not isinstance(y, DNDarray):
            raise TypeError(f"input needs to be DNDarrays, but were {type(x)}, {type(y)}")
        self.x = x
        self.y = y
        self.classes_ = jnp.unique(y._logical().ravel())
        return self

    def predict(self, x: DNDarray) -> DNDarray:
        """reference ``kneighborsclassifier.py:predict``"""
        if self.x is None:
            raise RuntimeError("fit needs to be called before predict")
        yt = self.y._logical().ravel()
        nq, nt = x.shape[0], self.x.shape[0]
        from ..core.kernels import dispatch_mode
        from ..spatial.distance import _nn_materialized, nearest_neighbors

        # the fused kernel's merge is O(k*(k+tile_m)) per tile — past k~64
        # the materializing cdist+top_k path wins, so gate on k as well
        if (
            dispatch_mode("topk_distance") != "fallback"
            and nq * nt > 1 << 22
            and x.split in (None, 0)
            and self.n_neighbors <= 64
        ):
            # fused pallas path: never materializes the (nq, nt) matrix
            _, idx_nd = nearest_neighbors(x, self.x, self.n_neighbors)
            idx = idx_nd._logical()
        else:
            from ..core.kernels import record_dispatch

            record_dispatch("topk_distance", "fallback")
            Xq = x._logical().astype(jnp.float32)
            Xt = self.x._logical().astype(jnp.float32)
            _, idx = _nn_materialized(Xq, Xt, self.n_neighbors)  # (nq, k) nearest
        neigh_labels = jnp.take(yt, idx)  # (nq, k)
        votes = jnp.sum(
            one_hot_encoding(neigh_labels.ravel(), self.classes_).reshape(
                idx.shape[0], self.n_neighbors, -1
            ),
            axis=1,
        )  # (nq, n_classes)
        pred = jnp.take(self.classes_, jnp.argmax(votes, axis=1))
        return DNDarray(pred, split=x.split, device=x.device, comm=x.comm)
