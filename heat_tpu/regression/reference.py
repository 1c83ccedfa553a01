"""Plain reference of the Lasso estimator: the published loop in NumPy float64, nothing of the engine.

What the tests (and anyone who doubts a fit) hold :class:`heat_tpu.regression.Lasso` to. No jax,
no shard, no program, no import from ``lasso.py``: the whole design matrix on the host, one
coordinate after the other, the straightforward way.
"""
from __future__ import annotations

import numpy as np

__all__ = ["lasso_cd"]


def lasso_cd(X, y, lam: float, sweeps: int) -> np.ndarray:
    """``sweeps`` sweeps of cyclic coordinate descent from ``theta = 0``, in float64.

    Minimises ``(1/2n) ||X theta - y||^2 + lam ||theta[1:]||_1`` over ``theta``: column 0 of ``X``
    is the column of ones the estimator expects, and its coefficient, the intercept, is not
    regularised. For each sweep, for each column ``j`` in order, with the residual
    ``r = y - X theta`` kept up to date::

        rho      = x_j . (r + x_j theta_j)
        theta_j  = rho / ||x_j||^2                                     for j = 0
                   sign(rho) max(|rho| - lam n, 0) / ||x_j||^2         otherwise
        r        = r - x_j (theta_j - theta_j before)

    A column of zeros keeps the coefficient 0. Returns ``theta`` of shape ``(columns,)``.

    Departures from upstream Heat's ``heat/regression/lasso.py``, each exact in what it computes:

    * upstream recomputes ``x @ theta`` for every coordinate; the residual kept here is the same
      quantity, updated by the one column that changed.
    * upstream works with means (``rho / n``, threshold ``lam``) and skips the division by
      ``||x_j||^2 / n``: it presumes standardised columns, for which that is 1. The division is
      kept here (``rho`` and the threshold ``lam n`` are the same numbers times ``n``), so the
      step is the exact coordinate minimiser on any columns and equal to upstream's on
      standardised ones.
    """
    # graftlint: host-sync - the reference is host NumPy by design: it is handed host arrays
    X = np.asarray(X, dtype=np.float64)
    # graftlint: host-sync - as above
    y = np.asarray(y, dtype=np.float64).reshape(-1)
    n, m = X.shape
    if y.shape[0] != n:
        raise ValueError(f"X has {n} rows, y has {y.shape[0]}")
    theta = np.zeros(m, dtype=np.float64)
    r = y.copy()
    for _ in range(sweeps):
        for j in range(m):
            xj = X[:, j]
            sq = xj @ xj
            if sq == 0.0:
                continue
            rho = xj @ (r + xj * theta[j])
            new = rho / sq if j == 0 else np.sign(rho) * max(abs(rho) - lam * n, 0.0) / sq
            r -= xj * (new - theta[j])
            theta[j] = new
    return theta

