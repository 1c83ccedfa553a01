"""Lasso regression (reference ``heat/regression/lasso.py``).

Cyclic coordinate descent with soft thresholding. The reference's
per-feature loop issues a distributed matvec per coordinate
(``lasso.py:10-186``); here a whole fit is one jitted program, sweeps in a
``lax.while_loop`` with the convergence test on the device, a sweep a
``lax.fori_loop`` over the features. Which sweep runs is decided by the
table's shape alone (:func:`_cd_sweep` states the rule):

* a tall table (``m <= n``, ``m <= 2048``) is swept in the Gram matrix's
  space: x is read twice a program, for ``X^T y``, the column norms and
  ``X^T X`` (one all-reduce of each over the data axis), and a sweep
  touches theta and an (m, m) matrix only;
* any other table is swept over x itself with the running residual
  carried, a column read and a scalar psum (on ICI) a coordinate.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import _hooks, types
from ..core._cache import ExecutableCache
from ..core.base import BaseEstimator, RegressionMixin
from ..core.communication import collective_lockstep
from ..core.dndarray import DNDarray

__all__ = ["Lasso"]

# streaming partial_fit programs — one jitted proximal-SGD step, compiled
# once per chunk geometry and reused for every subsequent chunk
_SGD_PROGRAMS = ExecutableCache(maxsize=8)


def _sgd_program():
    """Cached jitted proximal-SGD step for :meth:`Lasso.partial_fit`.

    The optimizer is the :mod:`heat_tpu.optim` SGD passthrough (optax) at
    unit learning rate; the actual ``lr`` arrives as a traced scalar by
    pre-scaling the gradient, so changing it does not retrace. The L1
    penalty is applied as a proximal soft-threshold of ``lr * lam`` after
    the gradient step (ISTA), with coordinate 0 — the intercept column —
    left unregularized exactly like :func:`_cd_sweep`. Both therefore
    minimize the same objective ``(1/2n)||X@theta - y||^2 + lam*||theta[1:]||_1``.
    Rows past ``n_valid`` are buffer tail padding and are masked out of
    both the residual and the gradient normalization.
    """
    key = "lasso_sgd"
    prog = _SGD_PROGRAMS.get(key)
    if prog is None:
        from .. import optim

        tx = optim.sgd(1.0)

        def step(X, yv, theta, lam, lr, n_valid):
            valid = jnp.arange(X.shape[0]) < n_valid
            Xs = jnp.where(valid[:, None], X, 0.0)
            ys = jnp.where(valid, yv, 0.0)
            nv = jnp.maximum(n_valid.astype(X.dtype), 1.0)
            resid = Xs @ theta - ys
            grad = (Xs.T @ resid) / nv
            opt_state = tx.init(theta)  # stateless for sgd: pure inside jit
            updates, _ = tx.update(grad * lr, opt_state, theta)
            th = optim.apply_updates(theta, updates)
            soft = jnp.sign(th) * jnp.maximum(jnp.abs(th) - lr * lam, 0.0)
            return jnp.where(jnp.arange(th.shape[0]) == 0, th, soft)

        _SGD_PROGRAMS[key] = jax.jit(step)
        prog = _SGD_PROGRAMS[key]
    return prog


# The widest table whose sweeps run in the Gram matrix's space (``_cd_path``).
_GRAM_MAX_COLUMNS = 2048


def _cd_path(n: int, m: int) -> str:
    """Which sweep a table of ``n`` rows and ``m`` columns takes: ``"gram"``
    or ``"residual"``; see :func:`_cd_sweep` for the rule and its reasons."""
    return "gram" if m <= n and m <= _GRAM_MAX_COLUMNS else "residual"


def _cd_step(rho, lam_n, col_sq_j, j):
    """The coordinate's new value from ``rho = x_j . (r + x_j theta_j)``:
    the soft threshold, coordinate 0 (the intercept column) not
    regularized, matching the reference (``lasso.py:160-164``)."""
    soft = jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - lam_n, 0.0)
    numer = jnp.where(j == 0, rho, soft)
    return jnp.where(col_sq_j > 0, numer / jnp.maximum(col_sq_j, 1e-30), 0.0)


def _gram_sweep(X: jnp.ndarray, y: jnp.ndarray, lam):
    """The sweep in the Gram matrix's space: x is read twice here, for all
    the sweeps of a program, and a sweep carries theta alone. With
    ``r = y - X theta``, ``x_j . (r + x_j theta_j) = q_j - sum_{i != j}
    G_ji theta_i`` where ``q = X^T y`` and ``G = X^T X``.

    What decides the result's accuracy is how sums of size n are added up.
    ``q``, the column norms and the column sums ``s`` are taken by a
    multiply-reduce (written side by side: one fused pass over x), whose
    float32 partial sums stay short. The one matmul adds each output into
    a single accumulator over all rows, so it is given only sums that stay
    near sqrt(n): the off-diagonals of the *centred* columns' Gram matrix,
    ``G_ij = sum_k (x_ki - s_i/n)(x_kj - s_j/n) + s_i s_j / n``, the
    subtraction riding the matmul's own read of x. The diagonal is set to
    zero and never meets the norms. The matmul contracts the rows of x as
    x stands (a reshape, a transpose or a cast would copy the table), at
    ``HIGHEST`` precision: a float32 product.
    """
    n, m = X.shape
    lam_n = lam * n
    with _hooks.phase("moments"):
        col_sq = jnp.sum(X * X, axis=0)
        q = jnp.sum(X * y[:, None], axis=0)
        s = jnp.sum(X, axis=0)
    with _hooks.phase("gram"):
        mean = s / n
        Z = X - mean[None, :]
        C = jax.lax.dot_general(
            Z, Z, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=X.dtype,
        )
        off = jnp.where(jnp.eye(m, dtype=bool), jnp.zeros((), X.dtype), C + s[:, None] * mean[None, :])

    def body(j, th):
        # an elementwise product and a sum: a vector dot at default
        # precision may be given one bf16 pass
        rho = q[j] - jnp.sum(off[j] * th)
        return th.at[j].set(_cd_step(rho, lam_n, col_sq[j], j))

    return lambda theta: jax.lax.fori_loop(0, m, body, theta)


def _residual_sweep(X: jnp.ndarray, y: jnp.ndarray, lam):
    """The sweep over x itself: the running residual is carried beside
    theta, so a sweep costs one matvec and one column read a coordinate
    instead of a matvec a coordinate."""
    n, m = X.shape
    lam_n = lam * n
    with _hooks.phase("moments"):
        col_sq = jnp.sum(X * X, axis=0)

    def body(j, carry):
        th, r = carry
        # rho_j over the residual with feature j added back
        rho = X[:, j] @ (r + X[:, j] * th[j])
        new_tj = _cd_step(rho, lam_n, col_sq[j], j)
        r = r - X[:, j] * (new_tj - th[j])
        return (th.at[j].set(new_tj), r)

    return lambda theta: jax.lax.fori_loop(0, m, body, (theta, y - X @ theta))[0]


def _cd_sweep(X: jnp.ndarray, y: jnp.ndarray, lam):
    """The sweep of a program over ``(X, y)``: a function ``theta -> theta``
    that runs one full cyclic coordinate-descent sweep (all features).
    Ask for it once a program, outside the sweeps' loop: what no sweep
    changes is computed here.

    The two sweeps give the same iterates, coordinate by coordinate, and
    share :func:`_cd_step` and nothing else (they want different state:
    theta alone, theta and a residual). Which one runs is decided by the
    table's shape, static under ``jit``, and by nothing else
    (:func:`_cd_path`):

    * ``m <= n`` and ``m <= 2048`` (:data:`_GRAM_MAX_COLUMNS`):
      :func:`_gram_sweep` (glmnet's "covariance updates", scikit-learn's
      ``precompute``). A program reads x twice and a sweep costs m^2.
      The bound on ``m`` is what the build may cost: on a v5e, for an x
      of 4 GB, a one-sweep fit takes 43 / 76 / 146 / 283 ms at m = 512 /
      1024 / 2048 / 4096 (it grows with m, the MXU's work) against 86 to
      108 ms over x itself (chip run, PR 34), so up to 2048 columns the
      second sweep has paid the build back at the latest (up to 1024, the
      first), and from 4096 it costs three sweeps and more. At 2048 the
      matrix is 16 MiB and the compiler keeps it in the chip's fast
      memory beside the loop (it does so up to 64 MiB, m = 4096, and not
      at 8192).
    * otherwise :func:`_residual_sweep`: every sweep reads x. The only
      path for a wide table (``m > n``), where the Gram matrix is larger
      than x and costs more than the sweeps it saves.
    """
    return _SWEEPS[_cd_path(*X.shape)](X, y, lam)


_SWEEPS = {"gram": _gram_sweep, "residual": _residual_sweep}


@jax.jit
def _cd_fit(X: jnp.ndarray, y: jnp.ndarray, theta: jnp.ndarray, lam, tol, max_iter):
    """Whole fit as ONE device program: sweeps inside a ``lax.while_loop``
    with the convergence test on device — a single dispatch and a single
    host fetch, like the device-resident cg/lanczos solvers (the eager
    loop fetched ``diff`` to host every sweep: a device→host sync per
    step). Returns (theta, n_iter)."""
    sweep = _cd_sweep(X, y, lam)

    def cond(carry):
        i, _, diff = carry
        return jnp.logical_and(i < max_iter, diff >= tol)

    def body(carry):
        i, th, _ = carry
        nt = sweep(th)
        return (i + 1, nt, jnp.max(jnp.abs(nt - th)))

    with _hooks.phase("sweep"):  # the sweeps' loop, and in it each sweep's over the columns
        i, th, _ = jax.lax.while_loop(
            cond, body, (jnp.int32(0), theta, jnp.asarray(jnp.inf, theta.dtype))
        )
    return th, i


@jax.jit
def _cd_block(X, y, theta, lam, tol, budget, diff0):
    """One bounded chunk of :func:`_cd_fit`: up to ``budget`` sweeps with
    the convergence ``diff`` carried in/out, so chained chunks execute
    exactly the whole-fit sweep sequence. This is the supervised-fit unit
    — the chunk boundary is where a supervisor checkpoints ``theta`` and
    recovers from faults. Returns (theta, sweeps_done, diff)."""
    sweep = _cd_sweep(X, y, lam)

    def cond(carry):
        i, _, diff = carry
        return jnp.logical_and(i < budget, diff >= tol)

    def body(carry):
        i, th, _ = carry
        nt = sweep(th)
        return (i + 1, nt, jnp.max(jnp.abs(nt - th)))

    with _hooks.phase("sweep"):
        i, th, diff = jax.lax.while_loop(cond, body, (jnp.int32(0), theta, diff0))
    return th, i, diff


class Lasso(BaseEstimator, RegressionMixin):
    """L1-regularized linear regression via coordinate descent (reference
    ``lasso.py:10``).

    Parameters: ``lam`` (L1 weight), ``max_iter``, ``tol``. An intercept
    column of ones is expected in x, matching the reference's usage.
    """

    def __init__(self, lam: float = 0.1, max_iter: int = 100, tol: float = 1e-6):
        self.lam = lam
        self.max_iter = max_iter
        self.tol = tol
        self.__theta = None
        self.n_iter = None

    @property
    def coef_(self) -> Optional[DNDarray]:
        return None if self.__theta is None else self.__theta[1:]

    @property
    def intercept_(self) -> Optional[DNDarray]:
        return None if self.__theta is None else self.__theta[0]

    @property
    def theta(self) -> Optional[DNDarray]:
        return self.__theta

    def soft_threshold(self, rho):
        """Soft thresholding operator (reference ``lasso.py``)."""
        lam = self.lam
        if isinstance(rho, DNDarray):
            import jax.numpy as jnp

            r = rho._logical()
            out = jnp.sign(r) * jnp.maximum(jnp.abs(r) - lam, 0.0)
            return DNDarray(out, split=rho.split, device=rho.device, comm=rho.comm)
        return jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - lam, 0.0)

    def rmse(self, gt: DNDarray, yest: DNDarray) -> float:
        """Root mean squared error (reference ``lasso.py``)."""
        diff = gt._logical().ravel() - yest._logical().ravel()
        return float(jnp.sqrt(jnp.mean(diff * diff)))

    def state_dict(self) -> dict:
        """Fitted + hyper state as plain host values."""
        d = {"lam": self.lam, "max_iter": self.max_iter, "tol": self.tol,
             "n_iter": self.n_iter}
        if self.__theta is not None:
            d["theta"] = self.__theta.numpy()
        return d

    def load_state_dict(self, d: dict, comm=None) -> "Lasso":
        """Restore :meth:`state_dict` output onto the current mesh."""
        self.lam = float(d["lam"])
        self.max_iter = int(d["max_iter"])
        self.tol = d["tol"]
        self.n_iter = d.get("n_iter")
        th = d.get("theta")
        self.__theta = None if th is None else DNDarray(th, split=None, comm=comm)
        return self

    def _fit_supervised(self, x: DNDarray, y: DNDarray, supervisor, block_iters: int):
        """Drive coordinate descent as a supervised step loop: one step =
        one jitted chunk of up to ``block_iters`` sweeps (see
        :func:`_cd_block`); the supervisor checkpoints ``theta`` at chunk
        boundaries and recovers per its fault policy."""
        if block_iters < 1:
            raise ValueError(f"block_iters must be >= 1, got {block_iters}")
        max_iter = self.max_iter
        tol = float(self.tol)
        X0 = x._logical().astype(jnp.promote_types(x.larray.dtype, jnp.float32))
        m = X0.shape[1]
        state = {
            "theta": DNDarray(jnp.zeros((m, 1), X0.dtype), split=None,
                              device=x.device, comm=x.comm),
            "diff": float("inf"),
            "n_iter": 0,
        }

        def step_fn(st, data, step):
            xd, yd = data
            X = xd._logical().astype(jnp.promote_types(xd.larray.dtype, jnp.float32))
            Y = yd._logical().astype(X.dtype).ravel()
            theta = st["theta"].larray.astype(X.dtype).ravel()
            budget = min(block_iters, max_iter - st["n_iter"])
            th, sweeps, diff = _cd_block(
                X, Y, theta,
                jnp.asarray(self.lam, X.dtype),
                jnp.asarray(tol, X.dtype),
                jnp.int32(budget),
                jnp.asarray(st["diff"], X.dtype),
            )
            diff_val = float(_hooks.fetch(diff, "lasso.diff"))
            new = dict(st)
            new["theta"] = DNDarray(th.reshape(-1, 1), split=None,
                                    device=xd.device, comm=xd.comm)
            new["diff"] = diff_val
            new["n_iter"] = st["n_iter"] + int(_hooks.fetch(sweeps, "lasso.sweeps"))
            return new, diff_val < tol or new["n_iter"] >= max_iter

        result = supervisor.run(step_fn, state, data=(x, y), label="lasso.fit")
        final = result.state
        self.n_iter = int(final["n_iter"])
        self.__theta = final["theta"]
        return self

    @_hooks.public_call("Lasso.fit")
    def fit(self, x: DNDarray, y: DNDarray, supervisor=None,
            block_iters: int = 16) -> "Lasso":
        """reference ``lasso.py:fit``; with ``supervisor`` the fit runs as
        a self-healing supervised step loop."""
        if not isinstance(x, DNDarray) or not isinstance(y, DNDarray):
            raise TypeError(f"input needs to be DNDarrays, but were {type(x)}, {type(y)}")
        if x.ndim != 2:
            raise ValueError(f"x needs to be 2D, but was {x.ndim}D")
        n, m = x.shape
        _hooks.observe("lasso.path", path=_cd_path(n, m), rows=n, columns=m)
        if supervisor is not None:
            return self._fit_supervised(x, y, supervisor, block_iters)
        X = x._logical().astype(jnp.promote_types(x.larray.dtype, jnp.float32))
        # reshape, not ravel: a 1-D y is then handed on as it is, where ravel's
        # jitted program copied it (40 MB a call at 1e7 rows)
        Y = y._logical().astype(X.dtype).reshape(-1)
        # the start and the three scalars go in as host values of the program's
        # dtypes: the jitted call places them itself, where four eager ops
        # before it cost 1.9 ms a fit with the device idle (PERF.md, PR 33)
        scalar = X.dtype.type
        theta, n_iter = _cd_fit(
            X,
            Y,
            np.zeros(X.shape[1], dtype=X.dtype),
            scalar(self.lam),
            scalar(self.tol),
            np.int32(self.max_iter),
        )
        self.n_iter = int(_hooks.fetch(n_iter, "lasso.n_iter"))
        self.__theta = DNDarray(theta.reshape(-1, 1), split=None, device=x.device, comm=x.comm)
        return self

    def partial_fit(self, x: DNDarray, y: DNDarray, lr: float = 0.01) -> "Lasso":
        """One proximal-SGD step on a single chunk (streaming fit).

        Feed row-block chunks (e.g. from a
        :class:`~heat_tpu.stream.chunked.ChunkIterator`, optionally behind
        a :class:`~heat_tpu.stream.prefetch.Prefetcher`) and the model
        converges to the same L1 objective the batch :meth:`fit` solves by
        coordinate descent — see :func:`_sgd_program`. The step runs on the
        PADDED device buffers so every full-size chunk reuses one compiled
        program (0 traces / 0 compiles warm); the valid row count masks the
        tail. ``theta`` persists across calls (and across a prior
        :meth:`fit`), so interleaving or resuming is fine.
        """
        if not isinstance(x, DNDarray) or not isinstance(y, DNDarray):
            raise TypeError(f"input needs to be DNDarrays, but were {type(x)}, {type(y)}")
        if x.ndim != 2:
            raise ValueError(f"x needs to be 2D, but was {x.ndim}D")
        X = x.larray.astype(jnp.promote_types(x.larray.dtype, jnp.float32))
        n_pad, m = X.shape
        if y.split == x.split and y.split is not None:
            # same axis-0 padding as x — use the padded buffer directly
            yv = y.larray.astype(X.dtype).reshape(y.larray.shape[0], -1)[:, 0]
            if yv.shape[0] != n_pad:
                raise ValueError(
                    f"y padded rows {yv.shape[0]} != x padded rows {n_pad}"
                )
        else:
            yv = y._logical().astype(X.dtype).ravel()
            if yv.shape[0] != x.gshape[0]:
                raise ValueError(f"y has {yv.shape[0]} rows, x has {x.gshape[0]}")
            if yv.shape[0] < n_pad:  # masked anyway; pad to the buffer shape
                yv = jnp.pad(yv, (0, n_pad - yv.shape[0]))
        if self.__theta is None:
            theta = jnp.zeros(m, dtype=X.dtype)
        else:
            theta = self.__theta.larray.astype(X.dtype).ravel()
            if theta.shape[0] != m:
                raise ValueError(f"x has {m} features, fitted theta has {theta.shape[0]}")
        theta = collective_lockstep(
            _sgd_program()(
                X,
                yv,
                theta,
                jnp.asarray(self.lam, X.dtype),
                jnp.asarray(lr, X.dtype),
                jnp.int32(x.gshape[0]),
            )
        )
        self.n_iter = (self.n_iter or 0) + 1
        self.__theta = DNDarray(theta.reshape(-1, 1), split=None, device=x.device, comm=x.comm)
        return self

    def predict(self, x: DNDarray) -> DNDarray:
        """reference ``lasso.py:predict``"""
        if self.__theta is None:
            raise RuntimeError("fit needs to be called before predict")
        out = x._logical() @ self.__theta._logical()
        return DNDarray(out, split=x.split, device=x.device, comm=x.comm)
