"""Iterative solvers (reference ``heat/core/linalg/solver.py``).

The reference's ``cg`` (``solver.py:13``) checks convergence on the host
every iteration and ``lanczos`` (``solver.py:68``) is an eager Python loop —
per-iteration host round-trips. Here both are **device-resident programs**:
``cg`` is one ``lax.while_loop`` with the convergence test on device, and
``lanczos`` is one ``lax.fori_loop`` — a single dispatch each, with GSPMD
inserting the matvec psums over ICI inside the loop body.

Padding discipline: the square operand is zero-extended to its padded
buffer extent on *both* axes and every Krylov vector carries a zero tail.
Zero rows/columns keep the iteration exactly in the valid subspace (the
residual's tail entries start at 0 and stay 0), so no per-iteration masking
is needed.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import _hooks
from .._operations import _mask_padding
from ..dndarray import LAYOUT_STATS, DNDarray

__all__ = ["cg", "lanczos"]


def _square_padded(A: DNDarray, ftype):
    """(n_pad, n_pad) zero-extended operand and the padded extent."""
    n = A.gshape[0]
    arr = A.larray.astype(ftype)
    if A.padded:
        arr = _mask_padding(arr, A.gshape, A.split, 0)
    n_pad = arr.shape[A.split] if A.split is not None else n
    pad = [(0, n_pad - s) for s in arr.shape]
    if any(p for _, p in pad):
        arr = jnp.pad(arr, pad)
    return arr, n, n_pad


def _padded_vector(v: DNDarray, n: int, n_pad: int, ftype):
    vec = v._logical().astype(ftype)
    if n_pad != n:
        vec = jnp.pad(vec, (0, n_pad - n))
    return vec


def cg(A: DNDarray, b: DNDarray, x0: DNDarray, out: Optional[DNDarray] = None) -> DNDarray:
    """Conjugate gradients for s.p.d. ``A`` (reference ``solver.py:13``).

    One compiled ``lax.while_loop``; convergence (``sqrt(r.r) < 1e-10``,
    the reference's threshold) is evaluated on device — no host sync until
    the final result is read.
    """
    if not isinstance(A, DNDarray) or not isinstance(b, DNDarray) or not isinstance(x0, DNDarray):
        raise TypeError(f"A, b and x0 need to be DNDarrays, got {type(A)}, {type(b)}, {type(x0)}")
    if A.ndim != 2:
        raise RuntimeError("A needs to be a 2D matrix")
    if b.ndim != 1:
        raise RuntimeError("b needs to be a 1D vector")
    if x0.ndim != 1:
        raise RuntimeError("x0 needs to be a 1D vector")

    ftype = jnp.promote_types(A.larray.dtype, jnp.float32)
    arr, n, n_pad = _square_padded(A, ftype)
    bv = _padded_vector(b, n, n_pad, ftype)
    xv = _padded_vector(x0, n, n_pad, ftype)

    with jax.default_matmul_precision("highest"):
        x = _cg_device(arr, bv, xv, n)

    res = DNDarray(x[:n], split=b.split, device=b.device, comm=b.comm)
    if out is not None:
        out.larray = res._logical()
        return out
    return res


@jax.jit
def _cg_device(arr, bv, xv, n):
    r0 = bv - arr @ xv
    state = (xv, r0, r0, jnp.dot(r0, r0), jnp.int32(0))

    def cond(s):
        _, _, _, rs, i = s
        return jnp.logical_and(rs >= 1e-20, i < n)

    def body(s):
        x, r, p, rsold, i = s
        Ap = arr @ p
        alpha = rsold / jnp.dot(p, Ap)
        x = x + alpha * p
        r = r - alpha * Ap
        rsnew = jnp.dot(r, r)
        p = r + (rsnew / rsold) * p
        return (x, r, p, rsnew, i + 1)

    x, *_ = jax.lax.while_loop(cond, body, state)
    return x


def lanczos(
    A: DNDarray,
    m: int,
    v0: Optional[DNDarray] = None,
    V_out: Optional[DNDarray] = None,
    T_out: Optional[DNDarray] = None,
) -> Tuple[DNDarray, DNDarray]:
    """Lanczos tridiagonalization of a symmetric matrix (reference
    ``solver.py:68``). Returns (V, T) with A ~= V T V^T.

    One compiled program (:func:`_lanczos_program`) for all m steps — O(1)
    dispatches where the reference paid a collective round-trip per step.
    Full re-orthogonalization is applied every step (the reference
    re-orthogonalizes conditionally); it reads the m Lanczos vectors, not
    A, and buys numerical stability. The m matvecs of A the call ran are
    the gauge ``LAYOUT_STATS["lanczos_steps"]``.
    """
    V, T, n = _lanczos_arrays(A, m, v0)
    V_dnd = DNDarray(V[:, :n].T, split=None, device=A.device, comm=A.comm)
    T_dnd = DNDarray(T, split=None, device=A.device, comm=A.comm)
    if V_out is not None:
        V_out.larray = V_dnd._logical()
        V_dnd = V_out
    if T_out is not None:
        T_out.larray = T_dnd._logical()
        T_dnd = T_out
    return V_dnd, T_dnd


def _lanczos_arrays(A: DNDarray, m: int, v0: Optional[DNDarray] = None):
    """``(V, T, n)``: the (m, n_pad) Lanczos vectors as rows, zero past
    column n, and the (m, m) tridiagonal T, as device arrays."""
    if not isinstance(A, DNDarray):
        raise TypeError(f"A needs to be a DNDarray, got {type(A)}")
    if A.ndim != 2 or A.shape[0] != A.shape[1]:
        raise RuntimeError("A needs to be a square matrix")
    m = int(m)

    ftype = jnp.promote_types(A.larray.dtype, jnp.float32)
    arr, n, n_pad = _square_padded(A, ftype)
    if v0 is None:
        v = jnp.pad(jnp.ones(n, dtype=arr.dtype) / jnp.sqrt(float(n)), (0, n_pad - n))
    else:
        v = _padded_vector(v0, n, n_pad, arr.dtype)
        v = v / jnp.linalg.norm(v)

    with jax.default_matmul_precision("highest"):
        V, T = _lanczos_program(arr, v, m=m)
    LAYOUT_STATS["lanczos_steps"] = m
    return V, T, n


def _matvec(arr, v):
    """``arr @ v`` as a float32 multiply and row sum: one read of ``arr`` a
    step in its own precision, and no matmul whose operand the compiler
    could split into bf16 parts and hoist out of the loop beside it."""
    return jnp.sum(arr * v[None, :], axis=1)


@partial(jax.jit, static_argnames="m")
def _lanczos_program(arr, v, m: int):
    """The m steps from the unit vector ``v``: the first matvec, then a
    ``fori_loop`` over the other m - 1, all of them inside
    ``ht.phase:lanczos``. Module-level jit: ``arr`` is a traced operand and
    m static, so same-shape solves share one executable."""
    with _hooks.phase("lanczos"):
        V = jnp.zeros((m, arr.shape[0]), dtype=arr.dtype).at[0].set(v)
        w = _matvec(arr, v)
        alpha = jnp.dot(w, v)
        alphas = jnp.zeros(m, dtype=arr.dtype).at[0].set(alpha)
        betas = jnp.zeros(m, dtype=arr.dtype)
        w = w - alpha * v

        def body(i, state):
            V, alphas, betas, w = state
            beta = jnp.linalg.norm(w)
            v_next = jnp.where(beta > 1e-12, w / jnp.where(beta == 0, 1.0, beta), jnp.zeros_like(w))
            # full re-orthogonalization against previous Lanczos vectors
            v_next = v_next - V.T @ (V @ v_next)
            nrm = jnp.linalg.norm(v_next)
            v_next = jnp.where(nrm > 1e-12, v_next / jnp.where(nrm == 0, 1.0, nrm), v_next)
            V = V.at[i].set(v_next)
            w2 = _matvec(arr, v_next)
            alpha = jnp.dot(w2, v_next)
            w2 = w2 - alpha * v_next - beta * V[i - 1]
            return (V, alphas.at[i].set(alpha), betas.at[i].set(beta), w2)

        V, alphas, betas, _ = jax.lax.fori_loop(1, m, body, (V, alphas, betas, w))
        T = jnp.diag(alphas) + jnp.diag(betas[1:], 1) + jnp.diag(betas[1:], -1)
    return V, T
