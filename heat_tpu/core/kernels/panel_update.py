"""Blocked panel-fused Cholesky kernel (panel factor + trailing update).

The single-device blocked factorization path runs the panel factor and
the O(bs·n) trailing GEMM as separate XLA ops, round-tripping the
trailing submatrix through HBM once per panel — O(n²·nb) bytes. This
kernel keeps the (padded) matrix resident in VMEM across an unrolled
walk over panels: each step factors the bs×bs diagonal block (masked
unblocked Cholesky — no LAPACK call exists inside a Mosaic kernel),
forward-substitutes the panel below it, and applies the trailing syrk
to the static window right of and below the panel while everything is
still on-chip. HBM traffic: one read of A and one write of L, total —
the floor.

Scope: real float32, n ≤ ``MAX_FUSED_N`` (the whole matrix must fit
VMEM). The distributed (p > 1) factorization keeps the shard_map path —
its per-panel all_gather between the solve and the trailing update
cannot live inside one kernel. LU keeps the XLA path too: tournament
pivoting is collective-bound, not fusion-bound (see docs/PERFORMANCE.md).

Comparator: ``jnp.linalg.cholesky`` on the same buffer. Parity: same
factor up to float32 re-association (~1e-6 relative); non-SPD inputs
propagate NaNs like ``jnp.linalg.cholesky``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._dispatch import register_kernel

__all__ = ["cholesky_blocked", "CHOL_KERNEL", "MAX_FUSED_N"]

# (n_pad, n_pad) working copy + the input block must fit scoped VMEM
MAX_FUSED_N = 1024

CHOL_KERNEL = register_kernel(
    "chol_panel_fused",
    fallback="fallback",
    comparator="jnp.linalg.cholesky (separate XLA panel + trailing-update ops)",
    roofline="one HBM read of A + one write of L; trailing updates stay in VMEM",
)


def _i32_range(n: int):
    """Typed loop bounds: see _dispatch's note on x64."""
    return jnp.int32(0), jnp.int32(n)


def _chol_unblocked(Akk: jnp.ndarray, bs: int) -> jnp.ndarray:
    """Unblocked right-looking Cholesky of a bs×bs block, mask-based
    (no dynamic indexing — column selection via iota). Every
    intermediate stays a 2-D vector: Mosaic has no scalar sqrt or
    divide and no layout for 1-D values."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
    ridx = jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
    diag = rows == cols

    def body(j, A):
        colj = jnp.sum(jnp.where(cols == j, A, 0.0), axis=1, keepdims=True)  # (bs, 1)
        djj = jnp.sum(jnp.where(ridx == j, colj, 0.0), axis=0, keepdims=True)
        d = jnp.sqrt(djj)  # (1, 1)
        lcol = jnp.where(ridx > j, colj / d, 0.0)
        newcol = jnp.where(ridx == j, d, lcol)
        # the same column laid along lanes, by a masked reduce over the
        # diagonal embedding (no in-kernel transpose of a narrow vector)
        lrow = jnp.sum(jnp.where(diag, lcol, 0.0), axis=0, keepdims=True)  # (1, bs)
        A = jnp.where(cols == j, newcol, A)
        return A - lcol * lrow  # zero outside rows > j, cols > j

    A = jax.lax.fori_loop(*_i32_range(bs), body, Akk)
    return jnp.where(rows >= cols, A, 0.0)


def _panel_solve(Lkk: jnp.ndarray, Pm: jnp.ndarray, bs: int) -> jnp.ndarray:
    """X with ``X @ Lkkᵀ = Pm`` (forward substitution over columns,
    mask-based selection; ``Pm`` is the (m, bs) panel below the block)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
    cidx = jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    pcols = jax.lax.broadcasted_iota(jnp.int32, Pm.shape, 1)

    def body(j, X):
        lrow = jnp.sum(jnp.where(rows == j, Lkk, 0.0), axis=0, keepdims=True)  # Lkk[j, :]
        w = jnp.where(cidx < j, lrow, 0.0)
        pj = jnp.sum(jnp.where(pcols == j, Pm, 0.0), axis=1, keepdims=True)
        acc = jnp.sum(X * w, axis=1, keepdims=True)
        ljj = jnp.sum(jnp.where(cidx == j, lrow, 0.0), axis=1, keepdims=True)
        return jnp.where(pcols == j, (pj - acc) / ljj, X)

    return jax.lax.fori_loop(*_i32_range(bs), body, jnp.zeros_like(Pm))


def _chol_kernel(a_ref, L_ref, *, bs: int, n_pad: int):
    """All panels in one invocation, unrolled: every slice offset is a
    Python int, so the compiler sees static, tile-aligned windows (it
    refuses a dynamic start on the lane axis)."""
    L_ref[:] = a_ref[:]  # working copy; panels overwrite it in place
    for off in range(0, n_pad, bs):
        end = off + bs
        Lkk = _chol_unblocked(L_ref[off:end, off:end], bs)
        L_ref[off:end, off:end] = Lkk
        if off:  # panel columns are final: zeros above the block
            L_ref[:off, off:end] = jnp.zeros((off, bs), L_ref.dtype)
        if end < n_pad:
            X = _panel_solve(Lkk, L_ref[end:, off:end], bs)
            L_ref[end:, off:end] = X
            # trailing syrk, full f32 products (the chip's default for
            # f32 operands is one bf16 pass: a 1e-3 factor)
            L_ref[end:, end:] -= jax.lax.dot_general(
                X, X, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def _chol_call(a, bs: int, interpret: bool):
    n = a.shape[0]
    n_pad = -(-n // bs) * bs
    ap = jnp.pad(a, ((0, n_pad - n), (0, n_pad - n)))
    # identity-extend the padding diagonal: chol([[A, 0], [0, I]]) keeps
    # the logical factor unchanged and the padded system SPD
    idx = jnp.arange(n_pad)
    pad_diag = (idx[:, None] == idx[None, :]) & (idx[:, None] >= n)
    ap = jnp.where(pad_diag, 1.0, ap)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    L = pl.pallas_call(
        functools.partial(_chol_kernel, bs=bs, n_pad=n_pad),
        in_specs=[whole],
        out_specs=whole,
        out_shape=jax.ShapeDtypeStruct((n_pad, n_pad), jnp.float32),
        interpret=interpret,
    )(ap)
    return L[:n, :n]


def cholesky_blocked(
    a: jnp.ndarray, *, bs: int = 128, interpret: bool = False
) -> jnp.ndarray:
    """Lower Cholesky factor of a local SPD (n, n) f32 buffer via the
    panel-fused kernel (one VMEM residency for factor + trailing update).

    ``bs`` other than a multiple of 128 puts panel windows off the lane
    tiling; the interpreter takes them, the chip's compiler only when one
    panel covers the matrix. ``interpret`` runs the kernel body in the
    pallas interpreter (parity tests ask for it by name)."""
    if a.ndim != 2 or a.shape[0] != a.shape[1]:
        raise ValueError(f"cholesky_blocked expects a square 2-D buffer, got {a.shape}")
    if a.shape[0] > MAX_FUSED_N:
        raise ValueError(
            f"n={a.shape[0]} exceeds MAX_FUSED_N={MAX_FUSED_N} (matrix must fit VMEM)"
        )
    a = a.astype(jnp.float32)
    # whole sublane groups: a panel edge off the 8-row tiling is refused
    bs = max(8, min(bs, -(-a.shape[0] // 8) * 8))
    return _chol_call(a, bs, interpret)
