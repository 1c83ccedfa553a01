"""Fused pairwise-distance + top-k Pallas kernel ("flash kNN").

The reference's kNN predict (``heat/classification/kneighborsclassifier.py:
10-136``) materializes the full (n_query, n_train) distance matrix and then
takes a top-k — HBM traffic and capacity O(n·m). This kernel streams y-tiles
through VMEM, keeps a running per-row top-k carry in the output block, and
never writes the distance matrix: O(n·k) output, one pass over x and y.

Distances are squared euclidean computed with the MXU-friendly quadratic
expansion ``|x|² + |y|² - 2·x@yᵀ`` (same formula as
``spatial.distance._quadratic_expand``), at full f32 products, so values
— and therefore neighbor ordering — match the materializing path at
``"highest"`` precision (bit for bit on the CPU). Ties break toward the
lower index, matching ``jax.lax.top_k``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._dispatch import register_kernel

__all__ = ["nearest_neighbors", "TOPK_KERNEL"]

_INT_MAX = 2**31 - 1  # python int: jnp constants would be captured consts in kernels

TOPK_KERNEL = register_kernel(
    "topk_distance",
    fallback="fallback",
    comparator="materializing cdist + jax.lax.top_k ((n, m) distance matrix in HBM)",
    roofline="one pass over x and y, O(n·k) output — never writes the (n, m) matrix",
)


def _merge_topk(cat_d: jnp.ndarray, cat_i: jnp.ndarray, k: int):
    """k smallest (distance, index) lexicographic pairs per row.

    Gather-free (Mosaic-friendly): k rounds of min-reduce + mask-out over
    the (rows, carry+tile) concatenation. Duplicate distances are
    disambiguated by the globally-unique column index, so exactly one entry
    is retired per round and ties break toward the lower index.
    """
    out_d, out_i = [], []
    d = cat_d
    for _ in range(k):
        mval = jnp.min(d, axis=1, keepdims=True)
        is_min = d == mval
        sel = jnp.min(
            jnp.where(is_min, cat_i, jnp.int32(_INT_MAX)), axis=1, keepdims=True
        )
        out_d.append(mval)
        out_i.append(sel)
        d = jnp.where(is_min & (cat_i == sel), jnp.inf, d)
    return jnp.concatenate(out_d, axis=1), jnp.concatenate(out_i, axis=1)


def _knn_kernel(x_ref, y_ref, d_ref, i_ref, *, k: int, m: int, tile_m: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        d_ref[:] = jnp.full(d_ref.shape, jnp.inf, dtype=d_ref.dtype)
        i_ref[:] = jnp.full(i_ref.shape, _INT_MAX, dtype=i_ref.dtype)

    x = x_ref[:]
    y = y_ref[:]
    # full f32 products: at the default (one bf16 pass) the cross term's
    # error exceeds the gaps between neighbours once |x|·|y| is large
    # against their distance, and the kernel returns the wrong rows
    xy = jnp.dot(x, y.T, preferred_element_type=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    y2 = jnp.sum(y * y, axis=1)[None, :]
    tile = jnp.maximum(x2 + y2 - 2.0 * xy, 0.0)
    col = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1) + j * tile_m
    if m % tile_m:  # mask the ragged last y-tile (m is static: y.shape[0])
        tile = jnp.where(col < m, tile, jnp.inf)
    nd, ni = _merge_topk(
        jnp.concatenate([d_ref[:], tile], axis=1),
        jnp.concatenate([i_ref[:], col], axis=1),
        k,
    )
    d_ref[:] = nd
    i_ref[:] = ni


@functools.partial(jax.jit, static_argnames=("k", "tile_n", "tile_m", "interpret"))
def _knn_local(x, y, k: int, tile_n: int, tile_m: int, interpret: bool):
    n, f = x.shape
    m = y.shape[0]
    xp = jnp.pad(x, ((0, (-n) % tile_n), (0, 0)))
    yp = jnp.pad(y, ((0, (-m) % tile_m), (0, 0)))
    grid = (xp.shape[0] // tile_n, yp.shape[0] // tile_m)
    # typed index-map zeros: see _dispatch's note on x64
    xmap = lambda i, j: (i, jnp.int32(0))
    ymap = lambda i, j: (j, jnp.int32(0))
    d, i = pl.pallas_call(
        functools.partial(_knn_kernel, k=k, m=m, tile_m=tile_m),
        grid=grid,
        # the (tile_n, tile_m) distance strip, the merge's copies of it and
        # the double-buffered y-tile: inside the compiler's default 16 MiB
        # of scoped VMEM at (f = 18, k = 5) only. At the default tiles it
        # refuses f = 128 or k = 64 there ("RESOURCE_EXHAUSTED: Ran out of
        # memory in memory space vmem"), both inside the classifier's gate
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        in_specs=[
            pl.BlockSpec((tile_n, f), xmap),
            pl.BlockSpec((tile_m, f), ymap),
        ],
        out_specs=[
            pl.BlockSpec((tile_n, k), xmap),
            pl.BlockSpec((tile_n, k), xmap),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((xp.shape[0], k), jnp.float32),
            jax.ShapeDtypeStruct((xp.shape[0], k), jnp.int32),
        ],
        interpret=interpret,
    )(xp, yp)
    return d[:n], i[:n]


def nearest_neighbors(
    x: jnp.ndarray,
    y: jnp.ndarray,
    k: int,
    *,
    tile_n: int = 256,
    tile_m: int | None = None,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """k nearest reference rows for every query row, without the (n, m)
    distance matrix.

    Parameters
    ----------
    x : (n, f) queries; y : (m, f) references — single-device arrays
        (callers shard_map over a mesh for split operands).
    k : neighbors to keep (k <= m). The merge pass costs O(k*(k+tile_m))
        per tile, so the kernel is profitable for small k (<= ~64);
        callers should prefer the materializing cdist+top_k path beyond
        that (see ``KNeighborsClassifier.predict``'s gate).

    Returns
    -------
    (d2, idx) : (n, k) squared distances (ascending) and reference indices.

    ``interpret`` runs the kernel body in the pallas interpreter (parity
    tests on CPU meshes ask for it by name; nothing selects it silently).
    """
    if x.ndim != 2 or y.ndim != 2 or x.shape[1] != y.shape[1]:
        raise ValueError(f"bad operand shapes {x.shape} x {y.shape}")
    m = y.shape[0]
    if not 0 < k <= m:
        raise ValueError(f"k={k} must be in [1, {m}]")
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    tile_n = min(tile_n, max(8, x.shape[0]))
    if tile_m is None:
        # wide y-tiles amortize the merge passes; cap the (tile_n, tile_m)
        # scratch at 8MB and the y-tile at 4MB to stay inside VMEM. The
        # merge is k unrolled passes over the strip, so the program, and
        # the compiler's time over it, grow with k * tile_m (260 s at
        # k = 64 on 8192-wide tiles): hold that product where k <= 8 has it
        f = x.shape[1]
        tile_m = min(8192, (1 << 21) // tile_n, (1 << 20) // max(f, 1), (1 << 16) // k)
    tile_m = max(128, min(tile_m, max(128, m)) // 128 * 128)
    return _knn_local(x, y, k, tile_n, tile_m, interpret)
