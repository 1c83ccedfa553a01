"""Fused distance + argmin + centroid-update Pallas kernel (Lloyd step).

The XLA Lloyd iteration (``cluster.kmeans._assign_stats``) is two HBM
passes over the data: the fused distance+argmin pass, then — because the
argmin→one-hot dependency blocks fusion — a separate ``onehotᵀ @ X``
update matmul that re-reads X. At k=8 that matmul also drives the MXU at
8-of-128 output lanes. This kernel
streams X through VMEM ONCE, in column tiles of the transposed buffer
(rows on the lane axis — the layout the chip keeps a narrow (n, f)
buffer in, so the transpose costs nothing): distances, argmin, the
one-hot update matmul, per-cluster counts and the inertia all happen
while the tile is resident, accumulating (sums, counts, inertia) across
the sequential TPU grid. Labels leave as a lane-dense row. Centers are
padded to whole sublane groups (a multiple of 8 rows).

Roofline: one read of the (n, f) buffer + O(n) label writes per Lloyd
iteration — half the unfused path's traffic. Comparator: the fused-XLA
``_assign_stats`` program (``kmeans_floor_probe``'s decomposition floor
is the unfused treatment both beat).

Parity: distances use the same quadratic expansion as
``spatial.distance._quadratic_expand`` and ties break toward the lower
index (matching ``jnp.argmin``), so labels are bit-identical; sums and
inertia accumulate per tile, so centroids match the XLA path to float32
re-association (~1e-6 relative, the documented tolerance). Both hold
against an oracle at full f32 products, which the kernel asks for; XLA's
own default for f32 operands on the chip is one bf16 pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._dispatch import register_kernel
from .moments import MAX_FEATURES, _tile_cols

__all__ = ["lloyd_local", "lloyd_sharded", "LLOYD_KERNEL"]

_INT_MAX = 2**31 - 1

# The (kp, tile) distance and one-hot temporaries scale the tile down with
# k (see ``lloyd_local``); the resident (kp, f) centers and sums and the
# (kp, 128) counts do not shrink with it: 3 KiB of VMEM per center with
# their double buffers, 3 MiB of the default 16 at this bound. Compiled
# for v5e up to it; more centers are declined at the call boundary
# (``kernel_fits``) and take the fused-XLA path, whose update matmul has
# the MXU's lanes full at such k anyway.
MAX_CLUSTERS = 1024

LLOYD_KERNEL = register_kernel(
    "lloyd_fused",
    fallback="fallback",
    comparator="fused-XLA _assign_stats (distance pass + separate update matmul)",
    roofline="one HBM read of X per Lloyd iteration vs two unfused — bandwidth bound",
)


def kernel_fits(f: int, k: int) -> bool:
    """Whether dispatch may give an (n, f) buffer and k centers to the
    kernel (``moments.MAX_FEATURES`` is the layout bound both share)."""
    return f <= MAX_FEATURES and k <= MAX_CLUSTERS


def _lloyd_kernel(nv_ref, x_ref, c_ref, labels_ref, sums_ref, cnt_ref, in_ref,
                  *, k: int, tile_n: int):
    """One (f, tile_n) column tile: the rows of the user's buffer sit on
    the lane axis, so distances are (kp, tile_n) and the labels leave as
    a lane-dense (1, tile_n) row. ``cnt_ref`` (kp, 128) and ``in_ref``
    (1, 128) carry the same value in every lane (the wrapper reads lane
    0): Mosaic stores vectors to VMEM, not scalars."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        sums_ref[:] = jnp.zeros(sums_ref.shape, sums_ref.dtype)
        cnt_ref[:] = jnp.zeros(cnt_ref.shape, cnt_ref.dtype)
        in_ref[:] = jnp.zeros(in_ref.shape, in_ref.dtype)

    x = x_ref[:]  # (f, tile_n)
    c = c_ref[:]  # (kp, f)
    # full f32 products: the chip's default for f32 operands is one bf16
    # pass, which moves labels at near ties and rounds the summed rows
    hi = jax.lax.Precision.HIGHEST
    xc = jnp.dot(c, x, preferred_element_type=jnp.float32, precision=hi)
    x2 = jnp.sum(x * x, axis=0, keepdims=True)
    c2 = jnp.sum(c * c, axis=1, keepdims=True)
    d2 = jnp.maximum(x2 + c2 - 2.0 * xc, 0.0)  # (kp, tile_n)
    row = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
    d2 = jnp.where(row < k, d2, jnp.inf)  # padded center rows can never win
    mval = jnp.min(d2, axis=0, keepdims=True)
    # argmin with ties toward the lower index, matching jnp.argmin
    labels = jnp.min(
        jnp.where(d2 == mval, row, jnp.int32(_INT_MAX)), axis=0, keepdims=True
    )
    labels_ref[:] = labels
    col = jax.lax.broadcasted_iota(jnp.int32, (1, x.shape[1]), 1) + i * tile_n
    valid = col < nv_ref[0]
    # zero both factors for padded rows: 0-weight x garbage would be nan
    onehot = (valid & (row == labels)).astype(x.dtype)
    xs = jnp.where(valid, x, 0.0)
    sums_ref[:] += jax.lax.dot_general(
        onehot, xs, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=hi,
    )
    cnt_ref[:] += jnp.sum(onehot, axis=1, keepdims=True)
    in_ref[:] += jnp.sum(jnp.where(valid, mval, 0.0), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("k", "tile_n", "interpret"))
def _lloyd_call(xa, centers, n_valid, k: int, tile_n: int, interpret: bool):
    n, f = xa.shape
    kp = -(-k // 8) * 8  # whole sublane groups of centers
    cp = jnp.pad(centers, ((0, kp - k), (0, 0)))
    # rows on the lane axis: see moments._moments_call for why the
    # transpose is free on the chip and a (tile, f) row block is not
    xt = xa.T
    z = jnp.int32  # typed index-map zeros: see _dispatch's note on x64
    fixed = lambda i, nv: (z(0), z(0))
    labels, sums, cnt, inertia = pl.pallas_call(
        functools.partial(_lloyd_kernel, k=k, tile_n=tile_n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(n, tile_n),),
            in_specs=[
                pl.BlockSpec((f, tile_n), lambda i, nv: (z(0), i)),
                pl.BlockSpec((kp, f), fixed),
            ],
            out_specs=[
                pl.BlockSpec((1, tile_n), lambda i, nv: (z(0), i)),
                pl.BlockSpec((kp, f), fixed),
                pl.BlockSpec((kp, 128), fixed),
                pl.BlockSpec((1, 128), fixed),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((kp, f), jnp.float32),
            jax.ShapeDtypeStruct((kp, 128), jnp.float32),
            jax.ShapeDtypeStruct((1, 128), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.asarray(n_valid, jnp.int32).reshape(1), xt, cp)
    return sums[:k], cnt[:k, 0], labels[0], inertia[0, 0]


def lloyd_local(
    xa: jnp.ndarray,
    centers: jnp.ndarray,
    n_valid=None,
    *,
    tile_n: int | None = None,
    interpret: bool = False,
):
    """Fused Lloyd assignment statistics of a local (n, f) buffer.

    Returns ``(sums, counts, labels, inertia)`` with the exact contract
    of ``cluster.kmeans._assign_stats``: per-cluster sums (k, f), counts
    (k,), per-row labels (n,) int32 and the summed min-distance inertia.
    ``interpret`` runs the kernel body in the pallas interpreter (parity
    tests on CPU meshes ask for it by name; nothing selects it silently).
    """
    if xa.ndim != 2 or centers.ndim != 2 or xa.shape[1] != centers.shape[1]:
        raise ValueError(f"bad operand shapes {xa.shape} x {centers.shape}")
    xa = xa.astype(jnp.float32)
    centers = centers.astype(jnp.float32)
    if n_valid is None:
        n_valid = xa.shape[0]
    # the tallest temporaries are the (f, tile) input and the (kp, tile)
    # distances: 2^18 / f lanes failed to compile at f = 2 (the tile pads
    # to 8 sublanes) and at k = 256 ("Ran out of memory in memory space vmem")
    k = centers.shape[0]
    tile_n = _tile_cols(xa.shape[0], max(xa.shape[1], k), tile_n)
    return _lloyd_call(xa, centers, n_valid, k, tile_n, interpret)


def lloyd_sharded(
    xa,
    centers,
    n_valid,
    mesh,
    *,
    tile_n: int | None = None,
    interpret: bool = False,
):
    """Fused Lloyd assignment statistics of a split-0 sharded buffer.

    Each shard runs :func:`lloyd_local` over its rows (validity window
    derived from the shard's position and the GLOBAL ``n_valid``); sums,
    counts and inertia psum over the mesh axis, labels stay sharded.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..communication import SPLIT_AXIS

    p = mesh.devices.size
    mi = xa.shape[0] // p

    def local(xs, cs, nv_g):
        r = jax.lax.axis_index(SPLIT_AXIS)
        nv = jnp.clip(nv_g - r * mi, 0, mi)
        sums, cnt, labels, inertia = lloyd_local(
            xs, cs, nv, tile_n=tile_n, interpret=interpret
        )
        return (
            jax.lax.psum(sums, SPLIT_AXIS),
            jax.lax.psum(cnt, SPLIT_AXIS),
            labels,
            jax.lax.psum(inertia, SPLIT_AXIS),
        )

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(SPLIT_AXIS, None), P(None, None), P()),
        out_specs=(P(), P(), P(SPLIT_AXIS), P()),
        check_vma=False,  # pallas_call out_shapes carry no vma info
    )(xa, centers, jnp.asarray(n_valid, jnp.int32))
