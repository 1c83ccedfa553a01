"""Fused one-pass Welford moments kernel (count / mean / M2 in one read).

The public two-call sequence ``ht.mean(x)`` + ``ht.std(x)`` used to read
the data three times (mean; then std's own mean + centered pass) where
a fused single-read sweep is possible (VERDICT round 5). This module is
the single-read path:

- :func:`moments_local` — a pallas kernel that streams tiles of a local
  (n, f) buffer through VMEM — column tiles of its transpose, rows on
  the lane axis, which is how the chip stores a narrow buffer — and
  Chan-merges each tile's (mean, M2) into a carried accumulator:
  exactly one HBM pass, compiled on TPU; the interpreter runs the same
  body on CPU test meshes when a parity test asks for it by name;
- :func:`chunk_moments` — the raw-jnp twin of the same dataflow
  (shifted one-pass sums, one fused XLA program, still a single read),
  the default fast path off-TPU and the building block
  ``stream.StreamingMoments``' fold and ``ht.mean``/``ht.var``/
  ``ht.std``'s moments panel dispatch through;
- :func:`moments_sharded` — shard_map wrapper combining per-shard
  moments with the parallel Chan formulas (psum of counts and
  count-weighted means, then M2 correction).

Roofline: axis-0 moments of an (n, f) f32 buffer move ``4nf`` bytes and
do O(nf) FLOPs — pure HBM bandwidth. One read is the floor. Comparator:
``jnp.mean`` + ``jnp.std`` (three reads).

Numerics: per-tile/per-chunk sums use the first valid row as a shift
(variance is shift-invariant), so M2 matches the two-pass oracle to
float32 re-association (~1e-6 relative — the documented tolerance in
the parity tests). Merging follows Chan et al., the same formulas as
``stream.estimators``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._dispatch import register_kernel

__all__ = ["chunk_moments", "moments_local", "moments_sharded", "MOMENTS_KERNEL"]

MOMENTS_KERNEL = register_kernel(
    "moments_onepass",
    fallback="xla",
    comparator="jnp.mean + jnp.std (three data reads)",
    roofline="one HBM read of the (n, f) buffer; O(nf) FLOPs — bandwidth bound",
)


def chunk_moments(xa: jnp.ndarray, n_valid):
    """(count, mean, M2) per column of a padded (n, f) buffer, one read.

    Traceable raw-jnp twin of the pallas kernel: the shifted one-pass
    sums ``s1 = Σ(x - x₀)`` and ``s2 = Σ(x - x₀)²`` fuse into a single
    XLA loop over the buffer (no dependent second pass — ``jnp.var``'s
    ``mean`` → ``mean((x - mean)²)`` chain cannot fuse). Rows at index
    ``>= n_valid`` are masked out; ``n_valid`` may be a traced scalar.
    """
    row = jax.lax.broadcasted_iota(jnp.int32, (xa.shape[0], 1), 0)
    valid = row < n_valid
    shift = xa[0:1, :]  # first row is always logically valid
    xs = jnp.where(valid, xa - shift, 0.0)
    nb = jnp.sum(valid.astype(xa.dtype))
    nb1 = jnp.maximum(nb, 1.0)
    s1 = jnp.sum(xs, axis=0)
    s2 = jnp.sum(xs * xs, axis=0)
    mean = shift[0] + s1 / nb1
    m2 = jnp.maximum(s2 - s1 * s1 / nb1, 0.0)
    return nb, mean, m2


def merge_moments(na, mean_a, m2_a, nb, mean_b, m2_b):
    """Chan pairwise combine of two (count, mean, M2) states (traceable)."""
    n = na + nb
    n1 = jnp.maximum(n, 1.0)
    delta = mean_b - mean_a
    mean = mean_a + delta * (nb / n1)
    m2 = m2_a + m2_b + delta * delta * (na * nb / n1)
    return n, mean, m2


def _moments_kernel(nv_ref, x_ref, mean_ref, m2_ref, *, tile_n: int):
    """One (f, tile_n) column tile: rows of the user's buffer sit on the
    lane axis. ``mean_ref``/``m2_ref`` are (f, 128) accumulators whose
    lanes all carry the same value (a lane-dense store; the wrapper reads
    lane 0). The running count needs no accumulator: tiles are visited in
    order, so it is ``min(i * tile_n, n_valid)``."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        mean_ref[:] = jnp.zeros(mean_ref.shape, mean_ref.dtype)
        m2_ref[:] = jnp.zeros(m2_ref.shape, m2_ref.dtype)

    nv = nv_ref[0]
    seen = jnp.minimum(i * tile_n, nv)
    here = jnp.minimum(nv - seen, tile_n)
    # scalar -> (1, 1) vectors: the scalar core has no float divide
    na = jnp.full((1, 1), seen, jnp.int32).astype(jnp.float32)
    nb = jnp.full((1, 1), here, jnp.int32).astype(jnp.float32)
    x = x_ref[:]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, x.shape[1]), 1) + i * tile_n
    valid = col < nv
    xs = jnp.where(valid, x, 0.0)
    nb1 = jnp.maximum(nb, 1.0)
    mean_b = jnp.sum(xs, axis=1, keepdims=True) / nb1
    d = jnp.where(valid, x - mean_b, 0.0)  # tile stays in VMEM: still one HBM read
    m2_b = jnp.sum(d * d, axis=1, keepdims=True)
    n1 = jnp.maximum(na + nb, 1.0)
    delta = mean_b - mean_ref[:]
    mean_ref[:] = mean_ref[:] + delta * (nb / n1)
    m2_ref[:] = m2_ref[:] + m2_b + delta * delta * (na * nb / n1)


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def _moments_call(xa, n_valid, tile_n: int, interpret: bool):
    n, f = xa.shape
    # The chip keeps a narrow (n, f) f32 buffer with n on the lane axis,
    # so the transpose is a bitcast there; a row-tiled (tile, f) block
    # would instead make XLA materialize a copy padded to 128 lanes
    # (4x the operand at f=32). The ragged last tile reads out of bounds;
    # the validity mask discards it.
    xt = xa.T
    z = jnp.int32  # typed index-map zeros: see _dispatch's note on x64
    acc = jax.ShapeDtypeStruct((f, 128), jnp.float32)
    mean, m2 = pl.pallas_call(
        functools.partial(_moments_kernel, tile_n=tile_n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(n, tile_n),),
            in_specs=[pl.BlockSpec((f, tile_n), lambda i, nv: (z(0), i))],
            out_specs=[
                pl.BlockSpec((f, 128), lambda i, nv: (z(0), z(0))),
                pl.BlockSpec((f, 128), lambda i, nv: (z(0), z(0))),
            ],
        ),
        out_shape=[acc, acc],
        interpret=interpret,
    )(jnp.asarray(n_valid, jnp.int32).reshape(1), xt)
    return jnp.asarray(n_valid, jnp.float32), mean[:, 0], m2[:, 0]


# The column tiling is free only while the chip keeps rows on the lane
# axis of the (n, f) buffer. It does while a row-major (8, 128) tiling would
# waste lanes (compiled for v5e: no copy around the kernel for f <= 120);
# from f = 124 the buffer is row-major there and ``xa.T`` becomes a copy
# of the whole operand in front of the kernel. The bound is where
# row-major would at least double the buffer; wider buffers are declined
# at the call boundary (``kernel_fits``) and take the kernel's XLA twin.
MAX_FEATURES = 64

# one (rows, tile) f32 temporary of about 1 MiB: the double-buffered input
# tile and the kernel's same-sized temporaries then stay well inside the
# compiler's default 16 MiB of scoped VMEM
_TILE_ELEMS = 1 << 18


def kernel_fits(f: int) -> bool:
    """Whether dispatch may give an (n, f) buffer to the kernel."""
    return f <= MAX_FEATURES


def _tile_cols(n: int, rows: int, tile_n: int | None) -> int:
    """Lane-axis tile, a multiple of 128 or the whole (short) axis, for a
    kernel whose tallest per-tile temporary has ``rows`` sublanes (VMEM
    holds them in whole groups of 8)."""
    if tile_n is None:
        tile_n = _TILE_ELEMS // (-(-rows // 8) * 8)
    if n <= tile_n:
        return n
    return max(128, tile_n // 128 * 128)


def moments_local(
    xa: jnp.ndarray,
    n_valid=None,
    *,
    tile_n: int | None = None,
    interpret: bool = False,
):
    """(count, mean, M2) per column of a local (n, f) buffer via the
    pallas kernel: row tiles stream through VMEM, each tile's moments
    Chan-merge into the carried accumulator — one HBM pass total.

    ``n_valid`` masks buffer tail padding (defaults to all rows).
    ``interpret`` runs the kernel body in the pallas interpreter (parity
    tests on CPU meshes ask for it by name; nothing selects it silently).
    """
    if xa.ndim != 2:
        raise ValueError(f"moments_local expects a 2-D buffer, got {xa.shape}")
    xa = xa.astype(jnp.float32)
    if n_valid is None:
        n_valid = xa.shape[0]
    tile_n = _tile_cols(xa.shape[0], xa.shape[1], tile_n)
    return _moments_call(xa, n_valid, tile_n, interpret)


def moments_sharded(xa, n_valid, mesh, *, tile_n: int | None = None, interpret: bool = False):
    """Global (count, mean, M2) of a split-0 sharded (n, f) buffer.

    Each shard runs :func:`moments_local`; the parallel Chan combine
    (psum counts and count-weighted means, then correct each shard's M2
    by its mean's distance to the global mean) runs over the mesh axis.
    ``n_valid`` is the GLOBAL logical row count; each shard derives its
    local validity window from its position.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..communication import SPLIT_AXIS

    p = mesh.devices.size
    mi = xa.shape[0] // p

    def local(xs, nv_g):
        r = jax.lax.axis_index(SPLIT_AXIS)
        nv = jnp.clip(nv_g - r * mi, 0, mi)
        cnt, mean, m2 = moments_local(xs, nv, tile_n=tile_n, interpret=interpret)
        gcnt = jax.lax.psum(cnt, SPLIT_AXIS)
        gcnt1 = jnp.maximum(gcnt, 1.0)
        gmean = jax.lax.psum(cnt * mean, SPLIT_AXIS) / gcnt1
        dm = mean - gmean
        gm2 = jax.lax.psum(m2 + cnt * dm * dm, SPLIT_AXIS)
        return gcnt, gmean, gm2

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(SPLIT_AXIS, None), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,  # pallas_call out_shapes carry no vma info
    )(xa, jnp.asarray(n_valid, jnp.int32))
