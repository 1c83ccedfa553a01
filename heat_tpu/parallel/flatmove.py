"""Bounded-memory flat redistribution — the TPU-native Alltoallv.

A row-major reshape of a split-0 array is, in flat element order, a
*contiguous-range redistribution*: input device r owns the flat range
``[A_r, A_r + L_r)`` (its valid rows), output device d needs
``[B_d, B_d + M_d)``. The reference moves exactly these ranges with one
``Alltoallv`` (``/root/reference/heat/core/manipulations.py:1821``);
XLA's v-collective-free SPMD model instead gets a static schedule:

1. Trace time: intersect the input/output interval partitions. Each
   nonempty intersection is an edge ``(src, dst, offsets, length)``; the
   overlap graph of two interval partitions has max degree
   ``ceil(max_block/min_block) + 1``, so a greedy bipartite edge coloring
   yields that many *matchings* (Koenig's theorem bounds the optimum by
   the degree).
2. Run time (shard_map): self-edges are local slices; each color becomes
   one ``lax.ppermute`` round moving a fixed-size piece (the round's
   longest edge), masked into place with a ``dynamic_update_slice`` +
   validity window.

Per-device memory: input block + output block + one piece — O(n/P).
Traffic: each element crosses the ICI exactly once, like Alltoallv.
Rounds: 2-3 for realistic reshapes (blocks within 2x of each other).

Used by :func:`heat_tpu.core._movement.reshape_padded` for the shapes
where GSPMD's own reshape partitioner falls back to an all-gather
(non-factorizable sharded dims); proven bounded in
``tests/test_distribution_proofs.py``.
"""
from __future__ import annotations

from functools import partial
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..core import _hooks
from ..core.communication import SPLIT_AXIS, MeshCommunication

from ..core._cache import ExecutableCache


def _bounded_exchange(label: str, fn, buf: jax.Array):
    """Dispatch one interval-exchange program under the collective
    watchdog (no-op passthrough when none is installed). The fault point
    fires inside the bounded region so chaos ``timeout``/``straggler``
    faults compose with ``resilience.deadlines`` — the testable stand-in
    for a reshard that really wedges on the interconnect."""

    def dispatch():
        _hooks.fault_point(f"collective.{label}", shape=tuple(buf.shape))
        out = fn(buf)
        if _hooks.get_deadline_runner() is not None and hasattr(out, "block_until_ready"):
            # block inside the deadline, not at the caller's first use —
            # async dispatch would let a wedged program escape the watchdog
            out = out.block_until_ready()  # graftlint: host-sync
        return out

    return _hooks.guarded_call(f"flatmove.{label}", dispatch)

__all__ = [
    "flat_schedule",
    "bucket_schedule",
    "reshape_flatmove_executable",
    "reshape_via_flatmove",
    "ragged_move_executable",
    "ragged_move",
    "bucket_move_executable",
    "bucket_move",
    "strided_take_executable",
    "strided_take",
    "MOVE_STATS",
]

# Running count of dispatched interval exchanges. Tests and the ragged
# bench read (and reset) this to assert a pipeline's exchange budget —
# e.g. redistribute→elementwise→redistribute must cost exactly ONE move.
# ``bucket_moves`` sub-counts the shuffle engine's bucketed exchanges
# (every bucket move is also a ragged move for budget purposes).
# ``tree_merges``/``tree_merge_rounds`` count ``communication.tree_merge``
# dispatches and their ppermute rounds — the rounds == ceil(log2 P)
# contract the multihost tests assert.
MOVE_STATS = {
    "ragged_moves": 0,
    "bucket_moves": 0,
    "tree_merges": 0,
    "tree_merge_rounds": 0,
}


class Edge(NamedTuple):
    src: int
    dst: int
    src_off: int  # offset inside the source's local flat block
    dst_off: int  # offset inside the destination's local flat block
    length: int


def flat_schedule(
    in_counts: Sequence[int], out_counts: Sequence[int]
) -> Tuple[List[Edge], List[List[Edge]]]:
    """(self_edges, rounds): matchings covering the interval overlaps."""
    p = len(in_counts)
    a = np.concatenate([[0], np.cumsum(in_counts)])
    b = np.concatenate([[0], np.cumsum(out_counts)])
    if a[-1] != b[-1]:
        raise ValueError(f"count sums differ: {a[-1]} vs {b[-1]}")
    edges: List[Edge] = []
    d = 0
    for r in range(p):
        if in_counts[r] == 0:
            continue
        while d < p and b[d + 1] <= a[r]:
            d += 1
        dd = d
        while dd < p and b[dd] < a[r + 1]:
            lo = max(int(a[r]), int(b[dd]))
            hi = min(int(a[r + 1]), int(b[dd + 1]))
            if hi > lo:
                edges.append(Edge(r, dd, lo - int(a[r]), lo - int(b[dd]), hi - lo))
            dd += 1
    return _color(edges)


def _color(edges: List[Edge]) -> Tuple[List[Edge], List[List[Edge]]]:
    """Split self-edges off and greedy-color the rest into ppermute
    matchings (each device at most once per round as src and as dst —
    the property :func:`_tables` requires). Interval overlap graphs stay
    near Delta; general bipartite edge sets stay under 2*Delta - 1."""
    self_edges = [e for e in edges if e.src == e.dst]
    rest = [e for e in edges if e.src != e.dst]
    src_used: dict = {}
    dst_used: dict = {}
    colored: dict = {}
    for e in rest:
        c = 0
        while c in src_used.get(e.src, ()) or c in dst_used.get(e.dst, ()):
            c += 1
        src_used.setdefault(e.src, set()).add(c)
        dst_used.setdefault(e.dst, set()).add(c)
        colored.setdefault(c, []).append(e)
    rounds = [colored[c] for c in sorted(colored)]
    return self_edges, rounds


def bucket_schedule(matrix: Sequence[Sequence[int]]) -> Tuple[List[Edge], List[List[Edge]]]:
    """(self_edges, rounds) for a *bucketed* exchange — the shuffle
    engine's Alltoallv. ``matrix[r][d]`` rows travel from device ``r`` to
    device ``d``; on ``r`` the outgoing buckets sit destination-major at
    offset 0 (rows locally sorted by partition id), on ``d`` the incoming
    buckets land source-major at offset 0. Unlike :func:`flat_schedule`
    this is NOT an order-preserving interval redistribution — any
    bipartite edge set is legal; the same greedy coloring turns it into
    ppermute matchings."""
    # graftlint: host-sync - P×P schedule input, already host-side metadata
    m = np.asarray(matrix, dtype=np.int64)
    if m.ndim != 2 or m.shape[0] != m.shape[1]:
        raise ValueError(f"bucket matrix must be square, got shape {m.shape}")
    if (m < 0).any():
        raise ValueError("bucket matrix has negative counts")
    p = m.shape[0]
    src_off = np.concatenate([np.zeros((p, 1), np.int64), np.cumsum(m, axis=1)], axis=1)
    dst_off = np.concatenate([np.zeros((1, p), np.int64), np.cumsum(m, axis=0)], axis=0)
    edges = [
        Edge(r, d, int(src_off[r, d]), int(dst_off[r, d]), int(m[r, d]))
        for r in range(p)
        for d in range(p)
        if m[r, d] > 0
    ]
    return _color(edges)


def _tables(edges: List[Edge], p: int):
    soff = np.zeros(p, np.int32)
    doff = np.zeros(p, np.int32)
    dlen = np.zeros(p, np.int32)
    for e in edges:
        soff[e.src] = e.src_off
        doff[e.dst] = e.dst_off
        dlen[e.dst] = e.length
    return jnp.asarray(soff), jnp.asarray(doff), jnp.asarray(dlen)


def _exchange(
    flat,
    *,
    axis_name: str,
    p: int,
    c_out: int,
    self_edges: List[Edge],
    rounds: List[List[Edge]],
):
    """Run the colored interval exchange on a 1-D local block: self-edges
    as local dynamic slices, each color as one ``ppermute`` round. Returns
    the 1-D output block of ``c_out`` elements."""
    r = lax.axis_index(axis_name)
    max_u = max(
        [e.length for e in self_edges] + [e.length for rnd in rounds for e in rnd],
        default=1,
    )
    # guard slices/updates against clamping: widen both ends by the piece
    src = jnp.concatenate([flat, jnp.zeros((max_u,), flat.dtype)])
    out = jnp.zeros((c_out + max_u,), flat.dtype)
    idx = jnp.arange(c_out + max_u, dtype=jnp.int32)

    def write(out, piece, doff, dlen):
        tmp = lax.dynamic_update_slice(out, piece, (doff,))
        mask = (idx >= doff) & (idx < doff + dlen)
        return jnp.where(mask, tmp, out)

    if self_edges:
        u = max(e.length for e in self_edges)
        soff, doff, dlen = _tables(self_edges, p)
        piece = lax.dynamic_slice(src, (soff[r],), (u,))
        out = write(out, piece, doff[r], dlen[r])
    for rnd in rounds:
        u = max(e.length for e in rnd)
        soff, doff, dlen = _tables(rnd, p)
        piece = lax.dynamic_slice(src, (soff[r],), (u,))
        recv = lax.ppermute(piece, axis_name, [(e.src, e.dst) for e in rnd])
        out = write(out, recv, doff[r], dlen[r])
    return out[:c_out]


def _flatmove_kernel(
    x,
    *,
    axis_name: str,
    p: int,
    c_in: int,
    c_out: int,
    out_block: Tuple[int, ...],
    self_edges: List[Edge],
    rounds: List[List[Edge]],
):
    out = _exchange(
        x.reshape((c_in,)),
        axis_name=axis_name,
        p=p,
        c_out=c_out,
        self_edges=self_edges,
        rounds=rounds,
    )
    return out.reshape(out_block)


def _ragged_kernel(
    x,
    *,
    axis_name: str,
    p: int,
    split: int,
    b_out: int,
    self_edges: List[Edge],
    rounds: List[List[Edge]],
):
    """Interval exchange of whole split-axis hyperplanes: transpose the
    split axis to the front so each device's valid rows form a contiguous
    flat prefix, exchange, transpose back."""
    shape = x.shape
    outer = int(np.prod(shape[:split], dtype=np.int64)) if split else 1
    b_in = shape[split]
    inner = (
        int(np.prod(shape[split + 1 :], dtype=np.int64))
        if split + 1 < len(shape)
        else 1
    )
    unit = outer * inner
    with _hooks.phase("move"):
        flat = jnp.moveaxis(x.reshape((outer, b_in, inner)), 1, 0).reshape((b_in * unit,))
        out_flat = _exchange(
            flat,
            axis_name=axis_name,
            p=p,
            c_out=b_out * unit,
            self_edges=self_edges,
            rounds=rounds,
        )
        out = jnp.moveaxis(out_flat.reshape((b_out, outer, inner)), 0, 1)
        return out.reshape(shape[:split] + (b_out,) + shape[split + 1 :])


def reshape_flatmove_executable(
    buf_shape: Tuple[int, ...],
    dtype,
    gshape: Tuple[int, ...],
    out_shape: Tuple[int, ...],
    comm: MeshCommunication,
):
    """The cached jitted interval-exchange program for one reshape;
    `.lower()`-able (used by the distribution-proof tests)."""
    mesh = comm.mesh
    p = mesh.shape[SPLIT_AXIS]
    in_rows, out_rows = gshape[0], out_shape[0]
    in_inner = int(np.prod(gshape[1:], dtype=np.int64)) if len(gshape) > 1 else 1
    out_inner = int(np.prod(out_shape[1:], dtype=np.int64)) if len(out_shape) > 1 else 1
    cr_in = buf_shape[0] // p
    out_pshape = comm.padded_shape(tuple(out_shape), 0)
    cr_out = out_pshape[0] // p
    key = ("flatmove", tuple(buf_shape), str(dtype), tuple(gshape), tuple(out_shape), mesh)
    fn = _JIT_CACHE.get(key)
    if fn is not None:
        return fn
    in_counts = [
        max(0, min(in_rows - r * cr_in, cr_in)) * in_inner for r in range(p)
    ]
    out_counts = [
        max(0, min(out_rows - d * cr_out, cr_out)) * out_inner for d in range(p)
    ]
    self_edges, rounds = flat_schedule(in_counts, out_counts)
    in_spec = P(*([SPLIT_AXIS] + [None] * (len(buf_shape) - 1)))
    out_spec = P(*([SPLIT_AXIS] + [None] * (len(out_pshape) - 1)))
    kernel = partial(
        _flatmove_kernel,
        axis_name=SPLIT_AXIS,
        p=p,
        c_in=int(np.prod(buf_shape, dtype=np.int64)) // p,
        c_out=int(np.prod(out_pshape, dtype=np.int64)) // p,
        out_block=(cr_out,) + tuple(out_pshape[1:]),
        self_edges=self_edges,
        rounds=rounds,
    )
    prog = shard_map(
        kernel, mesh=mesh, in_specs=in_spec, out_specs=out_spec, check_vma=False
    )
    fn = _JIT_CACHE[key] = jax.jit(prog)
    return fn


def ragged_move_executable(
    buf_shape: Tuple[int, ...],
    dtype,
    split: int,
    in_counts: Sequence[int],
    out_counts: Sequence[int],
    b_out: int,
    comm: MeshCommunication,
):
    """Cached jitted program redistributing split-axis hyperplanes between
    two *arbitrary* interval partitions (the reference's ragged
    ``redistribute_`` target maps, ``/root/reference/heat/core/dndarray.py:
    1029-1233``, chained Send/Recv there — colored ``ppermute`` rounds
    here).

    Device ``r`` holds ``in_counts[r]`` valid rows at offset 0 of its
    ``buf_shape[split] // P``-row block; the output buffer has ``b_out``
    rows per device with ``out_counts[d]`` valid rows at offset 0. Counts
    may be zero or skewed; per-device memory stays O(block + piece).
    ``.lower()``-able for the distribution-proof tests.
    """
    mesh = comm.mesh
    p = mesh.shape[SPLIT_AXIS]
    in_counts = tuple(int(c) for c in in_counts)
    out_counts = tuple(int(c) for c in out_counts)
    if len(in_counts) != p or len(out_counts) != p:
        raise ValueError(f"count maps must have length {p}")
    b_in = buf_shape[split] // p
    if max(in_counts, default=0) > b_in or max(out_counts, default=0) > int(b_out):
        raise ValueError("a count exceeds its per-device block size")
    key = (
        "ragged",
        tuple(buf_shape),
        str(dtype),
        split,
        in_counts,
        out_counts,
        int(b_out),
        mesh,
    )
    fn = _JIT_CACHE.get(key)
    if fn is not None:
        return fn
    ndim = len(buf_shape)
    outer = int(np.prod(buf_shape[:split], dtype=np.int64)) if split else 1
    inner = (
        int(np.prod(buf_shape[split + 1 :], dtype=np.int64))
        if split + 1 < ndim
        else 1
    )
    unit = outer * inner
    self_edges, rounds = flat_schedule(
        [c * unit for c in in_counts], [c * unit for c in out_counts]
    )
    spec = P(*[SPLIT_AXIS if i == split else None for i in range(ndim)])
    kernel = partial(
        _ragged_kernel,
        axis_name=SPLIT_AXIS,
        p=p,
        split=split,
        b_out=int(b_out),
        self_edges=self_edges,
        rounds=rounds,
    )
    prog = shard_map(kernel, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False)
    fn = _JIT_CACHE[key] = jax.jit(prog)
    return fn


def ragged_move(
    buf: jax.Array,
    split: int,
    in_counts: Sequence[int],
    out_counts: Sequence[int],
    b_out: int,
    comm: MeshCommunication,
) -> jax.Array:
    """Move a split-``split`` padded buffer between arbitrary interval
    partitions (see :func:`ragged_move_executable`). Watchdog-bounded
    (label ``flatmove.ragged``) when ``resilience.deadlines`` is active."""
    _hooks.trace_barrier("ragged_move")
    with _hooks.span("ht.exchange:ragged_move", p=comm.size):
        fn = ragged_move_executable(
            tuple(buf.shape), buf.dtype, split, in_counts, out_counts, b_out, comm
        )
        MOVE_STATS["ragged_moves"] += 1
        return _bounded_exchange("ragged", fn, buf)


def bucket_move_executable(
    buf_shape: Tuple[int, ...],
    dtype,
    split: int,
    matrix: Sequence[Sequence[int]],
    b_out: int,
    comm: MeshCommunication,
):
    """Cached jitted program for one bucketed exchange (shuffle engine).

    Device ``r`` holds its outgoing rows destination-major at offset 0 of
    its block: ``matrix[r][d]`` split-axis rows for destination ``d``, in
    destination-rank order (the shuffle's local sort by partition id
    produces exactly this layout). The output block of device ``d`` holds
    the incoming rows source-major at offset 0 —
    ``sum(matrix[r][d] for r)`` valid rows. Reuses the ragged interval
    kernel: only the edge schedule differs (:func:`bucket_schedule`
    instead of :func:`flat_schedule`). ``.lower()``-able for the
    distribution-proof tests."""
    mesh = comm.mesh
    p = mesh.shape[SPLIT_AXIS]
    m = tuple(tuple(int(c) for c in row) for row in matrix)
    if len(m) != p or any(len(row) != p for row in m):
        raise ValueError(f"bucket matrix must be {p}x{p}")
    b_in = buf_shape[split] // p
    if max((sum(row) for row in m), default=0) > b_in:
        raise ValueError("a source's outgoing rows exceed its block size")
    if max((sum(row[d] for row in m) for d in range(p)), default=0) > int(b_out):
        raise ValueError("a destination's incoming rows exceed b_out")
    key = ("bucket", tuple(buf_shape), str(dtype), split, m, int(b_out), mesh)
    fn = _JIT_CACHE.get(key)
    if fn is not None:
        return fn
    ndim = len(buf_shape)
    outer = int(np.prod(buf_shape[:split], dtype=np.int64)) if split else 1
    inner = (
        int(np.prod(buf_shape[split + 1 :], dtype=np.int64))
        if split + 1 < ndim
        else 1
    )
    unit = outer * inner
    self_edges, rounds = bucket_schedule(
        [[c * unit for c in row] for row in m]
    )
    spec = P(*[SPLIT_AXIS if i == split else None for i in range(ndim)])
    kernel = partial(
        _ragged_kernel,
        axis_name=SPLIT_AXIS,
        p=p,
        split=split,
        b_out=int(b_out),
        self_edges=self_edges,
        rounds=rounds,
    )
    prog = shard_map(kernel, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False)
    fn = _JIT_CACHE[key] = jax.jit(prog)
    return fn


def bucket_move(
    buf: jax.Array,
    split: int,
    matrix: Sequence[Sequence[int]],
    b_out: int,
    comm: MeshCommunication,
) -> jax.Array:
    """Run one bucketed exchange (see :func:`bucket_move_executable`).
    Counted in ``MOVE_STATS`` as both a ragged move (exchange budget) and
    a bucket move (the shuffle engine's per-operand assert); watchdog-
    bounded (label ``flatmove.bucket``) when ``resilience.deadlines`` is
    active."""
    _hooks.trace_barrier("bucket_move")
    with _hooks.span("ht.exchange:bucket_move", p=comm.size):
        fn = bucket_move_executable(
            tuple(buf.shape), buf.dtype, split, matrix, b_out, comm
        )
        MOVE_STATS["ragged_moves"] += 1
        MOVE_STATS["bucket_moves"] += 1
        return _bounded_exchange("bucket", fn, buf)


def _t_interval(lo: int, hi: int, start: int, step: int, m: int) -> Tuple[int, int]:
    """Indices t in [0, m) with lo <= start + step*t < hi (t0, t1)."""
    if step > 0:
        t0 = max(0, -(-(lo - start) // step))
        t1 = min(m, (hi - 1 - start) // step + 1) if hi > start else 0
    else:
        t0 = max(0, -(-(start - (hi - 1)) // (-step)))
        t1 = min(m, (start - lo) // (-step) + 1) if start >= lo else 0
    return t0, max(t0, t1)


def _strided_kernel(
    x,
    *,
    axis_name: str,
    p: int,
    split: int,
    step: int,
    b_out: int,
    offs: Tuple[int, ...],
    self_edges: List[Edge],
    rounds: List[List[Edge]],
):
    """Local strided compaction then interval exchange: device r gathers
    its selected rows (off_r + step*k within its block) to a contiguous
    prefix, then the colored ppermute rounds redistribute the selected
    extent onto the canonical layout."""
    shape = x.shape
    outer = int(np.prod(shape[:split], dtype=np.int64)) if split else 1
    b_in = shape[split]
    inner = (
        int(np.prod(shape[split + 1 :], dtype=np.int64))
        if split + 1 < len(shape)
        else 1
    )
    unit = outer * inner
    r = lax.axis_index(axis_name)
    rows = jnp.moveaxis(x.reshape((outer, b_in, inner)), 1, 0)  # (b_in, outer, inner)
    k = jnp.arange(b_in, dtype=jnp.int32)
    idx = jnp.clip(jnp.asarray(offs, jnp.int32)[r] + step * k, 0, b_in - 1)
    compact = rows[idx]  # local gather; garbage beyond count_r is masked by the exchange
    out_flat = _exchange(
        compact.reshape((b_in * unit,)),
        axis_name=axis_name,
        p=p,
        c_out=b_out * unit,
        self_edges=self_edges,
        rounds=rounds,
    )
    out = jnp.moveaxis(out_flat.reshape((b_out, outer, inner)), 0, 1)
    return out.reshape(shape[:split] + (b_out,) + shape[split + 1 :])


def strided_take_executable(
    buf_shape: Tuple[int, ...],
    dtype,
    split: int,
    n_logical: int,
    start: int,
    stop: int,
    step: int,
    comm: MeshCommunication,
):
    """A strided slice ``[start:stop:step]`` ALONG the split axis as one
    bounded program (selected rows land on their canonical layout).
    GSPMD's partitioner all-gathers for step != 1 (the selection breaks
    the interval structure); the reference instead computes rank-local
    selections and chains sends (``dndarray.py:652-908``). Here: local
    strided gather to a contiguous prefix, then the interval-exchange
    rounds. Returns ``(fn, m)`` with ``m`` the selected extent."""
    if step <= 0:
        # t-ascending visits devices in descending order for step<0 and
        # the interval schedule assumes rank-ascending concatenation; the
        # caller composes positive-step take + flip instead
        raise ValueError("strided_take requires step > 0")
    mesh = comm.mesh
    p = mesh.shape[SPLIT_AXIS]
    m = len(range(start, stop, step))
    b_in = buf_shape[split] // p
    key = ("stake", tuple(buf_shape), str(dtype), split, n_logical, start, stop, step, mesh)
    fn = _JIT_CACHE.get(key)
    if fn is not None:
        return fn, m
    ndim = len(buf_shape)
    outer = int(np.prod(buf_shape[:split], dtype=np.int64)) if split else 1
    inner = (
        int(np.prod(buf_shape[split + 1 :], dtype=np.int64))
        if split + 1 < ndim
        else 1
    )
    unit = outer * inner
    in_counts, offs = [], []
    for r in range(p):
        lo, hi = r * b_in, min(r * b_in + b_in, n_logical)
        t0, t1 = _t_interval(lo, hi, start, step, m) if hi > lo else (0, 0)
        in_counts.append(t1 - t0)
        offs.append((start + step * t0) - lo if t1 > t0 else 0)
    b_out = max(1, -(-m // p))
    out_counts = [max(0, min(m - r * b_out, b_out)) for r in range(p)]
    self_edges, rounds = flat_schedule(
        [c * unit for c in in_counts], [c * unit for c in out_counts]
    )
    spec = P(*[SPLIT_AXIS if i == split else None for i in range(ndim)])
    kernel = partial(
        _strided_kernel,
        axis_name=SPLIT_AXIS,
        p=p,
        split=split,
        step=step,
        b_out=b_out,
        offs=tuple(offs),
        self_edges=self_edges,
        rounds=rounds,
    )
    prog = shard_map(kernel, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False)
    fn = _JIT_CACHE[key] = jax.jit(prog)
    return fn, m


def strided_take(
    buf: jax.Array,
    split: int,
    n_logical: int,
    start: int,
    stop: int,
    step: int,
    comm: MeshCommunication,
) -> Tuple[jax.Array, int]:
    """Apply :func:`strided_take_executable`; returns ``(buffer, m)``.
    Watchdog-bounded (label ``flatmove.strided``) when active."""
    fn, m = strided_take_executable(
        tuple(buf.shape), buf.dtype, split, n_logical, start, stop, step, comm
    )
    return _bounded_exchange("strided", fn, buf), m


def reshape_via_flatmove(
    buf: jax.Array,
    gshape: Tuple[int, ...],
    out_shape: Tuple[int, ...],
    comm: MeshCommunication,
) -> jax.Array:
    """Reshape a split-0 padded buffer to the split-0 padded buffer of
    ``out_shape`` with the interval-exchange kernel. Pure collective
    permutes; per-device memory O(n/P). Watchdog-bounded (label
    ``flatmove.reshape``) when ``resilience.deadlines`` is active."""
    fn = reshape_flatmove_executable(
        tuple(buf.shape), buf.dtype, tuple(gshape), tuple(out_shape), comm
    )
    return _bounded_exchange("reshape", fn, buf)


_JIT_CACHE = ExecutableCache()  # bounded LRU (round-3 ADVICE)
