"""Sort-based distributed shuffle engine — the frame layer's substrate.

A shuffle moves every row to the device that owns its key, so that any
per-key computation (groupby aggregation, hash join, value counts)
becomes device-local afterwards. MPI frameworks express this as one
``Alltoallv`` with data-dependent bucket sizes; XLA programs need static
shapes, so the TPU-native formulation splits the same work into three
cached jitted programs plus ONE bounded bucketed exchange per operand:

1. **plan** (one program): locally sort rows by key (pads last) with
   the value columns carried as operands of the sort, fold each run of
   equal keys into its last row with a segmented scan — per-shard
   *partials*, at most one row per distinct local key, the combiner that
   makes low cardinality cheap — elect range splitters from per-shard
   key samples via one ``all_gather`` (replicated by construction —
   every device computes identical splitters, the sample-sort election),
   give each run's last row its destination partition, bring those rows
   to the front with their totals, each destination's contiguous and in
   key order, and ``all_gather`` the per-destination counts into the
   replicated P×P bucket matrix.
2. **exchange**: the host materializes the (tiny) bucket matrix — the
   same bounded host sync ``redistribute_`` performs for its target
   map — and dispatches :func:`heat_tpu.parallel.flatmove.bucket_move`
   once per operand column: colored ``ppermute`` matchings, counted in
   ``MOVE_STATS``, watchdog-bounded. No per-key traffic, ever.
3. **merge** (one program): the same three steps on the received
   partials — sort by key carrying them, scan with each statistic's
   combiner (sums add, counts add, mins min, maxs max), run ends to the
   front — legal because every statistic carried here is associative
   and commutative, the same contract as
   :class:`heat_tpu.stream.StreamingMoments.merge`.

No program moves a block-long column through an index vector: on a v5e a
gather or scatter of a 1e8-row column ran at 0.21 GB/s, bound by how the
chip executes indexed access and not by its memory, and the twelve of
them in the plan were 87 % of a 17.9 s groupby (PERF.md §6, PR 25). A
column moves as an operand of a sort the program runs anyway
(:func:`_carry_sort`) and equal keys fold by comparing neighbours
(:func:`_fold_runs`). Nor does a program sort where the order is there
already: rows that carry a one-bit "keep" and stand in the order the
result wants (a filter's, a join's matched left rows, a sorted block's
run ends) are shifted to the front in at most ⌈log2 block⌉ elementwise
passes (:func:`_compact_front`; a sort took 0.14 s an operand at 1e8
rows, 140 passes' worth, PERF.md §6 PR 32), and only a partition into
several destinations that interleave is a sort on a small leading key
(:func:`_partition_front`, :func:`_ends_first`).
A join matches the same way: both sides sorted together, the right row
first in each run of equal keys, its values carried along the run
(:func:`_scan_runs`). A sort costs by the operand, so that one carries
no more than it must: a row is one side's, and both sides' payloads
share operands width by width (:func:`_join_operands`); the row's place
is the second key, which makes the order a stable sort's without the
index operand one would add, and says which side the row is of. That
sort is the only key order a join needs, so
the co-partitioning before it makes none: a row partition is one stable
sort by destination, and rows arrive grouped by destination, in their
source's own order within one. On a mesh of one device there is nothing
to bring together and the join program reads the callers' buffers as
they stand: no election, no partition, no bucket move. The one indexed
access left is the election's 32 samples.

Partition decisions are REPLICATED at every step: splitters come out of
an ``all_gather`` inside the program, bucket matrices are identical on
every process (same program, same inputs), and the host-side schedule is
derived from those replicated values only — lockstep-clean at ws>1 by
construction, no rank ever branches on local state.

Program caching: plan/merge/join programs are keyed by (shape, dtypes,
statistics, partition mode, mesh) — all data-independent — so a warm
repeat is 0 traces / 0 compiles (Region-asserted in tests and bench).
The exchange program is keyed by the bucket matrix (data-dependent, like
the ragged redistribute it generalizes): repeated shuffles of the same
data replay cached executables end to end.

Key semantics: keys order by ``lax.sort``'s total order (NaN sorts
last; each NaN is its own group since NaN != NaN — pass integer keys
for pandas-like grouping). ``-0.0`` and ``0.0`` hash identically.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..core import _hooks
from ..core._cache import ExecutableCache
from ..core.communication import SPLIT_AXIS, MeshCommunication, collective_lockstep
from ..core.dndarray import DNDarray
from ..parallel.flatmove import bucket_move

__all__ = [
    "SHUFFLE_STATS",
    "shard_counts",
    "groupby_reduce",
    "shuffle_rows",
    "hash_join",
    "compact_rows",
    "STAT_COMBINE",
]

# one entry per (geometry, dtypes, stats, mode) — warm shuffles replay
_PROGRAMS = ExecutableCache(maxsize=128)

# running counters: tests and bench read these alongside MOVE_STATS to
# assert the engine's exchange budget and cache behavior; "row_shuffles"
# counts the sides a join really partitioned and exchanged (2 a join on
# a mesh, 0 on one device); "bucket_skew" is a gauge, not a count: the
# fullest destination's rows over the mean of the last row shuffle (1.0 =
# even; what the election achieved: every receive block is that long,
# rounded up by ``_receive_rows``); "compact_steps" is a gauge too: the
# passes ``_compact_front`` ran in the last join, filter or groupby on the
# shard that ran most (the bit length of the most rows dropped ahead of a
# kept one; 0 where nothing was), read from the counts fetched anyway;
# "join_sort_operands" is a gauge as well: the operands the last join's one
# sort carried (the key, the row's place, and of each byte width as many
# payload operands as the side with more columns of it has), known when
# its program is built; a stable sort's would count one more, the index
# the compiler adds (PERF.md counts a sort's cost by them)
SHUFFLE_STATS = {
    "groupbys": 0, "joins": 0, "compactions": 0, "row_shuffles": 0, "bucket_skew": 1.0, "compact_steps": 0,
    "join_sort_operands": 0,
}

# how each statistic kind folds in the merge stage (all associative)
STAT_COMBINE = {"sum": "sum", "sumsq": "sum", "count": "sum", "min": "min", "max": "max"}

# splitter-election oversampling per shard (sample-sort: s samples per
# shard bound the heaviest partition by ~n/P * (1 + 1/s))
_OVERSAMPLE = 32


def shard_counts(col: DNDarray) -> Tuple[int, ...]:
    """Per-shard valid-row counts of a split-0 column — ``lcounts`` for a
    ragged layout, the canonical ceil-div map otherwise. Pure metadata."""
    if col.lcounts is not None:
        return tuple(int(c) for c in col.lcounts)
    counts, _, _ = col.comm.counts_displs_shape(col.gshape, 0)
    return tuple(int(c) for c in counts)


# --------------------------------------------------------------- kernel pieces
def _max_key(dtype) -> np.ndarray:
    dt = np.dtype(dtype)
    if dt.kind == "f":
        return np.asarray(np.inf, dt)
    if dt.kind == "b":
        return np.asarray(True)
    return np.asarray(np.iinfo(dt).max, dt)


def _last_key(dtype) -> np.ndarray:
    """A key no valid key sorts after: NaN for floats (``lax.sort`` puts
    every NaN, as one class, behind +inf), the maximum otherwise."""
    dt = np.dtype(dtype)
    return np.asarray(np.nan, dt) if dt.kind == "f" else _max_key(dt)


def _carry_sort(leads, cols, stable: bool):
    """``lax.sort`` by the ``leads`` alone, with ``cols`` riding as the
    sort's own operands: no permutation comes out and no column goes
    through one (an indexed read of a 1e8-row column ran at 0.21 GB/s on
    a v5e, PERF.md §6 PR 25). Returns the sorted leads and cols, in that
    order."""
    ops = [*leads, *cols]
    with _hooks.phase("sort"):
        out = lax.sort(
            [o.astype(jnp.int8) if o.dtype == jnp.bool_ else o for o in ops],
            num_keys=len(leads), is_stable=stable,
        )
        return [r.astype(o.dtype) for r, o in zip(out, ops)]


def _sort_by_key(keys, n, payloads):
    """Stable local sort of a block's first ``n`` rows by key (lax.sort's
    total order: NaN last), the payload columns carried. The pads sit
    behind the valid rows already, so giving them the last key keeps them
    there, behind a valid row with that very key too: the rows valid
    after the sort are again the first ``n``. Returns (sorted_keys,
    sorted_payloads); the pads' keys come back as the last key."""
    valid = lax.iota(jnp.int32, keys.shape[0]) < n
    k = jnp.where(valid, keys, jnp.asarray(_last_key(keys.dtype)))
    sk, *spay = _carry_sort([k], payloads, stable=True)
    return sk, spay


def _partition_front(dest, cols):
    """Stable partition: rows in ascending ``dest`` (a small integer a
    row), their order kept within each value. Returns the moved cols."""
    return _carry_sort([dest], cols, stable=True)[1:]


def _ends_first(pid, p: int, sk, totals):
    """Each destination's group totals contiguous and in key order, ahead
    of every row that is no group's (``pid == p``), where the run ends'
    key order is NOT their (destination, key) order: hash mode on a mesh,
    whose destinations interleave along the keys. (Everywhere else the run
    ends stand in that order already and :func:`_compact_front` moves
    them.) Among the run ends of one destination the keys differ, so
    (pid, key) orders them fully and the sort needs no stability, which
    spares the index operand a stable one carries. Returns (keys,
    *totals)."""
    pid = pid.astype(jnp.int8 if p <= jnp.iinfo(jnp.int8).max else jnp.int32)
    return _carry_sort([pid, sk], totals, stable=False)[1:]


def _hash_pid(keys, p: int):
    """Destination partition of each key under multiplicative hashing.
    Equal keys (incl. -0.0 vs 0.0) always land on the same partition."""
    if jnp.issubdtype(keys.dtype, jnp.floating):
        z = jnp.where(keys == 0, jnp.zeros_like(keys), keys)
        if keys.dtype == jnp.float64:
            bits = lax.bitcast_convert_type(z, jnp.uint64).astype(jnp.uint32)
        else:
            bits = lax.bitcast_convert_type(z.astype(jnp.float32), jnp.uint32)
    elif keys.dtype == jnp.bool_:
        bits = keys.astype(jnp.uint32)
    else:
        bits = keys.astype(jnp.uint32)
    h = (bits * jnp.uint32(2654435761)) ^ (bits >> jnp.uint32(13))
    return (h % jnp.uint32(p)).astype(jnp.int32)


def _range_pid(keys, splitters):
    """Destination partition under elected range splitters (sorted,
    length P-1): equal keys compare identically so they co-locate, and
    partitions cover contiguous key ranges in rank order."""
    k = keys.astype(jnp.int8) if keys.dtype == jnp.bool_ else keys
    s = splitters.astype(k.dtype) if splitters.dtype != k.dtype else splitters
    # one comparison a splitter: the binary search would read the P-1
    # splitters through a block-long index vector
    return jnp.searchsorted(s, k, side="right", method="compare_all").astype(jnp.int32)


def _sample_ranks(n, b: int):
    """The ranks at which a shard's ``n`` sorted keys are sampled for the
    election: ``(i * n) // _OVERSAMPLE`` for each sample ``i``, evenly
    spaced from the smallest key up, clipped into its block of ``b``
    rows. The product is never formed: at 31 samples times 7e7 rows it
    leaves int32. A shard of fewer keys than samples repeats some; only
    an empty one has none to give (rank 0 is not below ``n``)."""
    with _hooks.phase("elect"):
        i = lax.iota(jnp.int32, _OVERSAMPLE)
        ranks = i * (n // _OVERSAMPLE) + (i * (n % _OVERSAMPLE)) // _OVERSAMPLE
        return jnp.clip(ranks, 0, b - 1)


def _splitters(samples, p: int):
    """Range splitters from every shard's key samples (a shard with no
    keys gives the max key 32 times, so an empty shard does not skew the
    splitters downward): one all_gather, sort, take the P-1 quantiles.
    Replicated by construction — every device computes the same values."""
    with _hooks.phase("elect"):
        gs = jnp.sort(lax.all_gather(samples, SPLIT_AXIS, tiled=True))
        return gs[(jnp.arange(1, p) * gs.shape[0]) // p]


# how a row takes in the row ``d`` before it in its run: ``op(own, before)``
_COMBINE = {
    "sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum,
    "first": lambda own, before: before,
}


def _scan_runs(sk, n, cols, combiners):
    """Inclusive scan of each column within the runs of equal keys among a
    sorted block's first ``n`` rows, ``_COMBINE[combiners[j]]`` on
    ``cols[j]``: afterwards the last row of a run holds the run's total
    (``"first"``: every row of a run holds what its first row held).
    Returns (keys, scanned columns).
    Rows i and i-d belong to one group exactly when their keys are equal
    (the block is sorted), so doubling d folds a run of length L in
    ceil(log2 L) elementwise passes, and once no pair at distance d is
    equal none is at 2d: the loop reads its length from the keys, on the
    device. Each step's shift is static (a pad, fused into the pass). The
    scan looks backwards only: what the pads behind the valid rows hold
    never reaches a valid row. A NaN equals nothing, itself included, and
    stays its own group."""
    b = sk.shape[0]
    ops = [_COMBINE[c] for c in combiners]

    def step(d: int):
        def back(x):  # row i reads row i - d
            return lax.pad(x, jnp.zeros((), x.dtype), [(d, -d, 0)])

        def fold(cs):
            i = lax.iota(jnp.int32, b)
            same = (sk == back(sk)) & (i >= d) & (i < n)
            out = tuple(jnp.where(same, op(c, back(c)), c) for op, c in zip(ops, cs))
            return jnp.any(same), out

        return fold

    steps = [step(1 << s) for s in range((b - 1).bit_length())]

    def body(carry):
        s, _, cs = carry
        go, cs = lax.switch(s, steps, cs)
        return s + 1, go, cs

    totals = tuple(cols)
    with _hooks.phase("scan"):  # the steps are traced where the loop is
        if steps and totals:
            totals = lax.while_loop(
                lambda c: c[1] & (c[0] < len(steps)), body, (jnp.int32(0), jnp.bool_(True), totals)
            )[2]
        # what the caller derives from the sorted keys next (run ends, destinations)
        # waits behind this barrier for the scan, and so is not held alive across it
        return lax.optimization_barrier((sk, totals))


# columns :func:`_compact_front` moves in one loop: a loop holds its columns twice beside the word
# that steers it, whatever the table's width. At question 2's shapes on a v5e the compaction took
# 648 / 622 / 609 / 596 ms in loops of 2 / 3 / 4 / 6 columns (each loop reads the word again), and a
# join of three columns held 3.77 / 5.02 columns of temporaries at 2 / 3 (2.51 with the sort this
# replaced; PERF.md §6, PR 32)
_COMPACT_GROUP = 2


def _compact_front(keep, cols):
    """The rows that ``keep`` marks, moved to the front of every column in
    the order they have (a stable compaction), without a sort and without
    an index: returns (columns, steps). What stands behind the kept rows
    afterwards is unspecified (some of the rows that were there before);
    every caller hands on the number of kept rows and nothing reads past
    it.
    A kept row has to move ``d`` rows forward, ``d`` the rows dropped
    before it (one cumulative sum). In step s = 0, 1, 2, ... every row
    whose ``d`` has bit s set moves 2^s rows: row i takes row i + 2^s,
    ``d`` with it, if that row moves, and becomes a hole (``d`` = 0: it
    moves no more) if only its own does. Lowest bit first no two kept
    rows ever meet (Hacker's Delight 7-4, "compress"), every kept row
    ends at i - d, and the steps needed are the bit length of the largest
    ``d``, read on the device: at most that of the block's length, 0
    where nothing is dropped ahead of a kept row. Each step is one
    elementwise pass whose shift is static (a pad, fused into the pass),
    as in :func:`_scan_runs`.
    Which rows take in which step does not depend on what they hold: one
    loop over ``d`` alone writes it down, bit s of ``takes`` for step s,
    and the columns follow it in loops of their own, ``_COMPACT_GROUP`` at
    a time, so that no loop holds more than that many columns twice."""
    b = keep.shape[0]
    cols = tuple(cols)
    widths = [1 << s for s in range((b - 1).bit_length())]
    if not widths or not cols:
        return list(cols), jnp.int32(0)
    with _hooks.phase("compact"):
        d = jnp.where(keep, lax.iota(jnp.int32, b) + 1 - jnp.cumsum(keep.astype(jnp.int32)), 0)
        steps = 32 - lax.clz(jnp.max(d))

    def ahead(x, w: int):  # row i reads row i + w
        return lax.pad(x, jnp.zeros((), x.dtype), [(-w, w, 0)])

    def halves(step):
        """``step(w, x)`` as a loop round's two switches, asked by the round ``r``: its first step
        (2r: the even widths) and its second (2r + 1: the odd ones and, where the count of steps
        is odd and ends before it, an entry that moves nothing). A round's halves are two
        instructions whatever is done, so each holds only the widths it can be asked for."""
        even = [partial(step, w) for w in widths[0::2]]
        odd = [*(partial(step, w) for w in widths[1::2]), lambda x: x]
        return (
            lambda r, x: lax.switch(r, even, x),
            lambda r, x: lax.switch(jnp.where(2 * r + 1 < steps, r, len(odd) - 1), odd, x),
        )

    def rounds(first, second, x):
        """Steps 0 .. ``steps`` - 1, two a loop round: the first writes beside the loop's carry and
        the second back into it, where one a round would copy every column back into the carry
        each time."""
        def body(carry):
            r, x = carry
            return r + 1, second(r, first(r, x))

        return lax.while_loop(lambda c: 2 * c[0] < steps, body, (jnp.int32(0), x))[1]

    def shifted(w, d):
        arriving = ahead(d, w)
        return jnp.where((arriving & w) != 0, arriving, jnp.where((d & w) != 0, 0, d))

    def noting(half, second: int):
        # after step s bit s of a row's d is set exactly where the row arrived in it: one that
        # stayed had it clear (as all have after the entry that moves nothing: no d is that
        # large). Outside the switch, so that ``takes`` is updated where it stands
        def noted(r, x):
            d, takes = x
            d = half(r, d)
            return d, takes | (d & (1 << (2 * r + second)))

        return noted

    first, second = halves(shifted)
    with _hooks.phase("compact"):  # both loops, traced here
        _, takes = rounds(noting(first, 0), noting(second, 1), (d, jnp.zeros_like(d)))
        move = halves(lambda w, cs: tuple(jnp.where((takes & w) != 0, ahead(c, w), c) for c in cs))
        out = []
        for j in range(0, len(cols), _COMPACT_GROUP):
            out += rounds(*move, cols[j : j + _COMPACT_GROUP])
        return out, steps


def _fold_runs(sk, n, cols, kinds):
    """Each run of equal keys among a sorted block's first ``n`` rows folded
    into its last row, ``kinds[j]``'s combiner on ``cols[j]``
    (:func:`_scan_runs`). Returns (keys, totals, is_end), ``is_end``
    marking those last rows."""
    sk, totals = _scan_runs(sk, n, cols, [STAT_COMBINE[kind] for kind in kinds])
    i = lax.iota(jnp.int32, sk.shape[0])
    is_end = (i < n) & ((i == n - 1) | (sk != jnp.concatenate([sk[1:], sk[-1:]])))
    return sk, totals, is_end


def _unique_samples(sk, is_end, u):
    """The election's samples among a sorted block's ``u`` distinct keys:
    the key at each sampled rank is the one at the run end whose running
    count of ends first reaches rank + 1."""
    b = sk.shape[0]
    with _hooks.phase("elect"):
        idx = _sample_ranks(u, b)
        pos = jnp.searchsorted(jnp.cumsum(is_end.astype(jnp.int32)), idx + 1, side="left")
        return jnp.where(idx < u, sk[jnp.clip(pos, 0, b - 1)], jnp.asarray(_max_key(sk.dtype)))


def _dest_matrix(pid, p: int):
    """This shard's per-destination counts, all_gathered into the
    replicated P×P bucket matrix (row = source, column = destination)."""
    row = jnp.sum(
        pid[None, :] == lax.iota(jnp.int32, p)[:, None], axis=1
    ).astype(jnp.int32)
    return lax.all_gather(row, SPLIT_AXIS)


def _counts_and_steps(count, steps):
    """The replicated vector a caller fetches anyway, a word longer: every
    shard's row count and, behind them, the steps :func:`_compact_front`
    ran on the shard that ran most (:func:`_read_counts` splits it)."""
    return jnp.concatenate([lax.all_gather(count, SPLIT_AXIS), lax.pmax(steps, SPLIT_AXIS)[None]])


# ------------------------------------------------------------------- programs
def _plan_executable(
    pshape: Tuple[int, ...],
    key_dtype,
    val_dtypes: Tuple[str, ...],
    stats: Tuple[Tuple[str, int, str], ...],
    p: int,
    mode: str,
    comm: MeshCommunication,
):
    """The groupby plan program: local sort carrying the values →
    segmented scan into partials → splitter election → destination
    tagging → the run ends to the front, each destination's contiguous
    and in key order → replicated bucket matrix. The run ends stand in
    key order; where that is their (destination, key) order too, on one
    device and under range splitters (a destination that rises with the
    key), :func:`_compact_front` moves them, and only hash mode on a
    mesh sorts (:func:`_ends_first`). One dispatch, data-independent
    cache key."""
    mesh = comm.mesh
    key = ("plan", pshape, str(key_dtype), val_dtypes, stats, p, mode, mesh)
    fn = _PROGRAMS.get(key)
    if fn is not None:
        return fn
    b = pshape[0] // p

    def frame_plan(kb, counts, *vals):
        n = counts[lax.axis_index(SPLIT_AXIS)]
        sk, svals = _sort_by_key(kb, n, list(vals))
        data = []
        for kind, ci, odt in stats:
            dt = jnp.dtype(odt)
            data.append(
                jnp.ones((b,), dt)
                if kind == "count"
                else svals[ci].astype(dt) ** 2
                if kind == "sumsq"
                else svals[ci].astype(dt)
            )
        sk, totals, is_end = _fold_runs(sk, n, data, [kind for kind, _, _ in stats])
        u = jnp.sum(is_end.astype(jnp.int32))
        if mode == "range":
            pid = _range_pid(sk, _splitters(_unique_samples(sk, is_end, u), p))
        else:
            pid = _hash_pid(sk, p)
        # everything that is no group's last row goes behind the last destination
        pid = jnp.where(is_end, pid, p)
        mat = _dest_matrix(pid, p)
        uvec = lax.all_gather(u, SPLIT_AXIS)
        if p == 1 or mode == "range":
            outs, steps = _compact_front(is_end, [sk, *totals])
        else:
            outs, steps = _ends_first(pid, p, sk, totals), jnp.int32(0)
        return (*outs, lax.pmax(steps, SPLIT_AXIS), mat, uvec)

    spec = P(SPLIT_AXIS)
    in_specs = (spec, P(), *([spec] * len(val_dtypes)))
    out_specs = (spec, *([spec] * len(stats)), P(), P(), P())
    prog = shard_map(frame_plan, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    fn = _PROGRAMS[key] = jax.jit(prog)
    return fn


def _merge_executable(
    pshape: Tuple[int, ...],
    key_dtype,
    stats: Tuple[Tuple[str, str], ...],
    p: int,
    comm: MeshCommunication,
):
    """The post-exchange merge program: sort received partials by key,
    scan with each statistic's associative combiner, compact the group
    totals to the front, report per-shard group counts (replicated) and,
    behind them, the most steps a compaction of this groupby ran: the
    plan's (``planned``, handed over on the device) or this one's."""
    mesh = comm.mesh
    key = ("gmerge", pshape, str(key_dtype), stats, p, mesh)
    fn = _PROGRAMS.get(key)
    if fn is not None:
        return fn

    def frame_merge(kb, counts, planned, *parts):
        n = counts[lax.axis_index(SPLIT_AXIS)]
        sk, sparts = _sort_by_key(kb, n, list(parts))
        sk, totals, is_end = _fold_runs(sk, n, sparts, [kind for kind, _ in stats])
        outs, steps = _compact_front(is_end, [sk, *totals])
        gvec = _counts_and_steps(jnp.sum(is_end.astype(jnp.int32)), jnp.maximum(steps, planned))
        return (*outs, gvec)

    spec = P(SPLIT_AXIS)
    in_specs = (spec, P(), P(), *([spec] * len(stats)))
    out_specs = (spec, *([spec] * len(stats)), P())
    prog = shard_map(frame_merge, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    fn = _PROGRAMS[key] = jax.jit(prog)
    return fn


def _elect_executable(
    pshapes: Tuple[Tuple[int, ...], ...],
    key_dtype,
    p: int,
    comm: MeshCommunication,
):
    """Splitter election over one or more key columns (a join elects from
    BOTH sides so the two shuffles agree on partition boundaries)."""
    mesh = comm.mesh
    key = ("elect", pshapes, str(key_dtype), p, mesh)
    fn = _PROGRAMS.get(key)
    if fn is not None:
        return fn
    nbufs = len(pshapes)

    def frame_elect(*args):
        blocks, counts = args[:nbufs], args[nbufs:]
        r = lax.axis_index(SPLIT_AXIS)
        mk = jnp.asarray(_max_key(blocks[0].dtype))
        samples = []
        for blk, cnt in zip(blocks, counts):
            n = cnt[r]
            sk, _ = _sort_by_key(blk, n, [])
            idx = _sample_ranks(n, blk.shape[0])
            samples.append(jnp.where(idx < n, sk[idx], mk))
        return _splitters(jnp.concatenate(samples), p)

    spec = P(SPLIT_AXIS)
    in_specs = tuple([spec] * nbufs + [P()] * nbufs)
    prog = shard_map(frame_elect, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False)
    fn = _PROGRAMS[key] = jax.jit(prog)
    return fn


def _partition_executable(
    pshape: Tuple[int, ...],
    key_dtype,
    payload_dtypes: Tuple[str, ...],
    p: int,
    mode: str,
    comm: MeshCommunication,
):
    """Row partition program (no pre-aggregation — the join path): each
    row's destination from the block as it stands (both destination
    functions are elementwise), one stable sort by destination carrying
    the key and the payloads, replicated bucket matrix. No key order is
    made: the join sorts by key itself, and a stable partition keeps a
    source's rows in their own order within each destination, which is
    all the result's row order rests on."""
    mesh = comm.mesh
    key = ("part", pshape, str(key_dtype), payload_dtypes, p, mode, mesh)
    fn = _PROGRAMS.get(key)
    if fn is not None:
        return fn
    b = pshape[0] // p

    def frame_partition(kb, counts, splitters, *vals):
        n = counts[lax.axis_index(SPLIT_AXIS)]
        pid = _range_pid(kb, splitters) if mode == "range" else _hash_pid(kb, p)
        # the pads go behind the last destination, whatever they hold
        pid = jnp.where(lax.iota(jnp.int32, b) < n, pid, p)
        mat = _dest_matrix(pid, p)
        return (*_partition_front(pid, [kb, *vals]), mat)

    spec = P(SPLIT_AXIS)
    in_specs = (spec, P(), P(), *([spec] * len(payload_dtypes)))
    out_specs = (spec, *([spec] * len(payload_dtypes)), P())
    prog = shard_map(frame_partition, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    fn = _PROGRAMS[key] = jax.jit(prog)
    return fn


def _recast(v, dtype):
    """``v``'s bits under ``dtype``, a type as wide as its own: no value is converted, so a float's
    NaN keeps its payload and ``-0.0`` its sign. A bool stands as an int8, as in :func:`_carry_sort`."""
    dtype = jnp.dtype(dtype)
    if v.dtype == dtype:
        return v
    if v.dtype == jnp.bool_:
        v = v.astype(jnp.int8)
    if dtype == jnp.bool_:
        return lax.bitcast_convert_type(v, jnp.int8).astype(jnp.bool_)
    return lax.bitcast_convert_type(v, dtype)


def _join_operands(l_dtypes: Sequence[str], r_dtypes: Sequence[str]):
    """The payload operands of the join's sort, each ``(left payload, right payload, dtype)`` by
    index, ``None`` where a side has none in it. A row is one side's: where it holds the other
    side's column nothing is read. So a left payload and a right payload of one byte width stand
    in ONE operand, the right block's rows first: under their own dtype where a partner of it is
    left (first come, first paired), else under the unsigned integer of their width (a bitcast
    each way: 1.2 ms a column of 1e8 rows on a v5e). A width one side has more columns of keeps
    the surplus in operands of their own: ``max(left, right)`` operands of each width in all,
    where each side's columns had operands of their own (a sort costs by the operand: 0.14 s each
    at 1e8 rows, PERF.md §6 PR 36)."""
    left, right = [jnp.dtype(d) for d in l_dtypes], [jnp.dtype(d) for d in r_dtypes]
    partner, free = {}, list(range(len(right)))
    for fits in (lambda a, b: a == b, lambda a, b: a.itemsize == b.itemsize):  # one dtype first, then one width
        for i in (i for i in range(len(left)) if i not in partner):
            j = next((j for j in free if fits(left[i], right[j])), None)
            if j is not None:
                partner[i] = j
                free.remove(j)
    shared = [
        (i, j, left[i] if left[i] == right[j] else jnp.dtype(f"uint{8 * left[i].itemsize}")) for i, j in partner.items()
    ]
    return [*shared, *((i, None, left[i]) for i in range(len(left)) if i not in partner), *((None, j, right[j]) for j in free)]


def _join_executable(
    l_pshape: Tuple[int, ...],
    r_pshape: Tuple[int, ...],
    key_dtype,
    l_dtypes: Tuple[str, ...],
    r_dtypes: Tuple[str, ...],
    how: str,
    p: int,
    comm: MeshCommunication,
):
    """Device-local merge join of two co-partitioned, exchanged sides,
    neither of them in any order. One sort of the right block with the
    left block behind it, by the key and then by where a row stood (the
    order a stable sort by the key gives, its index ours and not one more
    operand the compiler adds), both sides' payloads sharing operands
    width by width (:func:`_join_operands`): within a run of equal keys
    the right row, which stood earlier, comes first, then the left rows
    in their own order, and a row's side is read off where it stood.
    :func:`_scan_runs` carries that first row's values, and whether it
    was a right row at all (``hit``), along the run;
    :func:`_compact_front` shifts the left rows to keep to the front: the
    matched (inner), or all of them, what found no match NaN (left). One
    sort; no search, no index, no lookup. The result's block is as long
    as both blocks together: cut back to the left block's length, every
    column of it would be one more copy."""
    mesh = comm.mesh
    key = ("join", l_pshape, r_pshape, str(key_dtype), l_dtypes, r_dtypes, how, p, mesh)
    fn = _PROGRAMS.get(key)
    if fn is not None:
        return fn
    bl = l_pshape[0] // p
    br = r_pshape[0] // p
    if how == "left":  # the right columns become floats for the NaN of a row without a match
        r_dtypes = tuple(str(jnp.promote_types(d, jnp.float32)) for d in r_dtypes)
    operands = _join_operands(l_dtypes, r_dtypes)

    def frame_join(lk, lcnt, *rest):
        rk, rcnt = rest[len(l_dtypes)], rest[len(l_dtypes) + 1]
        lvals = list(rest[: len(l_dtypes)])
        rvals = [v.astype(d) for v, d in zip(rest[len(l_dtypes) + 2 :], r_dtypes)]
        r = lax.axis_index(SPLIT_AXIS)
        nl, nr = lcnt[r], rcnt[r]
        i = lax.iota(jnp.int32, br + bl)
        # a pad gets the key nothing sorts after, as in _sort_by_key; it may end up inside
        # the run of a valid row with that very key, where its place tells it apart
        valid = (i < nr) | ((i >= br) & (i < br + nl))
        k = jnp.where(valid, jnp.concatenate([rk, lk]), jnp.asarray(_last_key(lk.dtype)))
        cols = [
            jnp.concatenate([
                jnp.zeros((br,), d) if jr is None else _recast(rvals[jr], d),
                jnp.zeros((bl,), d) if jl is None else _recast(lvals[jl], d),
            ])
            for jl, jr, d in operands
        ]
        # (key, place) orders the rows fully: no stability is asked for, which spares the index
        # operand a stable sort carries beside ours, and no side tag rides along
        sk, si, *cols = _carry_sort([k, i], cols, stable=False)
        # both sides' flags are made here, behind a barrier, and the place dies with it: left to
        # the compiler, ``keep`` reads the place after the scan and a whole column stays alive
        # across it where two bits a row do (0.75 of a column at question 2's widths, 1.44 over
        # four chips at question 5's: sandbox compile, PR 36)
        is_right, is_left = lax.optimization_barrier((si < nr, (si >= br) & (si < br + nl)))
        slv, srv = [None] * len(l_dtypes), [None] * len(r_dtypes)
        for (jl, jr, _), c in zip(operands, cols):
            if jl is not None:  # what stands in the right rows of it is dropped, unread
                slv[jl] = _recast(c, l_dtypes[jl])
            if jr is not None:
                srv[jr] = _recast(c, r_dtypes[jr])
        if how == "left":
            # What a left row without a match comes out with, the scan hands it from the first row
            # of its run: itself or another left row or, under the key nothing sorts after, a pad of
            # the right block. So all but the right rows hold NaN before the scan; an inner join
            # keeps no such row and reads none of it
            srv = [jnp.where(is_right, v, jnp.asarray(jnp.nan, v.dtype)) for v in srv]
        # duplicate right keys would silently multiply rows in a merge
        # join — detect and report (replicated via max over shards)
        dup_local = jnp.any(is_right[1:] & is_right[:-1] & (sk[1:] == sk[:-1]))
        dup = lax.pmax(dup_local.astype(jnp.int32), SPLIT_AXIS)
        sk, (hit, *srv) = _scan_runs(sk, br + bl, [is_right, *srv], ["first"] * (1 + len(srv)))
        if how == "inner":
            keep = is_left & hit
            g = jnp.sum(keep.astype(jnp.int32))
        else:  # left: all valid left rows
            keep, g = is_left, nl
        outs, steps = _compact_front(keep, [sk, *slv, *srv])
        return (*outs, _counts_and_steps(g, steps), dup)

    spec = P(SPLIT_AXIS)
    in_specs = (
        spec, P(), *([spec] * len(l_dtypes)), spec, P(), *([spec] * len(r_dtypes)),
    )
    out_specs = (
        spec, *([spec] * (len(l_dtypes) + len(r_dtypes))), P(), P(),
    )
    prog = shard_map(frame_join, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    fn = _PROGRAMS[key] = jax.jit(prog)
    fn.sort_operands = 2 + len(operands)  # the key, the place, the payloads' (``hash_join``'s gauge)
    return fn


def _compact_executable(
    pshape: Tuple[int, ...],
    dtypes: Tuple[str, ...],
    p: int,
    comm: MeshCommunication,
):
    """Local filter compaction: the kept rows shifted to each shard's
    prefix in their order (:func:`_compact_front`; ragged result, ZERO
    exchanges), report kept counts."""
    mesh = comm.mesh
    key = ("compact", pshape, dtypes, p, mesh)
    fn = _PROGRAMS.get(key)
    if fn is not None:
        return fn
    b = pshape[0] // p

    def frame_compact(mask, counts, *cols):
        n = counts[lax.axis_index(SPLIT_AXIS)]
        keep = mask & (lax.iota(jnp.int32, b) < n)
        outs, steps = _compact_front(keep, cols)
        return (*outs, _counts_and_steps(jnp.sum(keep.astype(jnp.int32)), steps))

    spec = P(SPLIT_AXIS)
    in_specs = (spec, P(), *([spec] * len(dtypes)))
    out_specs = (*([spec] * len(dtypes)), P())
    prog = shard_map(frame_compact, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    fn = _PROGRAMS[key] = jax.jit(prog)
    return fn


# ------------------------------------------------------------- orchestration
def _counts_vec(counts: Sequence[int]) -> jnp.ndarray:
    return jnp.asarray(tuple(int(c) for c in counts), jnp.int32)


def _read_counts(vec, site: str) -> np.ndarray:
    """Fetch what :func:`_counts_and_steps` made: the per-shard counts come
    back, the steps go into the gauge ``SHUFFLE_STATS["compact_steps"]``."""
    vec = _hooks.fetch(vec, site)
    SHUFFLE_STATS["compact_steps"] = int(vec[-1])
    return vec[:-1]


def _receive_rows(fullest: int) -> int:
    """Rows of a row shuffle's receive block: the fullest bucket's, rounded
    up to the next of 64 lengths an octave (a multiple of the 128th of
    the power of two above it: at most a 64th more rows). The merge join
    is compiled for this length, and the fullest bucket moves with the
    keys by a few rows in ten thousand: so a table about as long as the
    last one finds its program compiled, whatever its keys."""
    step = 1 << max(fullest.bit_length() - 7, 0)
    return max(1, -(-fullest // step) * step)


def _exchange_operands(
    bufs: List[jax.Array], mat: np.ndarray, b_out: int, comm: MeshCommunication
) -> List[jax.Array]:
    """ONE bucket exchange per operand column over a shared schedule,
    into receive blocks of ``b_out`` rows."""
    return [
        collective_lockstep(bucket_move(b, 0, mat.tolist(), b_out, comm)) for b in bufs
    ]


def groupby_reduce(
    key_col: DNDarray,
    value_bufs: List[jax.Array],
    val_dtypes: Tuple[str, ...],
    stats: Tuple[Tuple[str, int, str], ...],
    mode: str = "range",
) -> Tuple[DNDarray, List[DNDarray], int]:
    """Distributed groupby: per-shard combine → one exchange per operand
    → per-shard merge. Returns (unique keys, one reduced column per
    requested statistic, n_groups) in a co-aligned ragged split-0 layout
    (with ``mode="range"`` the keys are additionally in global sorted
    order).

    ``stats`` is a tuple of ``(kind, value_index, out_dtype)`` with
    ``kind`` in {sum, sumsq, count, min, max} (count ignores the index).
    """
    if mode not in ("range", "hash"):
        raise ValueError(f"mode must be 'range' or 'hash', got {mode!r}")
    comm = key_col.comm
    p = comm.size
    kb = key_col._raw
    counts = _counts_vec(shard_counts(key_col))
    plan = _plan_executable(
        tuple(kb.shape), kb.dtype, val_dtypes, stats, p, mode, comm
    )
    out = collective_lockstep(plan(kb, counts, *value_bufs))
    pk, parts, planned, mat = out[0], list(out[1 : 1 + len(stats)]), out[-3], out[-2]
    # the replicated bucket matrix comes to host to build the static
    # exchange schedule — same bounded sync as redistribute_'s target map
    mat_np = _hooks.fetch(mat, "groupby.bucket_matrix")
    out_counts = mat_np.sum(axis=0)
    b_out = max(1, int(out_counts.max()))
    moved = _exchange_operands([pk, *parts], mat_np, b_out, comm)
    merge = _merge_executable(
        (p * b_out,),
        kb.dtype,
        tuple((kind, odt) for kind, _, odt in stats),
        p,
        comm,
    )
    mout = collective_lockstep(merge(moved[0], _counts_vec(out_counts), planned, *moved[1:]))
    gvec = _read_counts(mout[-1], "groupby.group_counts")
    n_groups = int(gvec.sum())
    mkeys = DNDarray._from_ragged(
        mout[0], (n_groups,), mout[0].dtype, 0, tuple(int(c) for c in gvec),
        device=key_col.device, comm=comm,
    )
    reduced = [
        DNDarray._from_ragged(
            buf, (n_groups,), buf.dtype, 0, tuple(int(c) for c in gvec),
            device=key_col.device, comm=comm,
        )
        for buf in mout[1 : 1 + len(stats)]
    ]
    SHUFFLE_STATS["groupbys"] += 1
    return mkeys, reduced, n_groups


def shuffle_rows(
    key_col: DNDarray,
    payload_bufs: List[jax.Array],
    mode: str = "range",
    splitters: Optional[jax.Array] = None,
) -> Tuple[List[jax.Array], np.ndarray, int]:
    """Full-row shuffle (no combining): co-locate equal keys. Returns
    (moved [key, *payload] buffers, per-shard out_counts, b_out). Rows
    arrive grouped by source shard, in their source's own order within
    one, in no key order; pass ``splitters`` to reuse a prior election
    (both sides of a join must agree)."""
    comm = key_col.comm
    p = comm.size
    kb = key_col._raw
    counts = _counts_vec(shard_counts(key_col))
    if mode == "range" and splitters is None:
        elect = _elect_executable((tuple(kb.shape),), kb.dtype, p, comm)
        splitters = collective_lockstep(elect(kb, counts))
    if splitters is None:
        splitters = jnp.zeros((max(p - 1, 1),), kb.dtype)
    part = _partition_executable(
        tuple(kb.shape), kb.dtype,
        tuple(str(b.dtype) for b in payload_bufs), p, mode, comm,
    )
    out = collective_lockstep(part(kb, counts, splitters, *payload_bufs))
    mat_np = _hooks.fetch(out[-1], "shuffle.bucket_matrix")
    out_counts = mat_np.sum(axis=0)
    fullest, rows = int(out_counts.max()), int(out_counts.sum())
    b_out = _receive_rows(fullest)
    moved = _exchange_operands(list(out[:-1]), mat_np, b_out, comm)
    SHUFFLE_STATS["row_shuffles"] += 1
    SHUFFLE_STATS["bucket_skew"] = fullest * p / rows if rows else 1.0
    return moved, out_counts, b_out


def hash_join(
    l_key: DNDarray,
    l_bufs: List[jax.Array],
    r_key: DNDarray,
    r_bufs: List[jax.Array],
    how: str = "inner",
    mode: str = "range",
) -> Tuple[List[jax.Array], np.ndarray, int]:
    """Distributed join: on a mesh of more than one device co-partition
    both sides with ONE shared splitter election and one exchange per
    operand on each side; then a device-local merge join, which sorts by
    key itself. On a mesh of one device equal keys are together already:
    the merge reads the callers' buffers under their own counts, nothing
    is elected, partitioned or moved, and ``mode`` has no effect. No
    buffer is donated. Right keys must be unique (m:1 join — the
    hash-join contract pandas calls ``validate="m:1"``). Returns (result
    buffers ``[key, *left_cols, *right_cols]``, per-shard counts,
    dup_flag). Left-join right columns are promoted to float and
    NaN-filled."""
    if how not in ("inner", "left"):
        raise ValueError(f"how must be 'inner' or 'left', got {how!r}")
    comm = l_key.comm
    p = comm.size
    if p == 1:
        l_moved, l_counts = [l_key._raw, *l_bufs], shard_counts(l_key)
        r_moved, r_counts = [r_key._raw, *r_bufs], shard_counts(r_key)
    else:
        splitters = None
        if mode == "range":
            elect = _elect_executable(
                (tuple(l_key._raw.shape), tuple(r_key._raw.shape)),
                l_key._raw.dtype, p, comm,
            )
            splitters = collective_lockstep(
                elect(
                    l_key._raw, r_key._raw,
                    _counts_vec(shard_counts(l_key)), _counts_vec(shard_counts(r_key)),
                )
            )
        l_moved, l_counts, _ = shuffle_rows(l_key, l_bufs, mode, splitters)
        r_moved, r_counts, _ = shuffle_rows(r_key, r_bufs, mode, splitters)
    join = _join_executable(
        tuple(l_moved[0].shape), tuple(r_moved[0].shape), l_moved[0].dtype,
        tuple(str(b.dtype) for b in l_moved[1:]),
        tuple(str(b.dtype) for b in r_moved[1:]),
        how, p, comm,
    )
    out = collective_lockstep(
        join(
            l_moved[0], _counts_vec(l_counts), *l_moved[1:],
            r_moved[0], _counts_vec(r_counts), *r_moved[1:],
        )
    )
    dup = int(_hooks.fetch(out[-1], "shuffle.join_dup"))
    gvec = _read_counts(out[-2], "shuffle.join_counts")
    SHUFFLE_STATS["joins"] += 1
    SHUFFLE_STATS["join_sort_operands"] = join.sort_operands
    return list(out[:-2]), gvec, dup


def compact_rows(
    mask_buf: jax.Array,
    col_bufs: List[jax.Array],
    counts: Sequence[int],
    comm: MeshCommunication,
) -> Tuple[List[jax.Array], np.ndarray]:
    """Local filter compaction (zero exchanges): each shard moves its
    kept rows to the block prefix; returns (buffers, kept counts)."""
    fn = _compact_executable(
        tuple(mask_buf.shape), tuple(str(b.dtype) for b in col_bufs), comm.size, comm
    )
    out = collective_lockstep(fn(mask_buf, _counts_vec(counts), *col_bufs))
    gvec = _read_counts(out[-1], "shuffle.compact_counts")
    SHUFFLE_STATS["compactions"] += 1
    return list(out[:-1]), gvec
