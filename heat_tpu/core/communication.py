"""Device-mesh "communication" layer — the TPU-native replacement for MPI.

The reference implements a 1941-line MPI wrapper
(``heat/core/communication.py``): tensor-aware buffers, derived datatypes,
GPU staging, axis-permuting collectives. On TPU none of that machinery is
needed — a ``jax.sharding.Mesh`` plus ``NamedSharding`` annotations *is* the
communication backend: XLA GSPMD inserts all-reduce / all-gather /
all-to-all / collective-permute on ICI automatically, and explicit
collectives are expressed with ``jax.lax`` primitives inside ``shard_map``.

What survives here is the *bookkeeping* interface the rest of the library
speaks (reference ``communication.py:120,161-239,1886-1937``):

- ``MPICommunication`` -> :class:`MeshCommunication`: holds the device mesh,
  knows the world ``size``/``rank``, computes ``chunk()`` partitions and
  ``counts_displs_shape()``.
- ``MPI_WORLD``/``MPI_SELF`` singletons and ``get_comm``/``use_comm``/
  ``sanitize_comm``.

Partitioning note: the reference balances remainders across the first ranks
(``communication.py:161-209``); XLA shards an axis in ceil-div blocks (the
last shard may be short or empty). ``chunk()`` follows the XLA convention so
that ``lshape_map`` always reflects the true on-device layout.
"""
from __future__ import annotations

import math
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ._dispatch import fence_cpu_collectives

__all__ = [
    "Communication",
    "MeshCommunication",
    "MPI_WORLD",
    "MPI_SELF",
    "WORLD",
    "SELF",
    "get_comm",
    "init_distributed",
    "use_comm",
    "sanitize_comm",
    "SPLIT_AXIS",
    "MPICommunication",
    "CUDA_AWARE_MPI",
    "collective_lockstep",
    "replicated_decision",
    "replicated_ids",
    "replicated_frame",
    "tree_merge",
    "tree_merge_rounds",
]

# canonical mesh-axis name carrying the DNDarray ``split`` dimension
SPLIT_AXIS = "split"


class Communication:
    """Base class for communication backends (reference ``communication.py:88``)."""

    @staticmethod
    def is_distributed() -> bool:
        raise NotImplementedError()

    def chunk(self, shape, split, rank=None) -> Tuple[int, Tuple[int, ...], Tuple[slice, ...]]:
        raise NotImplementedError()


class MeshCommunication(Communication):
    """A communicator backed by a JAX device mesh.

    Parameters
    ----------
    devices : list of jax.Device, optional
        Devices forming the mesh. Defaults to all devices of the default
        backend.
    mesh : jax.sharding.Mesh, optional
        Pre-built mesh. Must contain the axis ``split``; additional axes
        (e.g. a slow DCN axis for hierarchical data-parallelism) are allowed
        and are used by :mod:`heat_tpu.optim`.
    """

    def __init__(self, devices: Optional[List] = None, mesh: Optional[Mesh] = None):
        if mesh is not None:
            if SPLIT_AXIS not in mesh.axis_names:
                raise ValueError(f"mesh must contain axis {SPLIT_AXIS!r}, got {mesh.axis_names}")
            self._mesh = mesh
        elif devices is not None:
            self._mesh = Mesh(np.array(devices), axis_names=(SPLIT_AXIS,))
        else:
            # defer jax.devices() so that `import heat_tpu` does not
            # initialize the XLA backend — a prerequisite for
            # init_distributed(), which must run before first backend use
            self._mesh = None

    # -- world-style properties ------------------------------------------------
    @property
    def mesh(self) -> Mesh:
        if self._mesh is None:
            self._mesh = Mesh(np.array(jax.devices()), axis_names=(SPLIT_AXIS,))
        return self._mesh

    @property
    def size(self) -> int:
        """Number of shards along the split axis (MPI world-size analogue)."""
        return self.mesh.shape[SPLIT_AXIS]

    @property
    def rank(self) -> int:
        """Index of the controlling process (multi-host: ``jax.process_index``).

        Under single-controller JAX every process sees the *global* array, so
        unlike MPI code the library almost never branches on ``rank``.
        """
        return jax.process_index()

    def is_distributed(self) -> bool:
        return self.size > 1

    # -- sharding construction -------------------------------------------------
    def spec(self, ndim: int, split: Optional[int]) -> PartitionSpec:
        """PartitionSpec placing the mesh split-axis at dimension ``split``."""
        if split is None:
            return PartitionSpec()
        if not 0 <= split < max(ndim, 1):
            raise ValueError(f"split {split} out of range for ndim {ndim}")
        parts = [None] * ndim
        parts[split] = SPLIT_AXIS
        return PartitionSpec(*parts)

    def sharding(self, ndim: int, split: Optional[int]) -> NamedSharding:
        """NamedSharding for an ``ndim``-dim array split along ``split``."""
        return NamedSharding(self.mesh, self.spec(ndim, split))

    def padded_dim(self, n: int) -> int:
        """Physical size of a split dimension of logical size ``n``: the
        smallest multiple of the mesh size >= ``n`` (ceil-div padding).

        JAX rejects uneven ``NamedSharding``s at every array boundary
        (``device_put``/jit in/out); the TPU-native answer is static even
        shards + tail padding, with validity masks at reductions. The
        reference instead allowed ragged per-rank chunks
        (``communication.py:161-209``) — same logical layout, since the
        ceil-div chunks here are exactly the valid prefixes of the padded
        blocks.
        """
        n = int(n)
        block = -(-n // self.size) if n else 0
        return max(block, 1) * self.size

    def padded_shape(self, shape, split: Optional[int]) -> Tuple[int, ...]:
        """Physical (buffer) shape for a logical ``shape`` split at ``split``."""
        shape = tuple(int(s) for s in shape)
        if split is None:
            return shape
        out = list(shape)
        out[split] = self.padded_dim(shape[split])
        return tuple(out)

    def array_sharding(self, shape, split: Optional[int]) -> NamedSharding:
        """Sharding applied to a physical buffer of ``shape``. The split dim
        must already be padded to a multiple of the mesh size."""
        if split is not None and shape[split] % self.size != 0:
            raise ValueError(
                f"buffer dim {split} of shape {tuple(shape)} is not a multiple of the "
                f"mesh size {self.size}; pad with padded_shape() first"
            )
        return self.sharding(len(shape), split)

    # -- partition bookkeeping (reference communication.py:161-239) -----------
    def chunk(
        self, shape, split: Optional[int], rank: Optional[int] = None
    ) -> Tuple[int, Tuple[int, ...], Tuple[slice, ...]]:
        """Compute the shard of ``shape`` owned by ``rank`` along ``split``.

        Returns ``(offset, local_shape, slices)`` like the reference
        (``communication.py:161-209``), but using XLA's ceil-div layout.
        """
        shape = tuple(int(s) for s in shape)
        if split is None:
            return 0, shape, tuple(slice(0, s) for s in shape)
        rank = self.rank if rank is None else rank
        n = shape[split]
        block = -(-n // self.size) if n else 0  # ceil div
        start = min(rank * block, n)
        end = min(start + block, n)
        lshape = list(shape)
        lshape[split] = end - start
        slices = tuple(
            slice(start, end) if i == split else slice(0, s) for i, s in enumerate(shape)
        )
        return start, tuple(lshape), slices

    def counts_displs_shape(self, shape, split: int):
        """Per-rank counts/displacements along ``split`` (ref ``:211-239``)."""
        shape = tuple(int(s) for s in shape)
        n = shape[split]
        block = -(-n // self.size) if n else 0
        counts, displs = [], []
        for r in range(self.size):
            start = min(r * block, n)
            end = min(start + block, n)
            counts.append(end - start)
            displs.append(start)
        output_shape = list(shape)
        output_shape[split] = block
        return tuple(counts), tuple(displs), tuple(output_shape)

    def lshape_map(self, shape, split: Optional[int]) -> np.ndarray:
        """(size, ndim) array of every shard's local shape (ref ``dndarray.py:569``).

        Pure metadata on TPU — no Allreduce needed.
        """
        shape = tuple(int(s) for s in shape)
        ndim = max(len(shape), 1)
        out = np.empty((self.size, len(shape)), dtype=np.int64)
        for r in range(self.size):
            _, lshape, _ = self.chunk(shape, split, rank=r)
            out[r] = lshape if len(shape) else ()
        return out

    # -- misc -----------------------------------------------------------------
    def __repr__(self) -> str:
        # must not force lazy mesh resolution (would initialize the backend
        # and break a subsequent init_distributed)
        if self._mesh is None:
            return "MeshCommunication(<world, unresolved>)"
        return f"MeshCommunication(size={self.size}, mesh={self._mesh!r})"

    def __eq__(self, other) -> bool:
        # resolution-free: two unresolved communicators are equal only when
        # they are the same kind (unresolved SELF != unresolved WORLD)
        return type(self) is type(other) and self._mesh == other._mesh

    def __hash__(self):
        # constant per class: stable across lazy resolution (eq still
        # discriminates; collisions only cost dict-probe time)
        return hash(MeshCommunication)


class _SelfCommunication(MeshCommunication):
    """Single-device communicator (MPI_SELF analogue); resolves its device
    lazily so importing the package does not initialize the backend."""

    def __init__(self):
        self._mesh = None

    @property
    def mesh(self) -> Mesh:
        if self._mesh is None:
            # first LOCAL device: on a multi-host pod every process must
            # pick a device it can address (jax.devices()[0] lives on host 0)
            self._mesh = Mesh(np.array([jax.local_devices()[0]]), axis_names=(SPLIT_AXIS,))
        return self._mesh


# module-level singletons (reference communication.py:1886-1937)
WORLD = MeshCommunication()
SELF = _SelfCommunication()
# Names kept for reference-API familiarity; there is no MPI underneath.
MPI_WORLD = WORLD
MPI_SELF = SELF

_default_comm = WORLD


def get_comm() -> MeshCommunication:
    """The current default communicator (reference ``communication.py:1907``)."""
    return _default_comm


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids=None,
) -> MeshCommunication:
    """Initialize the multi-host runtime and rebuild the world communicator.

    The reference initializes at import under ``mpirun`` (MPI_Init inside
    ``import heat``, reference ``communication.py:1886-1891``). The TPU
    analogue is ``jax.distributed.initialize`` — on Cloud TPU pods every
    argument auto-detects from the metadata server, so a bare
    ``ht.init_distributed()`` at the top of the SPMD script is the whole
    story; on other clusters pass coordinator/process arguments explicitly.

    Importing ``heat_tpu`` does NOT initialize the XLA backend (the world
    communicators resolve their device mesh lazily), so this must be the
    first device-touching call of the program::

        import heat_tpu as ht
        ht.init_distributed()          # before any array is created
        x = ht.zeros((N, F), split=0)  # sharded over the whole pod

    After initialization the default communicator spans ALL global devices:
    intra-host collectives ride ICI, inter-host DCN (XLA routes per edge).
    """
    global _default_comm
    # Multi-process groups on the CPU platform (tests, local smoke runs)
    # need a host-side collectives layer armed BEFORE the backend comes up:
    # XLA's bare CPU client rejects cross-process programs outright
    # ("Multiprocess computations aren't implemented on the CPU backend").
    # TPU/GPU platforms never enter this branch, and an explicit user
    # choice (e.g. "mpi") is left alone.
    try:
        if (
            jax.config.jax_platforms == "cpu"
            and jax.config._read("jax_cpu_collectives_implementation") == "none"
        ):
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except AttributeError:
        pass  # this jax build predates (or renamed) the flag: nothing to arm
    kwargs = {
        k: v
        for k, v in dict(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            local_device_ids=local_device_ids,
        ).items()
        if v is not None
    }
    try:
        jax.distributed.initialize(**kwargs)
    except RuntimeError as e:
        if "must be called before" in str(e):
            raise RuntimeError(
                "the XLA backend is already initialized: call "
                "ht.init_distributed() before creating any array (or call "
                "jax.distributed.initialize() before importing anything "
                "that touches devices)"
            ) from e
        raise
    # drop any lazily-cached single-host mesh so WORLD/SELF re-resolve over
    # the now-global device set; aliases (MPI_WORLD, ht.WORLD, ...) keep
    # pointing at the same objects, so they refresh too
    WORLD._mesh = None
    SELF._mesh = None
    _default_comm = WORLD
    return WORLD


def use_comm(comm: Optional[MeshCommunication] = None) -> None:
    """Set the default communicator (reference ``communication.py:1927``)."""
    global _default_comm
    if comm is None:
        comm = WORLD
    if not isinstance(comm, Communication):
        raise TypeError(f"expected a Communication object, got {type(comm)}")
    _default_comm = comm


def sanitize_comm(comm) -> MeshCommunication:
    """Default-or-validate a communicator (reference ``communication.py:1917``)."""
    if comm is None:
        return get_comm()
    if not isinstance(comm, Communication):
        raise TypeError(f"expected a Communication object, got {type(comm)}")
    return comm


@contextmanager
def comm_context(comm: MeshCommunication):
    """Temporarily swap the default communicator."""
    global _default_comm
    prev = _default_comm
    _default_comm = comm
    try:
        yield comm
    finally:
        _default_comm = prev


# name-parity aliases: the reference's MPI backend class (``communication.py:120``)
# maps onto the mesh-collective backend here; there is no CUDA staging on TPU.
MPICommunication = MeshCommunication
CUDA_AWARE_MPI = False


def _assemble_from_chunks(read_chunk, gshape, split, comm, np_dtype):
    """Build the padded global buffer from per-device chunk reads.

    ``read_chunk(slices) -> np.ndarray`` returns the data for one device's
    valid chunk, addressed in GLOBAL coordinates (``comm.chunk`` layout).
    Each process materializes only its addressable devices' blocks,
    zero-padded to the even block size; the global ``jax.Array`` is
    stitched with ``make_array_from_single_device_arrays`` — the analogue
    of the reference's per-rank parallel reads (``io.py:57-147``). No
    device and no host ever holds the full array.

    Runs under the collective watchdog when one is installed
    (``resilience.deadlines``): a wedged chunk read or device transfer
    raises ``CollectiveTimeout`` instead of hanging the job.
    """
    from . import _hooks

    return _hooks.guarded_call(
        "collective.assemble",
        _assemble_from_chunks_impl,
        read_chunk, gshape, split, comm, np_dtype,
    )


def _assemble_from_chunks_impl(read_chunk, gshape, split, comm, np_dtype):
    from . import _hooks

    _hooks.fault_point(
        "collective.assemble",
        gshape=tuple(gshape),
        split=split,
        dtype=str(np.dtype(np_dtype)),
    )
    pshape = comm.padded_shape(gshape, split)
    sharding = comm.array_sharding(pshape, split)
    block_shape = list(pshape)
    block_shape[split] = pshape[split] // comm.size
    pid = jax.process_index()
    arrays = []
    blocks = {}  # split-rank -> host block, shared by replicated devices
    for rank, dev in _split_ranks(comm):
        if dev.process_index != pid:
            continue
        if rank not in blocks:
            _, lshape, slices = comm.chunk(gshape, split, rank=rank)
            buf = np.zeros(tuple(block_shape), dtype=np_dtype)
            if all(s > 0 for s in lshape):
                buf[tuple(slice(0, s) for s in lshape)] = read_chunk(slices)
                # chaos can plant NaNs here — the simulated silently-
                # corrupted shard that validate()/health_check() must catch
                _hooks.fault_point("collective.shard", array=buf, rank=rank)
            blocks[rank] = buf
        arrays.append(jax.device_put(blocks[rank], dev))
    return jax.make_array_from_single_device_arrays(pshape, sharding, arrays)


def ragged_process_allgather(arr: np.ndarray, axis: int = 0):
    """Allgather per-process host arrays whose extent along ``axis`` may
    differ: sizes are exchanged first, payloads padded to the max, and
    each process's block trimmed on receipt. Returns the list of blocks
    in process order. THE one implementation of this subtle protocol —
    ``assemble_local_shards``'s uneven path, ``unique``'s candidate
    merge, and ``nonzero``'s coordinate concat all route through it.

    The blocking host allgather is THE place a straggling or dead peer
    wedges every process; under an installed watchdog
    (``resilience.deadlines``) the wait is bounded and surfaces as
    ``CollectiveTimeout('collective.allgather')``."""
    from . import _hooks

    return _hooks.guarded_call(
        "collective.allgather", _ragged_process_allgather_impl, arr, axis
    )


def _ragged_process_allgather_impl(arr: np.ndarray, axis: int = 0):
    from jax.experimental import multihost_utils

    from . import _hooks

    nproc = jax.process_count()
    moved = np.moveaxis(np.asarray(arr), axis, 0)
    # per-rank extents along ``axis`` are allowed to differ — that is
    # this protocol's entire contract — so the lockstep fingerprint must
    # carry only the rank-invariant context (trailing dims, dtype, axis);
    # including the local extent would make every legal ragged gather
    # self-report as a divergence
    _hooks.fault_point(
        "collective.allgather",
        shape=tuple(moved.shape[1:]),
        axis=int(axis),
        dtype=str(moved.dtype),
    )
    counts = np.asarray(
        multihost_utils.process_allgather(np.asarray([moved.shape[0]], np.int64))
    ).reshape(-1)
    cap = int(counts.max()) if counts.size else 0
    if cap == 0:
        return [np.moveaxis(moved, 0, axis) for _ in range(nproc)]
    padded = np.zeros((cap,) + moved.shape[1:], moved.dtype)
    padded[: moved.shape[0]] = moved
    gathered = np.asarray(multihost_utils.process_allgather(padded)).reshape(
        (nproc, cap) + padded.shape[1:]
    )
    return [
        np.moveaxis(gathered[p, : int(counts[p])], 0, axis) for p in range(nproc)
    ]


def replicated_decision(flag, *, active: bool = True) -> bool:
    """Make a host-side boolean rendezvous-safe: every process returns the
    OR of all processes' flags, so a branch guarded by the result is
    taken identically everywhere even when the local inputs (wall clocks,
    filesystem probes) disagree.  THE sanctioned way to branch a
    collective-dispatching path on a process-local predicate.

    ``active=False`` — or a single-process world — returns ``bool(flag)``
    without dispatching anything, so callers whose predicate is already
    replicated (step counters, global metadata) pay nothing.  graftflow
    models this call as laundering taint (its summary table), which is
    exactly its contract."""
    flag = bool(flag)
    if not active or jax.process_count() == 1:
        return flag
    from . import _hooks

    return _hooks.guarded_call(
        "collective.replicated_decision", _replicated_decision_impl, flag
    )


def _replicated_decision_impl(flag: bool) -> bool:
    from jax.experimental import multihost_utils

    from . import _hooks

    _hooks.fault_point(
        "collective.replicated_decision", shape=(1,), dtype="bool"
    )
    votes = multihost_utils.process_allgather(np.asarray([flag], dtype=np.bool_))
    return bool(np.asarray(votes).any())


def replicated_ids(ids, *, cap: int = 64, active: bool = True) -> frozenset:
    """Union a small process-local set of integer ids across every
    process — the set-valued sibling of :func:`replicated_decision`, for
    decisions that need consensus on WHICH members, not just whether any.

    The motivating caller is elastic shrink under multiple controllers:
    ``probe`` only sees this process's addressable devices, so each rank
    holds a partial unhealthy set; building survivor meshes from partial
    sets would give every rank a DIFFERENT mesh. One fixed-width
    allgather (``cap`` slots, -1 padded — rank-invariant shape, so the
    collective itself is lockstep-safe) returns the identical union
    everywhere. ``active=False`` — or a single-process world — returns
    the local set without dispatching anything."""
    local = frozenset(int(i) for i in ids)
    if not active or jax.process_count() == 1:
        return local
    if len(local) > cap:
        raise ValueError(
            f"replicated_ids: {len(local)} ids exceed the {cap}-slot frame"
        )
    from . import _hooks

    def impl() -> frozenset:
        from jax.experimental import multihost_utils

        _hooks.fault_point("collective.replicated_ids", shape=(cap,), dtype="int32")
        frame = np.full((cap,), -1, dtype=np.int32)
        frame[: len(local)] = sorted(local)
        gathered = np.asarray(multihost_utils.process_allgather(frame)).ravel()
        return frozenset(int(i) for i in gathered if i >= 0)

    return _hooks.guarded_call("collective.replicated_ids", impl)


def replicated_frame(
    frame, *, label: str = "collective.replicated_frame", active: bool = True
) -> np.ndarray:
    """Exchange a small fixed-width int64 metadata frame: every process
    contributes one ``frame`` (identical shape/dtype everywhere by
    contract — a rank-dependent shape would desync the allgather itself)
    and receives the stacked ``(nproc, *frame.shape)`` array, identical
    on every rank.  The array-valued sibling of
    :func:`replicated_decision` / :func:`replicated_ids`: any pure
    function of the gathered frames computes the SAME value on every
    process, so its result may gate collectives — graftflow models this
    call as laundering taint, which is exactly that contract.

    ``label`` names the guarded-call site (and its fault point) so
    distinct frame protocols — the health monitor's EWMA frame, the
    serve dispatch tick — stay separately addressable under chaos
    schedules.  ``active=False`` — or a single-process world — returns
    ``frame[None]`` without dispatching anything, so single-controller
    callers run the identical decode path over a one-row gather."""
    frame = np.ascontiguousarray(frame, dtype=np.int64)
    if not active or jax.process_count() == 1:
        return frame[None]
    from . import _hooks

    def impl() -> np.ndarray:
        from jax.experimental import multihost_utils

        _hooks.fault_point(label, shape=frame.shape, dtype="int64")
        gathered = np.asarray(multihost_utils.process_allgather(frame))
        return gathered.reshape((jax.process_count(),) + frame.shape)

    return _hooks.guarded_call(label, impl)


def collective_lockstep(tree):
    """Pin a collective-bearing dispatch to completion under
    multi-controller execution and on the CPU backend's in-process mesh;
    a transparent pass-through otherwise.

    XLA matches cross-process collectives by launch order per device, but
    two *independent* programs (no data dependency — e.g. the moments and
    cov folds of the same streamed chunk) may execute concurrently on the
    runtime thread pool, interleaving their collectives differently on
    each process: the rendezvous then deadlocks or silently mixes data
    across programs. Blocking on each such program before launching the
    next independent one restores a total cross-process order. Eager op
    *chains* don't need this — data dependencies already serialize them.
    One process on an accelerator has no rendezvous to protect, so there
    this returns immediately and full async dispatch is preserved. One
    process on a multi-device CPU mesh does: a loop of folds that runs
    the host 32 programs ahead of the devices wedges the CPU client
    (:mod:`heat_tpu.core._dispatch` has the mechanism), so each fold is
    fenced there too."""
    if jax.process_count() > 1:
        jax.block_until_ready(tree)
    else:
        fence_cpu_collectives(tree)
    return tree


def tree_merge_rounds(nproc: int) -> int:
    """Exchange rounds :func:`tree_merge` dispatches for ``nproc``
    processes: ``ceil(log2 P)`` on the butterfly path, 0 when it falls
    back (P == 1, or a non-power-of-two world). Host-pure — the counter
    oracle the multihost tests assert against."""
    nproc = int(nproc)
    if nproc <= 1 or nproc & (nproc - 1):
        return 0
    return nproc.bit_length() - 1


# one jitted butterfly program per (combine, state structure, mesh) —
# every fold-then-merge epoch re-dispatches the same executable
_TREE_PROGRAMS: Optional[object] = None
_PROCESS_MESH: Optional[Mesh] = None


def _process_mesh() -> Mesh:
    """One-device-per-process mesh (split axis = process index): the
    substrate for replicated-state collectives. Each process contributes
    its first addressable device, ordered by process index, so rank ==
    ``jax.process_index`` on every controller."""
    global _PROCESS_MESH
    if _PROCESS_MESH is not None and _PROCESS_MESH.devices.size == jax.process_count():
        return _PROCESS_MESH
    first: Dict[int, object] = {}
    for d in jax.devices():
        first.setdefault(d.process_index, d)
    devs = [first[i] for i in range(jax.process_count())]
    _PROCESS_MESH = Mesh(np.array(devs), axis_names=(SPLIT_AXIS,))
    return _PROCESS_MESH


def tree_merge(state, combine, *, label: str = "collective.tree_merge", active: bool = True):
    """Merge one replicated-state pytree per process into the identical
    global state on EVERY process in ``ceil(log2 P)`` ``ppermute`` rounds
    — the log-depth alternative to allgathering all P states and folding
    them serially.

    ``state`` is a pytree of (host or device) arrays — one streaming
    estimator's state as held by THIS process; every process must pass
    the same tree structure, leaf shapes, and dtypes (a rank-dependent
    shape would desync the exchange itself). ``combine`` is a pure,
    jax-traceable, associative function ``(tree_a, tree_b) -> tree`` with
    ``tree_a`` the lower-rank operand; it must preserve leaf shapes and
    dtypes. The result on every process is the rank-ordered combination
    ``s_0 ⊕ s_1 ⊕ ... ⊕ s_{P-1}`` with the SAME balanced-tree bracketing
    everywhere, so the merged state is bit-identical across processes —
    replicated-state discipline holds by construction.

    Rounds: an XOR butterfly over a one-device-per-process mesh — round
    ``d`` pairs rank ``r`` with ``r ^ d`` (one full-permutation
    ``ppermute`` each), ``log2 P`` rounds total, counted in
    ``MOVE_STATS["tree_merge_rounds"]``. A non-power-of-two world has no
    single-permutation butterfly; it falls back to one flat
    ``process_allgather`` + a rank-ordered serial fold (still identical
    on every process, rounds counted as 0). ``active=False`` — or a
    single-process world — returns ``state`` unchanged.

    The dispatch runs under the collective watchdog (``label``) and is
    pinned with :func:`collective_lockstep`, so independent merges of
    several estimators stay rendezvous-ordered across controllers.
    """
    nproc = jax.process_count()
    if not active or nproc == 1:
        return state
    from . import _hooks

    leaves, treedef = jax.tree_util.tree_flatten(state)
    np_leaves = [np.asarray(x) for x in leaves]

    def impl():
        _hooks.fault_point(
            label,
            leaves=len(np_leaves),
            shapes=tuple(tuple(x.shape) for x in np_leaves),
            dtypes=tuple(str(x.dtype) for x in np_leaves),
        )
        if nproc & (nproc - 1):  # no butterfly off powers of two
            out = _flat_state_merge(np_leaves, treedef, combine, nproc)
        else:
            out = _butterfly_state_merge(np_leaves, treedef, combine, nproc)
        from ..parallel.flatmove import MOVE_STATS

        MOVE_STATS["tree_merges"] += 1
        MOVE_STATS["tree_merge_rounds"] += tree_merge_rounds(nproc)
        return out

    with _hooks.span("ht.exchange:tree_merge", p=nproc):
        merged = _hooks.guarded_call(label, impl)
    return collective_lockstep(merged)


def _flat_state_merge(np_leaves, treedef, combine, nproc):
    """Fallback: allgather every process's leaves, fold in rank order.
    Serial (P-1 combines) but structurally identical output on all
    ranks; used off power-of-two worlds."""
    from jax.experimental import multihost_utils

    gathered = [
        np.asarray(multihost_utils.process_allgather(x)).reshape((nproc,) + x.shape)
        for x in np_leaves
    ]
    acc = jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(g[0]) for g in gathered]
    )
    for r in range(1, nproc):
        nxt = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(g[r]) for g in gathered]
        )
        acc = combine(acc, nxt)
    return acc


def _butterfly_state_merge(np_leaves, treedef, combine, nproc):
    from jax import lax, shard_map

    from ._cache import ExecutableCache

    global _TREE_PROGRAMS
    if _TREE_PROGRAMS is None:
        _TREE_PROGRAMS = ExecutableCache(maxsize=32)
    pmesh = _process_mesh()
    pid = jax.process_index()
    my_dev = pmesh.devices.ravel()[pid]

    # each process donates its own row of the (P, *shape) stacked state
    stacked = []
    for x in np_leaves:
        pshape = (nproc,) + x.shape
        sharding = NamedSharding(
            pmesh, PartitionSpec(SPLIT_AXIS, *([None] * x.ndim))
        )
        local = jax.device_put(x[None], my_dev)
        stacked.append(
            jax.make_array_from_single_device_arrays(pshape, sharding, [local])
        )

    key = (
        "tree_merge",
        combine,
        treedef,
        tuple((tuple(x.shape), str(x.dtype)) for x in np_leaves),
        pmesh,
    )
    fn = _TREE_PROGRAMS.get(key)
    if fn is None:

        def kernel(*blocks):  # each (1, *shape): this rank's state
            r = lax.axis_index(SPLIT_AXIS)
            acc = [b[0] for b in blocks]
            d = 1
            while d < nproc:
                perm = [(i, i ^ d) for i in range(nproc)]
                recv = [lax.ppermute(a, SPLIT_AXIS, perm) for a in acc]
                own_t = jax.tree_util.tree_unflatten(treedef, acc)
                rec_t = jax.tree_util.tree_unflatten(treedef, recv)
                # rank-ordered operands: the lower rank of each pair goes
                # first, so every rank applies the same balanced tree
                lo = jax.tree_util.tree_leaves(combine(own_t, rec_t))
                hi = jax.tree_util.tree_leaves(combine(rec_t, own_t))
                low_first = (r & d) == 0
                acc = [jnp.where(low_first, a, b) for a, b in zip(lo, hi)]
                d <<= 1
            return tuple(a[None] for a in acc)

        specs = tuple(
            PartitionSpec(SPLIT_AXIS, *([None] * x.ndim)) for x in np_leaves
        )
        # every rank's block carries the identical merged state by
        # construction, which the varying-mesh-axes analysis cannot infer
        prog = shard_map(
            kernel, mesh=pmesh, in_specs=specs, out_specs=specs, check_vma=False
        )
        fn = _TREE_PROGRAMS[key] = jax.jit(prog)
    outs = fn(*stacked)
    # read this process's (identical) copy back off the process mesh; host
    # round-trip decommits the leaf so downstream arithmetic is free to
    # place results wherever the estimator's other arrays live
    merged_leaves = [
        jnp.asarray(np.asarray(o.addressable_shards[0].data)[0]) for o in outs
    ]
    return jax.tree_util.tree_unflatten(treedef, merged_leaves)


def _split_ranks(comm: MeshCommunication):
    """(split_rank, device) for every mesh device.

    A device's shard rank is its COORDINATE along the split mesh axis —
    not its position in ``devices.ravel()``, which diverges on multi-axis
    meshes (e.g. a 2-D DASO mesh, where devices sharing a split coordinate
    replicate the same shard)."""
    devs = comm.mesh.devices
    axis_idx = list(comm.mesh.axis_names).index(SPLIT_AXIS)
    for coords in np.ndindex(devs.shape):
        yield coords[axis_idx], devs[coords]


def assemble_local_shards(local: np.ndarray, split: int, comm: MeshCommunication):
    """Infer the global shape from per-process ``is_split`` shards and build
    the padded global buffer (reference ``factories.py:383-426``: neighbor
    Isend/Probe/Recv shape exchange + Allreduce consistency checks).

    Returns ``(buffer, gshape)``. Non-split dims must agree across
    processes; the split dim is the sum of the local extents. When every
    process holds the same extent and it divides evenly over the local
    devices, blocks align with process boundaries and assembly is
    local-only; otherwise the shards are allgathered once (O(n) host
    memory — the uneven path, like the reference's staged Recv).

    Bounded end-to-end by the collective watchdog when installed
    (``resilience.deadlines``), label ``collective.assemble_local``.
    """
    from . import _hooks

    return _hooks.guarded_call(
        "collective.assemble_local", _assemble_local_shards_impl, local, split, comm
    )


def _assemble_local_shards_impl(local: np.ndarray, split: int, comm: MeshCommunication):
    from jax.experimental import multihost_utils

    nproc = jax.process_count()
    pid = jax.process_index()
    shapes = multihost_utils.process_allgather(np.asarray(local.shape, dtype=np.int64))
    shapes = np.asarray(shapes).reshape(nproc, local.ndim)
    for d in range(local.ndim):
        if d != split and len(set(int(s) for s in shapes[:, d])) != 1:
            raise ValueError(
                f"local shards disagree on non-split dim {d}: {sorted(set(int(s) for s in shapes[:, d]))}"
            )
    sizes = [int(s) for s in shapes[:, split]]
    gshape = list(local.shape)
    gshape[split] = sum(sizes)
    gshape = tuple(gshape)

    block = comm.padded_shape(gshape, split)[split] // comm.size
    # is_split semantics: the global array is the pid-ordered concatenation
    # of the local shards. The local-only fast path requires every device
    # block (rank r covers global rows [r*block, (r+1)*block)) to fall
    # inside its OWN process's rows — true for equal, divisible extents on
    # a process-major mesh. The decision is computed from the REPLICATED
    # (rank, device) placement of the whole mesh, never from this
    # process's local view: a per-host check here diverges on a partially
    # permuted mesh, stranding the aligned hosts while the misaligned
    # ones enter the allgather below (graftflow F001).
    placement = _split_ranks(comm)
    per_proc: Dict[int, int] = {}
    for _r, d in placement:
        per_proc[d.process_index] = per_proc.get(d.process_index, 0) + 1
    dpp = next(iter(per_proc.values()))
    aligned = (
        len(set(sizes)) == 1
        and len(set(per_proc.values())) == 1
        and sizes[0] % dpp == 0
        and sizes[0] // dpp == block
        and all(r * block // sizes[0] == d.process_index for r, d in placement)
    )
    if aligned:
        offset = pid * sizes[0]  # this process's rows in global coordinates

        def read_chunk(slices):
            local_slices = list(slices)
            s = slices[split]
            local_slices[split] = slice(s.start - offset, s.stop - offset)
            return local[tuple(local_slices)]

    else:
        full = np.concatenate(ragged_process_allgather(local, axis=split), axis=split)

        def read_chunk(slices):
            return full[slices]

    buf = _assemble_from_chunks(read_chunk, gshape, split, comm, local.dtype)
    return buf, gshape
