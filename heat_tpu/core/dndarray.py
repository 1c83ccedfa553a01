"""DNDarray — a distributed n-dimensional array backed by a global ``jax.Array``.

Reference: ``heat/core/dndarray.py`` (1763 LoC). There, a DNDarray is a
*local* ``torch.Tensor`` shard plus global metadata, and every cross-rank
interaction is hand-written MPI. Here the underlying object is a **global**
``jax.Array`` carrying a ``NamedSharding`` over the device mesh; the Heat
``split`` axis maps 1:1 onto the mesh axis ``"split"`` of the array's
``PartitionSpec``. Consequences:

- **Padded buffers**: JAX requires every sharded dimension to be divisible
  by the mesh size, so the stored buffer is padded along the split axis to
  ``P * ceil(n / P)`` (``pshape``); the logical extent ``gshape`` is
  metadata. Padding sits strictly at the global tail, so logical index ->
  buffer index is the identity for every valid element; the valid region of
  device ``r``'s block is exactly the reference's ceil-div ``comm.chunk``.
  Pad content is *unspecified* — reductions/contractions mask it with the
  op's neutral element (see ``_operations``), data-movement ops work on the
  logical view (:meth:`_logical`). For divisible shapes (and ``split=None``)
  buffer == logical array and nothing changes.
- **Ragged layouts**: ``redistribute_`` (reference ``dndarray.py:1029``)
  accepts any partition of the split extent; a non-canonical target leaves
  the array in a *ragged* layout (``lcounts`` per-shard valid counts,
  data at offset 0 of each fixed-size block). Elementwise ops, reductions
  and cumops compute directly on ragged buffers (``_operations`` masks
  ragged-invalid rows exactly like tail padding), so ``balance_``
  (reference ``dndarray.py:470``) is reserved for consumers that need the
  canonical ceil-div map — matmul tiles, ``resplit_``, I/O assembly —
  reached via :attr:`larray`. See ``docs/PERFORMANCE.md`` for the layout
  model and per-op alignment costs.
- ``resplit_`` (reference ``dndarray.py:1235-1357``, tile-by-tile
  Isend/Irecv) is a single ``jax.device_put`` to a new sharding — XLA emits
  the optimal all-to-all/all-gather over ICI.
- halo exchange (reference ``dndarray.py:333-441``) is available both as
  global-slice metadata here and as a ``ppermute`` collective in
  :mod:`heat_tpu.parallel.halo` for use inside ``shard_map``.
- distributed ``__getitem__``/``__setitem__`` (reference
  ``dndarray.py:652-1676``, ~1000 lines of rank-local index translation)
  reduce to global ``jnp`` indexing plus a small split-propagation rule.
"""
from __future__ import annotations

from typing import List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import _hooks
from . import communication as comm_module
from . import devices, types
from .communication import MeshCommunication, sanitize_comm
from .devices import Device
from .stride_tricks import sanitize_axis

__all__ = ["DNDarray", "LAYOUT_STATS"]

# Running count of ragged→canonical rebalances actually performed by
# ``balance_`` (no-op calls are not counted). Tests hook this to assert
# that hot compute paths never force the rebalance round-trip. Beside it
# the gauge "lanczos_steps": the matvecs of its operand the last
# ``linalg.lanczos`` ran (``linalg/solver.py``).
LAYOUT_STATS = {"rebalances": 0, "lanczos_steps": 0}


class LocalIndex:
    """Kept for reference-API parity (``dndarray.py`` helper); indexing the
    global array covers all uses on TPU."""

    def __init__(self, obj):
        self.obj = obj

    def __getitem__(self, key):
        return self.obj[key]


class DNDarray:
    """Distributed N-Dimensional array (reference ``dndarray.py:63-86``).

    Parameters
    ----------
    array : jax.Array or array-like
        The global data. Will be placed with the sharding implied by
        ``split`` if not already.
    dtype : heat type, optional
        Inferred from ``array`` if omitted.
    split : int or None
        Axis sharded over the mesh, or None for replication.
    device, comm : placement metadata.
    balanced : bool
        Accepted for API parity; always True on TPU (XLA canonical layout).
    """

    def __init__(
        self,
        array,
        gshape: Optional[Tuple[int, ...]] = None,
        dtype=None,
        split: Optional[int] = None,
        device: Optional[Device] = None,
        comm: Optional[MeshCommunication] = None,
        balanced: bool = True,
    ):
        self.__comm = sanitize_comm(comm)
        self.__device = devices.sanitize_device(device)
        if dtype is not None:
            dtype = types.canonical_heat_type(dtype)
        if not isinstance(array, jax.Array):
            array = jnp.asarray(array, dtype=None if dtype is None else dtype.jax_type())
        if dtype is None:
            dtype = types.canonical_heat_type(array.dtype)
        elif array.dtype != np.dtype(dtype.jax_type()):
            array = array.astype(dtype.jax_type())
        if array.ndim == 0:
            split = None
        if gshape is None:
            gshape = tuple(array.shape)
        else:
            gshape = tuple(int(s) for s in gshape)
        split = sanitize_axis(gshape, split)
        self.__dtype = dtype
        self.__split = split
        self.__gshape = gshape
        self.__lcounts = None
        self.__array = _place(array, self.__comm, split, gshape)

    @classmethod
    def _from_buffer(
        cls,
        buffer: jax.Array,
        gshape: Tuple[int, ...],
        dtype,
        split: Optional[int],
        device: Optional[Device] = None,
        comm: Optional[MeshCommunication] = None,
    ) -> "DNDarray":
        """Wrap an already-padded, already-placed physical buffer.

        Internal fast path for op results: ``buffer.shape`` must equal
        ``comm.padded_shape(gshape, split)``.
        """
        out = cls.__new__(cls)
        out._DNDarray__comm = sanitize_comm(comm)
        out._DNDarray__device = devices.sanitize_device(device)
        out._DNDarray__dtype = types.canonical_heat_type(dtype)
        out._DNDarray__split = split
        out._DNDarray__gshape = tuple(int(s) for s in gshape)
        out._DNDarray__lcounts = None
        out._DNDarray__array = _place(buffer, out._DNDarray__comm, split, out._DNDarray__gshape)
        return out

    @classmethod
    def _from_ragged(
        cls,
        buffer: jax.Array,
        gshape: Tuple[int, ...],
        dtype,
        split: int,
        lcounts: Tuple[int, ...],
        device: Optional[Device] = None,
        comm: Optional[MeshCommunication] = None,
    ) -> "DNDarray":
        """Wrap a *ragged-layout* physical buffer: device ``r`` holds
        ``lcounts[r]`` valid split-axis rows at offset 0 of its block
        (block size ``buffer.shape[split] // P``). This is the TPU
        representation of the reference's unbalanced arrays
        (``dndarray.py:1029``): raggedness is real, observable through
        ``lshape_map``/``local_shards``/``counts_displs``, and elementwise
        ops / reductions / cumops compute directly on it (ragged-invalid
        rows are masked like tail padding — see
        :mod:`heat_tpu.core._operations`). Only consumers of the
        canonical ceil-div map (:meth:`larray`) rebalance.
        """
        comm = sanitize_comm(comm)
        lcounts = tuple(int(c) for c in lcounts)
        gshape = tuple(int(s) for s in gshape)
        p = comm.size
        if len(lcounts) != p or sum(lcounts) != gshape[split]:
            raise ValueError(
                f"lcounts {lcounts} do not partition extent {gshape[split]} over {p} shards"
            )
        if buffer.shape[split] % p or buffer.shape[split] // p < max(lcounts, default=0):
            raise ValueError(
                f"buffer split dim {buffer.shape[split]} cannot hold blocks of {max(lcounts)}"
            )
        out = cls.__new__(cls)
        out._DNDarray__comm = comm
        out._DNDarray__device = devices.sanitize_device(device)
        out._DNDarray__dtype = types.canonical_heat_type(dtype)
        out._DNDarray__split = split
        out._DNDarray__gshape = gshape
        out._DNDarray__lcounts = lcounts
        if _hooks.in_trace_safe():
            # lazy-fusion replay: see _place — placement is the jit's job
            out._DNDarray__array = buffer
        else:
            out._DNDarray__array = jax.device_put(
                buffer, comm.array_sharding(buffer.shape, split)
            )
        return out

    # ------------------------------------------------------------------ meta
    @property
    def larray(self) -> jax.Array:
        """The underlying global physical buffer (``jax.Array``).

        The reference returns the rank-local torch shard
        (``dndarray.py:110``); under single-controller JAX the process
        addresses the global sharded array, which is the analogous handle.
        **The buffer is padded along the split axis** when the logical
        extent does not divide the mesh size (``pshape`` vs ``gshape``);
        use :meth:`_logical` for the exact logical array. Per-device shards
        are available via :attr:`local_shards`.

        A ragged-layout array (after ``redistribute_`` to a non-canonical
        map) is rebalanced in place first — this accessor hands out the
        canonical ceil-div buffer, which is what matmul tiling, resplit
        and I/O assembly consume. Hot compute paths (elementwise ops,
        reductions, cumops) do NOT route through here on ragged arrays;
        they read :attr:`_raw` and mask per-shard ``lcounts`` instead
        (see ``_operations``), so the rebalance (one bounded interval
        exchange, counted in ``LAYOUT_STATS``) only happens for ops that
        genuinely need the canonical map.

        NOTE: basic-index ``__setitem__`` updates the buffer IN PLACE
        (donated scatter — the torch-like mutation the reference performs
        on its local tensor); a handle obtained from this property before
        a setitem is invalidated by it. Re-read ``larray`` after mutating.
        """
        if self.__lcounts is not None:
            self.balance_()
        return self.__array

    @larray.setter
    def larray(self, value):
        """Replace the data; ``value`` is interpreted as the *logical*
        global array (it will be padded/placed as needed)."""
        if not isinstance(value, jax.Array):
            value = jnp.asarray(value)
        gshape = tuple(value.shape)
        split = sanitize_axis(gshape, self.__split)
        self.__lcounts = None
        self.__array = _place(value, self.__comm, split, gshape)
        self.__gshape = gshape
        self.__split = split
        self.__dtype = types.canonical_heat_type(value.dtype)

    def _set_buffer(self, buffer: jax.Array, gshape=None) -> None:
        """Replace the physical buffer in place (internal; buffer must be
        padded for the current split)."""
        gshape = self.__gshape if gshape is None else tuple(int(s) for s in gshape)
        self.__lcounts = None
        self.__array = _place(buffer, self.__comm, self.__split, gshape)
        self.__gshape = gshape
        self.__dtype = types.canonical_heat_type(buffer.dtype)

    @property
    def pshape(self) -> Tuple[int, ...]:
        """Shape of the physical buffer (== ``gshape`` unless padded)."""
        return tuple(self.__array.shape)

    @property
    def _raw(self) -> jax.Array:
        """The physical buffer exactly as stored — no rebalance, no trim.
        Internal: for layout-preserving plumbing (copy, the ragged mover);
        everything else wants :attr:`larray` or :meth:`_logical`."""
        return self.__array

    @property
    def lcounts(self) -> Optional[Tuple[int, ...]]:
        """Per-split-shard valid row counts when the array is in a ragged
        (non-canonical) layout, else None. Set by ``redistribute_`` with a
        non-canonical target map; cleared by ``balance_`` or any
        computation (see :attr:`larray`)."""
        return getattr(self, "_DNDarray__lcounts", None)

    @property
    def padded(self) -> bool:
        """True when the buffer carries tail padding along the split axis."""
        return self.lcounts is not None or tuple(self.__array.shape) != self.__gshape

    def _logical(self) -> jax.Array:
        """The exact logical global array (buffer with tail padding sliced
        off; a ragged array is rebalanced first). Cheap no-op when not
        padded; otherwise an XLA slice that may reshard — intended for
        data-movement ops, not hot elementwise paths.
        """
        if not self.padded:
            return self.__array
        buf = self.larray  # rebalances a ragged layout in place
        sl = tuple(slice(0, s) for s in self.__gshape)
        return buf[sl]

    def _iter_local_shards(self, dedup: bool = False):
        """Yield ``(split_start, trimmed_shard)`` for each addressable
        shard in split-start order — THE padded-shard trimming invariant
        (valid extent = min(n - start, block)); every consumer of
        process-local shard data routes through here so the formula lives
        once. ``dedup`` skips replicated devices (multi-axis meshes) that
        hold the same split coordinate."""
        shards = sorted(
            self.__array.addressable_shards,
            key=lambda s: tuple(sl.start or 0 for sl in s.index),
        )
        split = self.__split
        lcounts = self.lcounts
        if lcounts is not None:
            # ragged layout: shard r holds lcounts[r] valid rows at local
            # offset 0; its logical start is the running displacement
            block = self.__array.shape[split] // self.__comm.size
            _, displs = self.counts_displs()
            seen = set()
            for s in shards:
                r = (s.index[split].start or 0) // block
                if dedup:
                    if r in seen:
                        continue
                    seen.add(r)
                sl = [slice(None)] * self.ndim
                sl[split] = slice(0, int(lcounts[r]))
                yield int(displs[r]), s.data[tuple(sl)]
            return
        if dedup and split is None:
            # every replica would share key 0 and all but one shard would
            # silently vanish; callers must handle replicated arrays
            raise ValueError("dedup=True requires a split array")
        seen = set()
        for s in shards:
            start = 0 if split is None else (s.index[split].start or 0)
            if dedup:
                if start in seen:
                    continue
                seen.add(start)
            if split is None or not self.padded:
                yield start, s.data
                continue
            n = self.__gshape[split]
            valid = max(0, min(n - start, s.data.shape[split]))
            sl = [slice(None)] * self.ndim
            sl[split] = slice(0, valid)
            yield start, s.data[tuple(sl)]

    @property
    def local_shards(self) -> List[jax.Array]:
        """Per-device addressable shards, trimmed to their *valid* extent
        (TPU-native view of 'local' data): shard ``r``'s shape equals the
        reference's ``comm.chunk`` result even when the buffer is padded."""
        return [data for _, data in self._iter_local_shards()]

    @property
    def comm(self) -> MeshCommunication:
        return self.__comm

    @comm.setter
    def comm(self, comm):
        buf = self.larray  # rebalance under the old comm first
        self.__comm = sanitize_comm(comm)
        self.__array = _place(buf, self.__comm, self.__split)

    @property
    def device(self) -> Device:
        return self.__device

    @device.setter
    def device(self, device):
        self.__device = devices.sanitize_device(device)

    @property
    def dtype(self):
        return self.__dtype

    @property
    def split(self) -> Optional[int]:
        return self.__split

    @property
    def gshape(self) -> Tuple[int, ...]:
        return self.__gshape

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.__gshape

    @property
    def lshape(self) -> Tuple[int, ...]:
        """Shape of the data addressable by *this process* (reference: the
        rank-local shape, ``dndarray.py:172``). Single-host this is the
        whole logical array; multi-host it is the union of the valid chunks
        of this process's devices (a contiguous split-axis range, since mesh
        order is process-major)."""
        if self.__split is None:
            return self.__gshape
        counts, displs = self.counts_displs()
        pid = jax.process_index()
        # Index devices by their coordinate along the mesh's SPLIT axis only
        # (_split_ranks): on a multi-axis mesh (e.g. DASO's (slow, split))
        # the raveled device order must not index counts/displs (length =
        # split extent). A process owning devices at several slow positions
        # sees the union of their split ranges (the slow axis replicates a
        # split-sharded array).
        mine = sorted(
            {
                r
                for r, d in comm_module._split_ranks(self.__comm)
                if d.process_index == pid
            }
        )
        if not mine:  # pragma: no cover - defensive
            mine = list(range(len(counts)))
        lo = displs[mine[0]]
        hi = displs[mine[-1]] + counts[mine[-1]]
        lshape = list(self.__gshape)
        lshape[self.__split] = hi - lo
        return tuple(lshape)

    @property
    def lshape_map(self) -> np.ndarray:
        """(size, ndim) map of every shard's shape — computed, not
        communicated (reference ``dndarray.py:569-600`` used an Allreduce)."""
        lcounts = self.lcounts
        if lcounts is not None:
            out = np.tile(np.asarray(self.__gshape, dtype=np.int64), (self.__comm.size, 1))
            out[:, self.__split] = lcounts
            return out
        return self.__comm.lshape_map(self.gshape, self.__split)

    def create_lshape_map(self, force_check: bool = False) -> np.ndarray:
        return self.lshape_map

    @property
    def balanced(self) -> bool:
        return self.lcounts is None

    def is_balanced(self, force_check: bool = False) -> bool:
        """Whether the layout is the canonical ceil-div one (reference
        ``dndarray.py:508``). False only after a ``redistribute_`` to a
        non-canonical target map."""
        return self.lcounts is None

    def health_check(self, check_values: bool = False) -> "DNDarray":
        """Validate this array's distributed invariants — ``gshape`` vs
        ``lshape_map`` vs the physical buffer, dtype annotation, split
        range; ``check_values=True`` additionally scans the logical values
        for NaN/Inf. Raises :class:`heat_tpu.resilience.ValidationError`
        on any violation; returns ``self`` when healthy (chainable)."""
        from ..resilience.validate import validate

        return validate(self, check_values=check_values)

    @property
    def ndim(self) -> int:
        return len(self.__gshape)

    @property
    def size(self) -> int:
        return int(np.prod(self.__gshape)) if self.__gshape else 1

    @property
    def gnumel(self) -> int:
        return self.size

    @property
    def lnumel(self) -> int:
        return int(np.prod(self.lshape))

    @property
    def nbytes(self) -> int:
        return self.size * np.dtype(self.__dtype.jax_type()).itemsize

    @property
    def gnbytes(self) -> int:
        return self.nbytes

    @property
    def lnbytes(self) -> int:
        return self.lnumel * np.dtype(self.__dtype.jax_type()).itemsize

    @property
    def imag(self) -> "DNDarray":
        from . import complex_math

        return complex_math.imag(self)

    @property
    def real(self) -> "DNDarray":
        from . import complex_math

        return complex_math.real(self)

    @property
    def T(self) -> "DNDarray":
        from .linalg import transpose

        return transpose(self)

    @property
    def loc(self) -> LocalIndex:
        return LocalIndex(self.larray)

    @property
    def lloc(self) -> LocalIndex:
        """Local-shard indexing view (reference ``dndarray.py:239``)."""
        return LocalIndex(self.larray)

    @property
    def stride(self) -> Tuple[int, ...]:
        """Element strides of the (C-contiguous) global array (reference
        ``dndarray.py:308``)."""
        strides = []
        acc = 1
        for dim in reversed(self.gshape):
            strides.append(acc)
            acc *= dim
        return tuple(reversed(strides))

    @property
    def strides(self) -> Tuple[int, ...]:
        """Byte strides, numpy-style (reference ``dndarray.py:315``)."""
        item = np.dtype(self.__dtype.jax_type()).itemsize
        return tuple(s * item for s in self.stride)

    @property
    def halo_next(self):
        """Halos received from the *next* shard, for every inter-shard
        boundary (reference ``dndarray.py:124`` stored the per-rank received
        buffer; single-controller JAX exposes all boundaries at once).

        Shape ``(num_shards - 1, ..., halo_size, ...)`` with ``halo_size``
        replacing the split dimension: entry ``i`` is the halo shard ``i``
        receives from shard ``i + 1``.
        """
        hs = self.halo_size
        if hs == 0 or self.__split is None:
            return None
        counts, displs = self.counts_displs()  # honors a ragged layout
        log = self._logical()  # slices below are in logical coordinates
        slabs = []
        for i in range(1, len(counts)):
            # a halo crosses boundary i only when both neighbors hold >= hs
            if counts[i - 1] < hs or counts[i] < hs:
                continue
            sl = [slice(None)] * self.ndim
            sl[self.__split] = slice(displs[i], displs[i] + hs)
            slabs.append(log[tuple(sl)])
        return jnp.stack(slabs) if slabs else None

    @property
    def halo_prev(self):
        """Halos received from the *previous* shard, for every inter-shard
        boundary (reference ``dndarray.py:131``): entry ``i`` is the halo
        shard ``i + 1`` receives from shard ``i``."""
        hs = self.halo_size
        if hs == 0 or self.__split is None:
            return None
        counts, displs = self.counts_displs()  # honors a ragged layout
        log = self._logical()
        slabs = []
        for i in range(1, len(counts)):
            if counts[i - 1] < hs or counts[i] < hs:
                continue
            sl = [slice(None)] * self.ndim
            sl[self.__split] = slice(max(displs[i] - hs, 0), displs[i])
            slabs.append(log[tuple(sl)])
        return jnp.stack(slabs) if slabs else None

    def counts_displs(self) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """Per-device item counts and offsets along the split axis
        (reference ``dndarray.py:543``)."""
        if self.__split is None:
            raise ValueError(
                "Non-distributed DNDarray. Cannot calculate counts and displacements."
            )
        counts = self.lshape_map[:, self.__split]
        displs = np.concatenate([[0], np.cumsum(counts)[:-1]])
        return tuple(int(c) for c in counts), tuple(int(d) for d in displs)

    def is_distributed(self) -> bool:
        """Whether data lives on more than one device (reference
        ``dndarray.py:952``)."""
        return self.__split is not None and self.__comm.is_distributed()

    def cpu(self) -> "DNDarray":
        """Return a host-memory copy (reference ``dndarray.py:560`` moved
        torch storage to CPU). The returned DNDarray's buffer lives on the
        JAX CPU backend — it does not occupy accelerator HBM."""
        host = jax.device_put(
            jnp.asarray(self.numpy()), jax.local_devices(backend="cpu")[0]
        )
        out = DNDarray.__new__(DNDarray)
        out._DNDarray__comm = self.__comm
        out._DNDarray__device = devices.cpu
        out._DNDarray__dtype = self.__dtype
        out._DNDarray__split = None
        out._DNDarray__gshape = self.__gshape
        out._DNDarray__lcounts = None
        out._DNDarray__array = host
        return out

    # ------------------------------------------------------------- placement
    def resplit_(self, axis: Optional[int] = None) -> "DNDarray":
        """In-place redistribution to a new split axis (reference
        ``dndarray.py:1235``). One ``device_put``; XLA chooses the collective
        (all-gather for ``axis=None``, all-to-all for split->split).

        Watchdog-bounded (label ``collective.resplit``) when
        ``resilience.deadlines`` is active — a resharding that wedges on
        the interconnect surfaces as ``CollectiveTimeout``, not a hang."""
        from . import _hooks

        axis = sanitize_axis(self.gshape, axis)
        if axis == self.__split:
            return self

        def reshard():
            _hooks.fault_point(
                "collective.resplit", gshape=self.__gshape, to_split=axis
            )
            out = _place(self._logical(), self.__comm, axis, self.__gshape, force=True)
            if _hooks.get_deadline_runner() is not None:
                out = out.block_until_ready()  # keep the wedge inside the deadline
            return out

        self.__array = _hooks.guarded_call("collective.resplit", reshard)
        self.__split = axis
        return self

    def resplit(self, axis: Optional[int] = None) -> "DNDarray":
        """Out-of-place resplit (reference ``manipulations.py:3329``)."""
        axis = sanitize_axis(self.gshape, axis)
        return DNDarray(
            self._logical(),
            gshape=self.__gshape,
            dtype=self.__dtype,
            split=axis,
            device=self.__device,
            comm=self.__comm,
        )

    def redistribute_(self, lshape_map=None, target_map=None) -> "DNDarray":
        """Move data to a target per-shard shape map (reference
        ``dndarray.py:1029-1233``, chained Send/Recv there).

        Any map that partitions the split extent is accepted, like the
        reference's — including skewed and empty shards:

        - the current map (canonical or ragged): no-op;
        - the canonical map of a *different* split axis: one resharding
          (XLA chooses the collective);
        - any other partition of the split extent: a ragged interval
          exchange (:func:`heat_tpu.parallel.flatmove.ragged_move` —
          colored ``ppermute`` rounds, per-device memory O(block)). The
          result is a *ragged-layout* array: ``lshape_map`` /
          ``local_shards`` / ``counts_displs`` reflect the target map
          exactly; any subsequent computation rebalances first (see
          :attr:`larray`).

        ``lshape_map`` (the current-layout hint in the reference, computed
        there with an Allreduce) is validated against the true metadata.
        """
        if lshape_map is not None:
            given = np.asarray(lshape_map)
            if given.shape != self.lshape_map.shape or not np.array_equal(
                given, self.lshape_map
            ):
                raise ValueError(
                    f"lshape_map {given.tolist()} does not describe this array's "
                    f"current layout {self.lshape_map.tolist()}"
                )
        if target_map is None:
            return self
        target = np.asarray(target_map)
        # 0-d arrays have an empty (size, 0) map — matching lshape_map's
        # convention, so the identity early-return below covers them
        size, ndim = self.__comm.size, self.ndim
        if target.shape != (size, ndim):
            raise ValueError(
                f"target_map must have shape {(size, ndim)}, got {target.shape}"
            )
        if (target < 0).any():
            raise ValueError("target_map entries must be non-negative")
        if np.array_equal(target, self.lshape_map):
            return self  # already in this layout (covers split=None too)
        split = self.__split
        if split is not None:
            non_split = [k for k in range(ndim) if k != split]
            counts = target[:, split]
            if (
                all((target[:, k] == self.__gshape[k]).all() for k in non_split)
                and int(counts.sum()) == self.__gshape[split]
            ):
                return self._ragged_redistribute(tuple(int(c) for c in counts))
        for axis in ([split] if split is not None else []) + [
            k for k in range(self.ndim) if k != split
        ]:
            if np.array_equal(target, self.__comm.lshape_map(self.gshape, axis)):
                if axis != self.__split:
                    self.resplit_(axis)
                return self
        raise ValueError(
            "target_map neither partitions the split extent nor matches the "
            "canonical layout of any split axis"
        )

    def _ragged_redistribute(self, counts: Tuple[int, ...]) -> "DNDarray":
        """In-place interval exchange from the current layout to per-shard
        split-axis ``counts`` (sum equals the split extent)."""
        from ..parallel.flatmove import ragged_move

        split = self.__split
        p = self.__comm.size
        cur = tuple(int(c) for c in self.lshape_map[:, split])
        canonical = self.__comm.counts_displs_shape(self.__gshape, split)[0]
        b_out = max(1, max(counts))
        if counts == tuple(canonical):
            # target IS the canonical map: land exactly on the canonical
            # padded buffer and drop the ragged state
            b_out = self.__comm.padded_dim(self.__gshape[split]) // p
        if counts == cur and self.__array.shape[split] // p == b_out:
            # already in the target layout PHYSICALLY (counts alone are
            # not enough: a ragged buffer whose counts happen to equal a
            # map can still carry a wider block — e.g. a shuffle result
            # whose group counts coincide with the ceil-div map)
            if counts == tuple(canonical) and self.__lcounts is not None:
                self.__lcounts = None
                self.__array = _place(
                    self.__array, self.__comm, split, self.__gshape, force=True
                )
            return self
        _hooks.trace_barrier("redistribute_")
        buf = ragged_move(self.__array, split, cur, counts, b_out, self.__comm)
        if counts == tuple(canonical):
            self.__lcounts = None
            self.__array = _place(buf, self.__comm, split, self.__gshape, force=True)
        else:
            self.__lcounts = counts
            self.__array = jax.device_put(
                buf, self.__comm.array_sharding(buf.shape, split)
            )
        return self

    def balance_(self) -> "DNDarray":
        """Rebalance to the canonical ceil-div layout (reference
        ``dndarray.py:470``). No-op unless the array is in a ragged layout
        from ``redistribute_``; then one bounded interval exchange.

        Elementwise ops, reductions and cumops compute directly on ragged
        layouts (see :mod:`heat_tpu.core._operations`), so this is only
        needed by consumers of the canonical ceil-div map — matmul tiling,
        ``resplit_``, I/O assembly — all of which reach it via
        :attr:`larray`. ``LAYOUT_STATS["rebalances"]`` counts the
        exchanges actually performed (tests hook it to prove hot paths
        stay ragged)."""
        if self.lcounts is not None:
            _hooks.trace_barrier("balance_")
            LAYOUT_STATS["rebalances"] += 1
            canonical, _, _ = self.__comm.counts_displs_shape(self.__gshape, self.__split)
            self._ragged_redistribute(tuple(canonical))
        return self

    def get_halo(self, halo_size: int) -> None:
        """Fetch split-axis neighbor halos (reference ``dndarray.py:333-441``).

        Stores ``halo_prev``/``halo_next`` global-slice views. The
        collective version for use inside ``shard_map`` lives in
        :func:`heat_tpu.parallel.halo.exchange`.
        """
        if not isinstance(halo_size, int) or halo_size < 0:
            raise (TypeError if not isinstance(halo_size, int) else ValueError)(
                f"halo_size needs to be a non-negative int, got {halo_size}"
            )
        self.__halo_size = halo_size

    @property
    def halo_size(self) -> int:
        return getattr(self, "_DNDarray__halo_size", 0)

    def array_with_halos(self) -> jax.Array:
        """Global array (halos are implicit in the global view); kept for
        API parity with reference ``dndarray.py:445``."""
        return self.larray

    # ------------------------------------------------------------ conversion
    def astype(self, dtype, copy: bool = True) -> "DNDarray":
        """Cast to a new heat type (reference ``dndarray.py:451``).
        Layout-preserving: a ragged array casts in place without
        rebalancing (elementwise, no data movement)."""
        dtype = types.canonical_heat_type(dtype)
        buf = self.__array
        casted = buf.astype(dtype.jax_type())
        if copy:
            if casted is buf:
                # same-dtype astype returns the SAME array; a true copy is
                # required because basic-index setitem donates its buffer
                # (an aliasing "copy" would be deleted with the original)
                casted = jnp.copy(casted)
            if self.__lcounts is not None:
                return DNDarray._from_ragged(
                    casted, self.__gshape, dtype, self.__split, self.__lcounts,
                    self.__device, self.__comm,
                )
            return DNDarray._from_buffer(
                casted, self.__gshape, dtype, self.__split, self.__device, self.__comm
            )
        self.__array = casted
        self.__dtype = dtype
        return self

    def numpy(self) -> np.ndarray:
        """Gather the logical global array to host memory (reference
        ``dndarray.py:991``). Tail padding is sliced off host-side.

        Multi-host, a split array is assembled with ONE ragged process
        allgather of the valid local blocks (every process must call —
        collective, like the reference's ``resplit(None)`` gather)."""
        _hooks.observe("host.gather", shape=self.__gshape)
        with _hooks.span("ht.fetch:dndarray.gather"):
            return self._gather_to_host()

    def _gather_to_host(self) -> np.ndarray:
        buf = self.larray
        if getattr(buf, "is_fully_addressable", True):
            host = np.asarray(jax.device_get(buf))
            if tuple(host.shape) != self.__gshape:
                host = host[tuple(slice(0, s) for s in self.__gshape)]
            return host
        if self.__split is None:
            # replicated: any local device holds the full array
            return np.asarray(jax.device_get(buf.addressable_shards[0].data))
        split = self.__split
        shards = [
            (start, np.asarray(jax.device_get(shard)))
            for start, shard in self._iter_local_shards(dedup=True)
            if shard.shape[split] > 0  # empty trims carry no data
        ]
        starts = [s for s, _ in shards]
        sizes = [d.shape[split] for _, d in shards]
        contiguous = all(
            starts[i] + sizes[i] == starts[i + 1] for i in range(len(shards) - 1)
        )
        # fast path: each process owns one contiguous split range and
        # process order equals split order (process-major meshes — the
        # default); a permuted mesh takes the place-by-offset fallback
        # (the alignment guard assemble_local_shards applies, comm:489).
        # The decision must be GLOBAL — ranks disagreeing on the path
        # would dispatch different collective sequences — so the local
        # contiguity flag rides along with the range start.
        from jax.experimental import multihost_utils

        lo = starts[0] if starts else self.__gshape[split]
        meta = np.asarray(
            multihost_utils.process_allgather(
                np.asarray([lo, int(contiguous)], np.int64)
            )
        ).reshape(-1, 2)
        aligned = bool(meta[:, 1].all()) and bool(
            (np.diff(meta[:, 0]) > 0).all()
            # strictly increasing: EQUAL starts mean a replication axis
            # spans processes (each holds the full range) — concatenating
            # replicas would multiply the extent; the coverage-mask
            # fallback handles that layout
        )
        np_dtype = np.dtype(self.__dtype.jax_type())
        if aligned:
            if shards:
                local = np.concatenate([d for _, d in shards], axis=split)
            else:  # pragma: no cover - a process with no valid rows
                shape = list(self.__gshape)
                shape[split] = 0
                local = np.zeros(shape, np_dtype)
            blocks = comm_module.ragged_process_allgather(local, axis=split)
            return np.concatenate(blocks, axis=split)
        # fallback (permuted device order): place local shards at their
        # logical offsets and merge across processes by coverage mask
        out = np.zeros(self.__gshape, np_dtype)
        covered = np.zeros(self.__gshape[split], bool)
        for start, d in shards:
            sl = [slice(None)] * self.ndim
            sl[split] = slice(start, start + d.shape[split])
            out[tuple(sl)] = d
            covered[start : start + d.shape[split]] = True
        all_out = np.asarray(multihost_utils.process_allgather(out))
        all_cov = np.asarray(multihost_utils.process_allgather(covered))
        for p_i in range(all_out.shape[0]):
            mask = all_cov[p_i] & ~covered
            if mask.any():
                sl = [slice(None)] * self.ndim
                sl[split] = mask
                out[tuple(sl)] = all_out[p_i][tuple(sl)]
                covered |= all_cov[p_i]
        return out

    def __array__(self, dtype=None):
        out = self.numpy()
        return out.astype(dtype) if dtype is not None else out

    def tolist(self, keepsplit: bool = False):
        return self.numpy().tolist()

    def item(self):
        """Scalar extraction (reference ``dndarray.py:955``)."""
        _hooks.observe("host.item")
        with _hooks.span("ht.fetch:dndarray.item"):
            if self.padded:
                return self._logical().item()
            return self.__array.item()

    def __bool__(self) -> bool:
        return bool(self.__cast(bool))

    def __int__(self) -> int:
        return int(self.__cast(int))

    def __float__(self) -> float:
        return float(self.__cast(float))

    def __complex__(self) -> complex:
        return complex(self.__cast(complex))

    def __cast(self, cast_function):
        if np.prod(self.shape) == 1:
            _hooks.observe("host.scalar")
            with _hooks.span("ht.fetch:dndarray.scalar"):
                return cast_function(self._logical().reshape(()).item())
        raise TypeError("only size-1 arrays can be converted to Python scalars")

    def __len__(self) -> int:
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.gshape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # --------------------------------------------------------------- fill ops
    def fill_diagonal(self, value) -> "DNDarray":
        """Fill the main diagonal in place (reference ``dndarray.py:608``)."""
        n = min(self.gshape[0], self.gshape[1]) if self.ndim >= 2 else 0
        if self.ndim != 2:
            raise ValueError("input array must be 2D")
        idx = jnp.arange(n)
        self.__array = _place(
            self.larray.at[idx, idx].set(value),
            self.__comm,
            self.__split,
            self.__gshape,
        )
        return self

    # -------------------------------------------------------------- indexing
    def __getitem__(self, key) -> "DNDarray":
        """Global indexing (reference ``dndarray.py:652-908``).

        The result's split follows the reference's rules: slicing keeps the
        split (shifted over removed dims); a scalar index on the split axis
        replicates; advanced indexing on the split axis yields split=0.
        """
        buf = self.larray  # rebalances a ragged layout first
        key_t, out_split = self.__translate_key(key)
        fast = self.__basic_getitem(buf, key_t, out_split)
        if fast is not None:
            return fast
        result = buf[key_t]
        if isinstance(result, jax.Array) and result.ndim == 0:
            out_split = None
        return DNDarray(
            result,
            dtype=self.__dtype,
            split=out_split if result.ndim else None,
            device=self.__device,
            comm=self.__comm,
        )

    def __basic_getitem(self, buf, key_t, out_split):
        """Basic-index fast path: one cached pinned pipeline per key
        structure (ints become traced operands). Returns None when the key
        is not basic (advanced/bool/scalar-bool) or the array is not
        distributed — the caller then takes the eager path."""
        if self.__split is None or not self.__comm.is_distributed():
            return None
        key_seq = list(key_t) if isinstance(key_t, tuple) else [key_t]
        struct: List[Tuple] = []
        ints: List[int] = []
        in_dim = 0
        for pos, k in enumerate(key_seq):
            if k is None:
                struct.append(("n",))
                continue
            if isinstance(k, (bool, np.bool_)):
                return None
            if isinstance(k, (int, np.integer)):
                k = int(k)
                if k < 0:  # dynamic gather clamps; wrap host-side
                    k += self.__gshape[in_dim]
                if not 0 <= k < self.__gshape[in_dim]:
                    # traced indices clamp/zero instead of raising; keep
                    # the reference's (numpy's) IndexError contract
                    raise IndexError(
                        f"index {k} is out of bounds for axis {in_dim} with "
                        f"size {self.__gshape[in_dim]}"
                    )
                # split-dim ints lower as a one-hot contraction ('I') so
                # GSPMD never gathers the operand
                struct.append(("I",) if in_dim == self.__split else ("i",))
                ints.append(k)
                in_dim += 1
            elif isinstance(k, slice):
                if in_dim == self.__split:
                    start, stop, step = k.indices(self.__gshape[in_dim])
                    if step != 1:
                        return self.__strided_split_getitem(
                            buf, key_seq, pos, start, stop, step
                        )
                struct.append(("s", k.start, k.stop, k.step))
                in_dim += 1
            else:
                return None
        # shape of the logical result (independent of the int values)
        static_key = tuple(
            0 if t[0] in ("i", "I") else (slice(t[1], t[2], t[3]) if t[0] == "s" else None)
            for t in struct
        )
        out_gshape = jax.eval_shape(
            lambda b: b[static_key], jax.ShapeDtypeStruct(buf.shape, buf.dtype)
        ).shape
        if len(out_gshape) == 0 or 0 in out_gshape:
            # scalar or empty result: nothing to distribute (XLA refuses
            # pinned shardings on zero-size outputs)
            return None
        from ._movement import getitem_executable

        fn = getitem_executable(
            buf.shape, buf.dtype, self.__split, tuple(struct),
            tuple(out_gshape), out_split, self.__comm,
        )
        return DNDarray._from_buffer(
            fn(buf, *ints), out_gshape, self.__dtype, out_split,
            self.__device, self.__comm,
        )

    def __strided_split_getitem(self, buf, key_seq, pos, start, stop, step):
        """A step != 1 slice on the split axis: GSPMD's partitioner would
        all-gather (strided selection breaks the interval structure), so
        run the strided-take interval-exchange kernel
        (:func:`heat_tpu.parallel.flatmove.strided_take`) — negative
        steps as positive-take + pinned flip — then apply the remaining
        key dims through the regular pipeline."""
        from ..parallel.flatmove import strided_take

        split = self.__split
        m = len(range(start, stop, step))
        if m == 0:
            return None  # empty result: the eager path handles it exactly
        if step > 0:
            buf2, _ = strided_take(
                buf, split, self.__gshape[split], start, stop, step, self.__comm
            )
        else:
            first = start + step * (m - 1)
            buf2, _ = strided_take(
                buf, split, self.__gshape[split], first, start + 1, -step, self.__comm
            )
        mid_gshape = tuple(
            m if d == split else s for d, s in enumerate(self.__gshape)
        )
        mid = DNDarray._from_buffer(
            buf2, mid_gshape, self.__dtype, split, self.__device, self.__comm
        )
        if step < 0:
            from ._movement import flip_padded

            mid = DNDarray._from_buffer(
                flip_padded(mid.larray, mid_gshape, split, split, self.__comm),
                mid_gshape, self.__dtype, split, self.__device, self.__comm,
            )
        rest = list(key_seq)
        rest[pos] = slice(None)
        return mid[tuple(rest)]

    def __setitem__(self, key, value) -> None:
        """Global scatter-update (reference ``dndarray.py:1359-1676``).

        Keys are normalized to the logical extent, so only valid elements
        are ever written; tail padding stays untouched.

        Basic-index keys (ints/slices) run as a cached donated jitted
        scatter with pinned shardings — in-place on device, O(updates)
        for a loop of setitems, matching the reference's local in-place
        write (``dndarray.py:1359``). Advanced keys fall back to an eager
        sharding-preserving update."""
        buf = self.larray  # rebalances a ragged layout first
        key_t, _ = self.__translate_key(key)
        if isinstance(value, DNDarray):
            value = value._logical()
        value = jnp.asarray(value, dtype=self.__dtype.jax_type())
        struct: List[Tuple] = []
        ints: List[int] = []
        in_dim = 0
        for k in key_t if isinstance(key_t, tuple) else (key_t,):
            if k is None or isinstance(k, (bool, np.bool_)):
                break  # newaxis / scalar-bool keys: rare, eager path
            if isinstance(k, (int, np.integer)):
                k = int(k)
                if k < 0:
                    k += self.__gshape[in_dim]
                if not 0 <= k < self.__gshape[in_dim]:
                    # a traced scatter index would silently DROP the
                    # out-of-bounds update; keep the IndexError contract
                    raise IndexError(
                        f"index {k} is out of bounds for axis {in_dim} with "
                        f"size {self.__gshape[in_dim]}"
                    )
                struct.append(("i",))
                ints.append(k)
                in_dim += 1
            elif isinstance(k, slice):
                struct.append(("s", k.start, k.stop, k.step))
                in_dim += 1
            else:
                break
        else:
            from ._movement import setitem_executable

            if value is buf:
                # self-assignment (a[:] = a on an unpadded array): the
                # donated argument must not alias an operand
                value = jnp.copy(value)
            fn = setitem_executable(
                buf.shape, buf.dtype, self.__split, tuple(struct),
                tuple(value.shape), value.dtype, self.__comm,
            )
            self.__array = fn(buf, value, *ints)
            return
        # advanced indexing: eager update keeps the operand's sharding, so
        # _place is a metadata no-op (no forced device_put)
        self.__array = _place(
            buf.at[key_t].set(value),
            self.__comm,
            self.__split,
            self.__gshape,
        )

    def __translate_key(self, key):
        """Normalize an index key against the *logical* shape and compute
        the resulting split axis.

        Keys addressing the (possibly padded) split dimension are rewritten
        so they can never select tail padding: slices get explicit logical
        bounds, negative scalars/arrays are wrapped mod the logical extent,
        boolean masks are False-padded to the buffer extent.
        """
        split = self.__split
        if isinstance(key, DNDarray):
            # coordinate-list indexing: x[nonzero(x)] with an (n, ndim) int
            # key selects per-row coordinates (reference torch-style
            # ``dndarray.py:700-707`` handling of nonzero results)
            if (
                key.ndim == 2
                and self.ndim > 1
                and key.gshape[1] == self.ndim
                and types.issubdtype(key.dtype, types.integer)
            ):
                logical_key = key._logical()
                cols = tuple(logical_key[:, d] for d in range(self.ndim))
                return cols, (0 if split is not None else None)
            key = key._logical()
        if not isinstance(key, tuple):
            key = (key,)
        key = tuple(k._logical() if isinstance(k, DNDarray) else k for k in key)
        # jnp accepts builtin-bool scalar keys but asserts on np.bool_ ones
        key = tuple(bool(k) if isinstance(k, np.bool_) else k for k in key)
        # expand ellipsis ("in"/.index would trip elementwise == on array keys);
        # a multi-dim boolean mask consumes mask.ndim input dims
        def _consumed(k):
            if k is None or k is Ellipsis:
                return 0
            if isinstance(k, (bool, np.bool_)):
                return 0  # scalar bool adds an axis, consumes no input dim
            a = np.asarray(k) if not isinstance(k, (jax.Array, np.ndarray, slice, int, np.integer)) else k
            if isinstance(a, (jax.Array, np.ndarray)) and a.dtype == np.bool_:
                return a.ndim
            return 1

        n_specified = sum(_consumed(k) for k in key)
        e = next((i for i, k in enumerate(key) if k is Ellipsis), None)
        if e is not None:
            fill = (slice(None),) * (self.ndim - n_specified)
            key = key[:e] + fill + key[e + 1 :]
            n_specified = self.ndim  # ellipsis expansion covers every dim
        # numpy's IndexError contract on EVERY path: static jnp indexing
        # clamps out-of-bounds scalars instead of raising
        dim = 0
        for k in key:
            c = _consumed(k)
            if c and dim + c > self.ndim:
                raise IndexError(
                    f"too many indices for array with {self.ndim} dimensions"
                )
            if isinstance(k, (int, np.integer)) and not isinstance(k, (bool, np.bool_)):
                d = self.__gshape[dim]
                if not -d <= int(k) < d:
                    raise IndexError(
                        f"index {int(k)} is out of bounds for axis {dim} with size {d}"
                    )
            dim += c
        if split is None:
            return key, None
        needs_norm = self.padded
        n_split = self.__gshape[split]
        n_buf = self.__array.shape[split]
        if needs_norm and n_specified <= split:
            # make sure the split dim is explicitly keyed so normalization
            # below can exclude the tail padding
            key = key + (slice(None),) * (split + 1 - n_specified)
        # walk input dims -> output dims to find where split lands,
        # normalizing split-dim keys against the logical extent
        in_dim = 0
        out_dim = 0
        out_split: Optional[int] = None
        new_key = []
        for k in key:
            if k is None:
                new_key.append(k)
                out_dim += 1
                continue
            if isinstance(k, (bool, np.bool_)):
                new_key.append(k)
                out_dim += 1  # scalar bool adds an axis, consumes none
                continue
            if in_dim == split:
                if isinstance(k, slice):
                    out_split = out_dim
                    if needs_norm:
                        k = _normalize_slice(k, n_split)
                elif isinstance(k, (int, np.integer)):
                    out_split = None  # scalar on split axis -> replicated bcast
                    if not -n_split <= int(k) < n_split:
                        # validate HERE: wrapping an already-wrapped value
                        # downstream would alias a valid index
                        raise IndexError(
                            f"index {int(k)} is out of bounds for axis "
                            f"{split} with size {n_split}"
                        )
                    if needs_norm and k < 0:
                        k = int(k) + n_split
                else:
                    out_split = 0  # advanced index on split axis -> split 0
                    if needs_norm:
                        arr = jnp.asarray(k)
                        if arr.dtype == jnp.bool_:
                            # mask covers dims [in_dim, in_dim + arr.ndim);
                            # False-pad the split-dim axis to buffer extent
                            pads = [(0, 0)] * arr.ndim
                            pads[split - in_dim] = (0, n_buf - n_split)
                            k = jnp.pad(arr, pads, constant_values=False)
                        else:
                            k = jnp.where(arr < 0, arr + n_split, arr)
                new_key.append(k)
                in_dim += 1
                out_dim += 1 if not isinstance(k, (int, np.integer)) else 0
                continue
            if isinstance(k, (int, np.integer)):
                in_dim += 1
            elif isinstance(k, slice):
                in_dim += 1
                out_dim += 1
            else:  # array-like advanced index
                arr = np.asarray(k) if not isinstance(arr_k := k, jax.Array) else arr_k
                if arr.dtype == np.bool_ or arr.dtype == jnp.bool_:
                    if needs_norm and in_dim < split < in_dim + arr.ndim:
                        pads = [(0, 0)] * arr.ndim
                        pads[split - in_dim] = (0, n_buf - n_split)
                        k = jnp.pad(jnp.asarray(arr), pads, constant_values=False)
                    in_dim += arr.ndim
                else:
                    in_dim += 1
                out_dim += 1
            new_key.append(k)
        key = tuple(new_key)
        # trailing unindexed dims: split stays at its offset position
        if in_dim <= split and out_split is None:
            out_split = out_dim + (split - in_dim)
        return key, out_split

    # ------------------------------------------------------------ arithmetic
    def __add__(self, other):
        from . import arithmetics

        return arithmetics.add(self, other)

    __radd__ = __add__

    def __sub__(self, other):
        from . import arithmetics

        return arithmetics.sub(self, other)

    def __rsub__(self, other):
        from . import arithmetics

        return arithmetics.sub(other, self)

    def __mul__(self, other):
        from . import arithmetics

        return arithmetics.mul(self, other)

    __rmul__ = __mul__

    def __truediv__(self, other):
        from . import arithmetics

        return arithmetics.div(self, other)

    def __rtruediv__(self, other):
        from . import arithmetics

        return arithmetics.div(other, self)

    def __floordiv__(self, other):
        from . import arithmetics

        return arithmetics.floordiv(self, other)

    def __rfloordiv__(self, other):
        from . import arithmetics

        return arithmetics.floordiv(other, self)

    def __mod__(self, other):
        from . import arithmetics

        return arithmetics.mod(self, other)

    def __rmod__(self, other):
        from . import arithmetics

        return arithmetics.mod(other, self)

    def __pow__(self, other, modulo=None):
        from . import arithmetics

        return arithmetics.pow(self, other)

    def __rpow__(self, other):
        from . import arithmetics

        return arithmetics.pow(other, self)

    def __matmul__(self, other):
        from .linalg import matmul

        return matmul(self, other)

    def __neg__(self):
        from . import arithmetics

        return arithmetics.neg(self)

    def __pos__(self):
        from . import arithmetics

        return arithmetics.pos(self)

    def __abs__(self):
        from . import rounding

        return rounding.abs(self)

    def __invert__(self):
        from . import arithmetics

        return arithmetics.invert(self)

    def __and__(self, other):
        from . import arithmetics

        return arithmetics.bitwise_and(self, other)

    def __or__(self, other):
        from . import arithmetics

        return arithmetics.bitwise_or(self, other)

    def __xor__(self, other):
        from . import arithmetics

        return arithmetics.bitwise_xor(self, other)

    def __lshift__(self, other):
        from . import arithmetics

        return arithmetics.left_shift(self, other)

    def __rshift__(self, other):
        from . import arithmetics

        return arithmetics.right_shift(self, other)

    # in-place variants: replace buffer, keep metadata
    def __iadd__(self, other):
        return self.__set_from(self.__add__(other))

    def __isub__(self, other):
        return self.__set_from(self.__sub__(other))

    def __imul__(self, other):
        return self.__set_from(self.__mul__(other))

    def __itruediv__(self, other):
        return self.__set_from(self.__truediv__(other))

    def __set_from(self, result: "DNDarray") -> "DNDarray":
        self.__array = result.larray
        self.__dtype = result.dtype
        self.__split = result.split
        return self

    # ------------------------------------------------------------ relational
    def __eq__(self, other):
        from . import relational

        return relational.eq(self, other)

    def __ne__(self, other):
        from . import relational

        return relational.ne(self, other)

    def __lt__(self, other):
        from . import relational

        return relational.lt(self, other)

    def __le__(self, other):
        from . import relational

        return relational.le(self, other)

    def __gt__(self, other):
        from . import relational

        return relational.gt(self, other)

    def __ge__(self, other):
        from . import relational

        return relational.ge(self, other)

    __hash__ = None

    # ------------------------------------------------------------ reductions
    def sum(self, axis=None, out=None, keepdims=False):
        from . import arithmetics

        return arithmetics.sum(self, axis=axis, out=out, keepdims=keepdims)

    def prod(self, axis=None, out=None, keepdims=False):
        from . import arithmetics

        return arithmetics.prod(self, axis=axis, out=out, keepdims=keepdims)

    def mean(self, axis=None):
        from . import statistics

        return statistics.mean(self, axis)

    def std(self, axis=None, ddof=0):
        from . import statistics

        return statistics.std(self, axis, ddof=ddof)

    def var(self, axis=None, ddof=0):
        from . import statistics

        return statistics.var(self, axis, ddof=ddof)

    def min(self, axis=None, out=None, keepdims=None):
        from . import statistics

        return statistics.min(self, axis=axis, out=out, keepdims=keepdims)

    def max(self, axis=None, out=None, keepdims=None):
        from . import statistics

        return statistics.max(self, axis=axis, out=out, keepdims=keepdims)

    def argmin(self, axis=None, out=None):
        from . import statistics

        return statistics.argmin(self, axis=axis, out=out)

    def argmax(self, axis=None, out=None):
        from . import statistics

        return statistics.argmax(self, axis=axis, out=out)

    def all(self, axis=None, out=None, keepdims=False):
        from . import logical

        return logical.all(self, axis=axis, out=out, keepdims=keepdims)

    def any(self, axis=None, out=None, keepdims=False):
        from . import logical

        return logical.any(self, axis=axis, out=out, keepdims=keepdims)

    def cumsum(self, axis):
        from . import arithmetics

        return arithmetics.cumsum(self, axis)

    def cumprod(self, axis):
        from . import arithmetics

        return arithmetics.cumprod(self, axis)

    # ---------------------------------------------------------- manipulation
    def reshape(self, *shape, new_split=None):
        from . import manipulations

        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return manipulations.reshape(self, shape, new_split=new_split)

    def flatten(self):
        from . import manipulations

        return manipulations.flatten(self)

    def ravel(self):
        from . import manipulations

        return manipulations.ravel(self)

    def squeeze(self, axis=None):
        from . import manipulations

        return manipulations.squeeze(self, axis)

    def expand_dims(self, axis):
        from . import manipulations

        return manipulations.expand_dims(self, axis)

    def transpose(self, axes=None):
        from .linalg import transpose

        return transpose(self, axes)

    def flip(self, axis=None):
        from . import manipulations

        return manipulations.flip(self, axis)

    def unique(self, sorted=False, return_inverse=False, axis=None):
        from . import manipulations

        return manipulations.unique(self, sorted=sorted, return_inverse=return_inverse, axis=axis)

    def copy(self):
        from . import memory

        return memory.copy(self)

    def abs(self, out=None, dtype=None):
        from . import rounding

        return rounding.abs(self, out=out, dtype=dtype)

    def ceil(self, out=None):
        from . import rounding

        return rounding.ceil(self, out)

    def floor(self, out=None):
        from . import rounding

        return rounding.floor(self, out)

    def round(self, decimals=0, out=None, dtype=None):
        from . import rounding

        return rounding.round(self, decimals, out, dtype)

    def trunc(self, out=None):
        from . import rounding

        return rounding.trunc(self, out)

    def exp(self, out=None):
        from . import exponential

        return exponential.exp(self, out)

    def log(self, out=None):
        from . import exponential

        return exponential.log(self, out)

    def sqrt(self, out=None):
        from . import exponential

        return exponential.sqrt(self, out)

    def sin(self, out=None):
        from . import trigonometrics

        return trigonometrics.sin(self, out)

    def cos(self, out=None):
        from . import trigonometrics

        return trigonometrics.cos(self, out)

    def tan(self, out=None):
        from . import trigonometrics

        return trigonometrics.tan(self, out)

    def tanh(self, out=None):
        from . import trigonometrics

        return trigonometrics.tanh(self, out)

    def isclose(self, other, rtol=1e-05, atol=1e-08, equal_nan=False):
        from . import logical

        return logical.isclose(self, other, rtol=rtol, atol=atol, equal_nan=equal_nan)

    def nonzero(self):
        from . import indexing

        return indexing.nonzero(self)

    def clip(self, a_min, a_max, out=None):
        from . import rounding

        return rounding.clip(self, a_min, a_max, out)

    def tril(self, k=0):
        from .linalg import tril

        return tril(self, k)

    def triu(self, k=0):
        from .linalg import triu

        return triu(self, k)

    # ----------------------------------------------------------------- print
    def __repr__(self) -> str:
        from . import printing

        return printing.__str__(self)

    def __str__(self) -> str:
        from . import printing

        return printing.__str__(self)


def _normalize_slice(s: slice, n: int) -> slice:
    """Rewrite ``s`` with explicit bounds for a logical extent ``n`` so it
    can be applied to a tail-padded buffer without selecting padding."""
    start, stop, step = s.indices(n)
    if step < 0:
        # stop == -1 means "run through index 0"; an explicit -1 would wrap
        return slice(start, None if stop < 0 else stop, step)
    return slice(start, stop, step)


def _place(
    array: jax.Array,
    comm: MeshCommunication,
    split: Optional[int],
    gshape: Optional[Tuple[int, ...]] = None,
    force: bool = False,
) -> jax.Array:
    """Ensure ``array`` is the padded physical buffer for (comm, split,
    gshape), carrying the even NamedSharding over the mesh.

    ``array`` may arrive as the logical array (shape == gshape; it is
    zero-padded along the split dim to a multiple of the mesh size) or as an
    already-padded buffer (shape == padded_shape; taken as-is). Every shape
    is shardable this way — non-divisible logical extents get tail padding
    instead of the replication fallback of round 1.
    """
    gshape = tuple(array.shape) if gshape is None else tuple(int(s) for s in gshape)
    if split is not None:
        target_shape = comm.padded_shape(gshape, split)
        if tuple(array.shape) == gshape and gshape != target_shape:
            pad = [(0, t - s) for t, s in zip(target_shape, array.shape)]
            array = jnp.pad(array, pad)
        elif tuple(array.shape) != target_shape:
            raise ValueError(
                f"buffer shape {tuple(array.shape)} matches neither logical {gshape} "
                f"nor padded {target_shape}"
            )
    if _hooks.in_trace_safe():
        # lazy-fusion replay: tracers cannot be device_put; the fused
        # program's out_shardings pin the final placement instead
        return array
    target = comm.array_sharding(array.shape, split)
    current = getattr(array, "sharding", None)
    if not force and current is not None and current.is_equivalent_to(target, array.ndim):
        return array
    if not target.is_fully_addressable and getattr(array, "is_fully_addressable", True):
        # Multi-controller staging: device_put of a process-local value onto
        # a process-spanning sharding makes jax issue a blocking
        # broadcast_one_to_all (its cross-process equality check), which can
        # deadlock against async collectives already in flight. Assemble the
        # global array from per-device local shards instead — no collective;
        # the value-replicated-across-processes contract is documented at
        # the factories/chunked-reader host boundary.
        if not target.addressable_devices:
            # A mesh this process owns no slice of cannot hold data placed
            # BY this process (jax dies with an opaque IndexError deep in
            # make_array_from_callback — and only on the device-less ranks,
            # so the group crashes divergently). Name the real mistake:
            # sub-meshes must be drawn round-robin across processes, not as
            # a jax.devices()[:k] prefix (tests/_mh_helpers.submesh).
            raise ValueError(
                f"sharding mesh owns no devices addressable by process "
                f"{jax.process_index()}; every participating process must "
                f"hold at least one mesh device — build sub-meshes spanning "
                f"all processes (e.g. an equal share of each process's "
                f"local devices), not as a global device-list prefix"
            )
        host = np.asarray(array)
        return jax.make_array_from_callback(
            # np.array: own the shard memory (callback results may be aliased
            # zero-copy) without promoting 0-d shards the way
            # ascontiguousarray would
            host.shape, target, lambda idx: np.array(host[idx], copy=True)
        )
    return jax.device_put(array, target)
