"""Dedicated depth tests for the L1 communication layer (VERDICT r3 item 6).

`heat_tpu/core/communication.py` is the layer every DNDarray rides on;
round 3 exercised it only indirectly. This file mirrors the reference's
`test_communication.py` (2,482 LoC of chunk/buffer/collective cases) for
the TPU design: partition bookkeeping (chunk/counts/lshape_map) on an
uneven-extent battery, sharding construction, sub-mesh and multi-axis
meshes, the chunked assembly protocol, communicator plumbing
(WORLD/SELF/use_comm/comm_context/sanitize), and the multi-host
init/alignment logic that is testable in one process.

ws-2 clean (PR 17 burn-down): sub-mesh constructions draw
process-spanning device sets from ``tests._mh_helpers.submesh`` instead
of ``jax.devices()[:k]`` prefixes (which land entirely on process 0 and
deadlock the group), host reads of padded global buffers go through a
shard-assembling ``_host_read`` instead of ``np.asarray`` (not fully
addressable at ws>1), and the sharding-partition / is_split assertions
check the union across processes, not just the local shards.
"""
from __future__ import annotations

from unittest import mock

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.core import communication as comm_mod
from heat_tpu.core.communication import (
    SPLIT_AXIS,
    MeshCommunication,
    _assemble_from_chunks,
    _split_ranks,
    assemble_local_shards,
    ragged_process_allgather,
    sanitize_comm,
)
from tests._mh_helpers import submesh
from tests.base import TestCase


def _host_read(buf, split):
    """Read a (possibly multi-process) padded global buffer on every host.

    Single-process: plain ``np.asarray``. Multi-process the buffer is not
    fully addressable, so the process-local shards concatenate in split
    order and one ragged allgather stitches the per-process blocks in pid
    order (the mesh is process-major, so that IS the global buffer).
    Collective at ws>1 — every process must call."""
    import jax

    if getattr(buf, "is_fully_addressable", True):
        return np.asarray(jax.device_get(buf))
    shards = sorted(
        buf.addressable_shards, key=lambda s: (s.index[split].start or 0)
    )
    seen = set()
    blocks = []
    for s in shards:
        start = s.index[split].start or 0
        if start in seen:  # replicated coordinate (multi-axis meshes)
            continue
        seen.add(start)
        blocks.append(np.asarray(jax.device_get(s.data)))
    local = np.concatenate(blocks, axis=split)
    return np.concatenate(
        ragged_process_allgather(local, axis=split), axis=split
    )


def _extent_battery(p):
    """Split extents that historically break ceil-div bookkeeping."""
    return sorted({0, 1, p - 1, p, p + 1, 2 * p, 2 * p + 3, 7 * p + 5, 1000})


class TestPartitionBookkeeping(TestCase):
    def test_padded_dim_battery(self):
        p = self.comm.size
        for n in _extent_battery(p):
            padded = self.comm.padded_dim(n)
            if n == 0:
                # degenerate extents still get one addressable row per
                # device (XLA rejects zero-size shards)
                self.assertEqual(padded, p)
            else:
                self.assertEqual(padded, -(-n // p) * p)
                self.assertGreaterEqual(padded, n)
                self.assertLess(padded - n, p)
            self.assertEqual(padded % p, 0)

    def test_padded_shape_per_axis(self):
        p = self.comm.size
        shape = (2 * p + 3, 5, p - 1 if p > 1 else 1)
        for split in range(3):
            ps = self.comm.padded_shape(shape, split)
            for d in range(3):
                if d == split:
                    self.assertEqual(ps[d], self.comm.padded_dim(shape[d]))
                else:
                    self.assertEqual(ps[d], shape[d])
        self.assertEqual(self.comm.padded_shape(shape, None), shape)

    def test_chunk_covers_extent_exactly(self):
        p = self.comm.size
        for n in _extent_battery(p):
            shape = (n, 4)
            covered = 0
            prev_end = 0
            for r in range(p):
                off, lshape, slices = self.comm.chunk(shape, 0, rank=r)
                self.assertEqual(off, slices[0].start)
                self.assertEqual(lshape[0], slices[0].stop - slices[0].start)
                self.assertEqual(lshape[1], 4)
                self.assertEqual(slices[1], slice(0, 4))
                # chunks are ordered, disjoint, contiguous
                self.assertEqual(slices[0].start, prev_end if covered else slices[0].start)
                if lshape[0]:
                    self.assertGreaterEqual(slices[0].start, prev_end)
                prev_end = slices[0].stop
                covered += lshape[0]
            self.assertEqual(covered, n, f"extent {n} not exactly covered")

    def test_chunk_matches_counts_displs_shape(self):
        p = self.comm.size
        for n in _extent_battery(p):
            shape = (3, n)
            counts, displs, out_shape = self.comm.counts_displs_shape(shape, 1)
            self.assertEqual(len(counts), p)
            self.assertEqual(sum(counts), n)
            self.assertEqual(out_shape[0], 3)
            for r in range(p):
                off, lshape, _ = self.comm.chunk(shape, 1, rank=r)
                self.assertEqual(off, displs[r], f"rank {r} extent {n}")
                self.assertEqual(lshape[1], counts[r], f"rank {r} extent {n}")

    def test_chunk_rank_defaults_to_self(self):
        off, lshape, slices = self.comm.chunk((10, 2), 0)
        off_r, lshape_r, slices_r = self.comm.chunk((10, 2), 0, rank=self.comm.rank)
        self.assertEqual((off, lshape, slices), (off_r, lshape_r, slices_r))

    def test_chunk_split_none_is_everything(self):
        off, lshape, slices = self.comm.chunk((5, 6), None)
        self.assertEqual(off, 0)
        self.assertEqual(lshape, (5, 6))
        self.assertEqual(slices, (slice(0, 5), slice(0, 6)))

    def test_lshape_map_consistent_with_chunk(self):
        p = self.comm.size
        for n in _extent_battery(p):
            m = self.comm.lshape_map((n, 3), 0)
            self.assertEqual(m.shape, (p, 2))
            self.assertEqual(int(m[:, 0].sum()), n)
            for r in range(p):
                _, lshape, _ = self.comm.chunk((n, 3), 0, rank=r)
                np.testing.assert_array_equal(m[r], lshape)

    def test_lshape_map_replicated(self):
        m = self.comm.lshape_map((4, 5), None)
        self.assertEqual(m.shape, (self.comm.size, 2))
        assert (m == [4, 5]).all()

    def test_ceil_div_front_loading(self):
        """Blocks are ceil-div: every shard except possibly a tail run has
        the full block, and empty shards only appear at the end."""
        p = self.comm.size
        for n in _extent_battery(p):
            counts = self.comm.lshape_map((n,), 0)[:, 0]
            block = -(-n // p) if n else 0
            nonempty = [c for c in counts if c > 0]
            self.assertTrue(all(c == block for c in nonempty[:-1]))
            tail = counts.tolist()
            self.assertEqual(tail, sorted(tail, reverse=True), f"extent {n}")


class TestShardingConstruction(TestCase):
    def test_spec_places_split_axis(self):
        for ndim in (1, 2, 4):
            for split in range(ndim):
                spec = self.comm.spec(ndim, split)
                self.assertEqual(len(spec), ndim)
                self.assertEqual(spec[split], SPLIT_AXIS)
                for d in range(ndim):
                    if d != split:
                        self.assertIsNone(spec[d])
        self.assertEqual(tuple(self.comm.spec(3, None)), ())

    def test_spec_out_of_range(self):
        with pytest.raises(ValueError):
            self.comm.spec(2, 2)
        with pytest.raises(ValueError):
            self.comm.spec(2, -1)

    def test_array_sharding_requires_divisible(self):
        p = self.comm.size
        self.comm.array_sharding((2 * p, 3), 0)  # fine
        if p > 1:
            with pytest.raises(ValueError):
                self.comm.array_sharding((2 * p + 1, 3), 0)
        sh = self.comm.array_sharding((5, 4), None)
        self.assertTrue(sh.is_fully_replicated)

    def test_sharding_shards_actually_partition(self):
        import jax
        import jax.numpy as jnp

        p = self.comm.size
        nproc = jax.process_count()
        data = np.arange(4 * p * 3, dtype=np.float32).reshape(4 * p, 3)
        # make_array_from_callback builds the same global array at any
        # world size (device_put of the full value cannot: the buffer is
        # not fully addressable at ws>1)
        buf = jax.make_array_from_callback(
            (4 * p, 3),
            self.comm.array_sharding((4 * p, 3), 0),
            lambda idx: jnp.asarray(data[idx]),
        )
        # each process addresses exactly its share of the split shards...
        starts = sorted((s.index[0].start or 0) for s in buf.addressable_shards)
        self.assertEqual(len(starts), p // nproc)
        for s in buf.addressable_shards:
            self.assertEqual(s.data.shape, (4, 3))
        # ...and the union across processes partitions the global extent:
        # the process-spanning assertion (ws-2 burn-down), a plain
        # ragged allgather of the local start offsets
        all_starts = sorted(
            int(v)
            for block in ragged_process_allgather(
                np.asarray(starts, dtype=np.int64), axis=0
            )
            for v in block
        )
        self.assertEqual(all_starts, [4 * r for r in range(p)])


class TestSplitRanks(TestCase):
    def test_default_mesh_each_rank_once(self):
        seen = [r for r, _ in _split_ranks(self.comm)]
        self.assertEqual(sorted(seen), list(range(self.comm.size)))

    def test_multi_axis_mesh_replicates_ranks(self):
        import jax
        from jax.sharding import Mesh

        devs = jax.devices()
        if len(devs) < 4 or len(devs) % 2:
            pytest.skip("needs an even multi-device mesh")
        mesh = Mesh(
            np.asarray(devs).reshape(2, len(devs) // 2), axis_names=("nodes", SPLIT_AXIS)
        )
        comm = MeshCommunication(mesh=mesh)
        self.assertEqual(comm.size, len(devs) // 2)
        pairs = list(_split_ranks(comm))
        self.assertEqual(len(pairs), len(devs))  # every device enumerated
        from collections import Counter

        counts = Counter(r for r, _ in pairs)
        self.assertEqual(set(counts), set(range(comm.size)))
        self.assertTrue(all(c == 2 for c in counts.values()))  # one per node row

    def test_multi_axis_mesh_dndarray_layout(self):
        import jax
        from jax.sharding import Mesh

        devs = jax.devices()
        if len(devs) < 4 or len(devs) % 2:
            pytest.skip("needs an even multi-device mesh")
        mesh = Mesh(
            np.asarray(devs).reshape(2, len(devs) // 2), axis_names=("nodes", SPLIT_AXIS)
        )
        comm = MeshCommunication(mesh=mesh)
        n = 2 * comm.size + 1
        x = np.arange(n * 2, dtype=np.float32).reshape(n, 2)
        a = ht.array(x, split=0, comm=comm)
        np.testing.assert_array_equal(a.numpy(), x)
        self.assertEqual(int(a.lshape_map[:, 0].sum()), n)
        # dedup'd shard iteration yields each split rank once despite the
        # nodes-axis replication
        starts = [s for s, _ in a._iter_local_shards(dedup=True)]
        self.assertEqual(len(starts), len(set(starts)))
        total = sum(d.shape[0] for _, d in a._iter_local_shards(dedup=True))
        self.assertEqual(total, n)
        # and a reduction over the replicated layout is still exact
        self.assertAlmostEqual(float(a.sum()), float(x.sum()), places=3)


class TestSubMeshComms(TestCase):
    def test_sub_mesh_sizes_and_values(self):
        import jax

        # sub-mesh sizes that span every process: a prefix of
        # jax.devices() would land entirely on process 0 at ws>1 and
        # deadlock the group, so sizes are multiples of the process
        # count drawn through the process-spanning submesh() helper
        devs = jax.devices()
        nproc = jax.process_count()
        ks = sorted(
            k for k in {nproc, 2 * nproc, 3 * nproc, len(devs)}
            if k <= len(devs) and k // nproc <= jax.local_device_count()
        )
        for k in ks:
            comm = MeshCommunication(devices=submesh(k))
            self.assertEqual(comm.size, k)
            n = 2 * k + 1
            x = np.arange(n, dtype=np.float32)
            a = ht.array(x, split=0, comm=comm)
            self.assertEqual(a.comm.size, k)
            np.testing.assert_array_equal(a.numpy(), x)
            self.assertAlmostEqual(float(a.sum()), float(x.sum()), places=4)

    def test_binary_op_across_different_comms_raises(self):
        import jax

        devs = jax.devices()
        nproc = jax.process_count()
        if len(devs) < nproc + 1:
            pytest.skip("needs a sub-mesh smaller than the world")
        c1 = MeshCommunication(devices=submesh(nproc))
        a = ht.array(np.zeros(4, np.float32), split=0)
        b = ht.array(np.zeros(4, np.float32), split=0, comm=c1)
        with pytest.raises((ValueError, TypeError)):
            a + b

    def test_comm_context_scopes_factories(self):
        import jax

        nproc = jax.process_count()
        sub = MeshCommunication(devices=submesh(nproc))
        before = ht.get_comm()
        with comm_mod.comm_context(sub):
            x = ht.zeros((6,), split=0)
            self.assertEqual(x.comm.size, nproc)
            self.assertIs(ht.get_comm(), sub)
        self.assertIs(ht.get_comm(), before)

    def test_comm_context_restores_on_error(self):
        import jax

        sub = MeshCommunication(devices=submesh(jax.process_count()))
        before = ht.get_comm()
        with pytest.raises(RuntimeError):
            with comm_mod.comm_context(sub):
                raise RuntimeError("boom")
        self.assertIs(ht.get_comm(), before)


class TestLockstepFence(TestCase):
    def test_cpu_mesh_dispatch_is_pinned_to_completion(self):
        """On the in-process multi-device CPU mesh a loop of collective
        programs must not run the host ahead of the devices: past 32
        programs in flight the CPU client wedges (tests/test_sketch.py's
        warm streaming loop aborted its process that way). So
        ``collective_lockstep`` returns only what has finished there."""
        import jax

        from heat_tpu.core.communication import collective_lockstep

        if self.comm.size < 2:
            pytest.skip("needs a multi-device mesh")
        x = ht.random.randn(1024, 1024, split=0).larray
        prog = jax.jit(lambda a: ((a @ a.T) @ (a @ a.T)).sum(axis=0))
        jax.block_until_ready(prog(x))  # compiled: the next call only dispatches
        out = collective_lockstep((prog(x), prog(x + 1.0)))
        self.assertTrue(all(o.is_ready() for o in out))

    def test_single_device_results_pass_through(self):
        """One device has no rendezvous to protect: nothing to wait for,
        and whatever is not an array passes through untouched."""
        import jax.numpy as jnp

        from heat_tpu.core._dispatch import fence_cpu_collectives
        from heat_tpu.core.communication import collective_lockstep

        tree = {"a": jnp.arange(4.0), "n": 3, "none": None}
        self.assertIs(collective_lockstep(tree), tree)
        fence_cpu_collectives(None)
        fence_cpu_collectives([])


class TestCommunicatorPlumbing(TestCase):
    def test_sanitize_defaults_and_rejects(self):
        self.assertIs(sanitize_comm(None), ht.get_comm())
        self.assertIs(sanitize_comm(self.comm), self.comm)
        with pytest.raises(TypeError):
            sanitize_comm("not a comm")
        with pytest.raises(TypeError):
            sanitize_comm(42)

    def test_use_comm_roundtrip(self):
        import jax

        sub = MeshCommunication(devices=submesh(jax.process_count()))
        try:
            comm_mod.use_comm(sub)
            self.assertIs(ht.get_comm(), sub)
            with pytest.raises(TypeError):
                comm_mod.use_comm("nope")
        finally:
            comm_mod.use_comm(None)  # None restores WORLD
        self.assertIs(ht.get_comm(), comm_mod.WORLD)

    def test_world_self_singletons(self):
        self.assertEqual(comm_mod.SELF.size, 1)
        self.assertIs(comm_mod.MPI_WORLD, comm_mod.WORLD)
        self.assertIs(comm_mod.MPI_SELF, comm_mod.SELF)
        self.assertFalse(comm_mod.SELF.is_distributed())
        # name parity: the reference's class name maps to the mesh backend
        self.assertIs(comm_mod.MPICommunication, MeshCommunication)
        self.assertFalse(comm_mod.CUDA_AWARE_MPI)

    def test_equality_and_hash(self):
        import jax

        devs = list(jax.devices())
        a = MeshCommunication(devices=devs)
        b = MeshCommunication(devices=devs)
        a.mesh, b.mesh  # resolve both
        self.assertEqual(a, b)
        self.assertEqual(hash(a), hash(b))
        nproc = jax.process_count()
        if len(devs) > nproc:
            c = MeshCommunication(devices=submesh(nproc))
            c.mesh
            self.assertNotEqual(a, c)
        self.assertNotEqual(a, "something else")

    def test_repr_does_not_resolve(self):
        fresh = MeshCommunication()
        r = repr(fresh)
        self.assertIn("unresolved", r)
        self.assertIsNone(fresh._mesh)  # repr must not init the backend
        fresh.mesh
        self.assertIn("size=", repr(fresh))

    def test_init_distributed_already_initialized_message(self):
        """The backend-already-up failure must translate to an actionable
        error (the raw jax message names internals)."""
        import jax

        with mock.patch.object(
            jax.distributed,
            "initialize",
            side_effect=RuntimeError("jax.distributed.initialize must be called before any JAX computations"),
        ):
            with pytest.raises(RuntimeError, match="before creating any array"):
                ht.init_distributed(coordinator_address="localhost:1", num_processes=2, process_id=0)

    def test_init_distributed_unrelated_error_passthrough(self):
        import jax

        with mock.patch.object(
            jax.distributed, "initialize", side_effect=RuntimeError("something else")
        ):
            with pytest.raises(RuntimeError, match="something else"):
                ht.init_distributed(coordinator_address="localhost:1", num_processes=2, process_id=0)


class TestChunkedAssembly(TestCase):
    def test_assemble_from_chunks_values(self):
        p = self.comm.size
        for n in (p, 2 * p + 3, max(p - 1, 1), 1):
            full = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
            requested = []

            def read_chunk(slices):
                requested.append(slices)
                return full[slices]

            buf = _assemble_from_chunks(read_chunk, (n, 3), 0, self.comm, np.float32)
            self.assertEqual(tuple(buf.shape), self.comm.padded_shape((n, 3), 0))
            got = _host_read(buf, 0)[:n]
            np.testing.assert_array_equal(got, full)
            # every request was a canonical per-rank chunk with valid
            # rows (each process requests only its addressable ranks'
            # chunks — no host ever reads the full array)
            for sl in requested:
                self.assertGreater(sl[0].stop - sl[0].start, 0)
                self.assertLessEqual(sl[0].stop, n)

    def test_assemble_skips_empty_chunks(self):
        import jax

        p = self.comm.size
        if p < 2:
            pytest.skip("needs empty tail shards")
        n = 1  # only rank 0 has data
        calls = []

        def read_chunk(slices):
            calls.append(slices)
            return np.ones((1, 2), np.float32)

        buf = _assemble_from_chunks(read_chunk, (n, 2), 0, self.comm, np.float32)
        # empty shards never call the reader: only the process that
        # addresses rank 0's device reads anything at all
        pid = jax.process_index()
        local_nonempty = sum(
            1
            for r, d in _split_ranks(self.comm)
            if int(d.process_index) == pid
            and self.comm.chunk((n, 2), 0, rank=r)[1][0] > 0
        )
        self.assertEqual(len(calls), local_nonempty)
        np.testing.assert_array_equal(_host_read(buf, 0)[:1], np.ones((1, 2)))

    def test_assemble_split1(self):
        p = self.comm.size
        n = 3 * p + 1
        full = np.arange(2 * n, dtype=np.float64).reshape(2, n)
        buf = _assemble_from_chunks(
            lambda sl: full[sl], (2, n), 1, self.comm, np.float64
        )
        np.testing.assert_array_equal(_host_read(buf, 1)[:, :n], full)

    def test_ragged_allgather_blocks(self):
        import jax

        nproc = jax.process_count()
        x = np.arange(12, dtype=np.int64).reshape(3, 4)
        blocks = ragged_process_allgather(x, axis=0)
        self.assertEqual(len(blocks), nproc)
        for b in blocks:  # every process contributed the same payload
            np.testing.assert_array_equal(b, x)
        # empty payload round-trips too
        empty = ragged_process_allgather(np.empty((0, 4)), axis=0)
        self.assertEqual(len(empty), nproc)
        for b in empty:
            self.assertEqual(b.shape, (0, 4))

    def test_assemble_local_shards(self):
        import jax

        nproc = jax.process_count()
        local = np.arange(10, dtype=np.float32).reshape(5, 2)
        buf, gshape = assemble_local_shards(local, 0, self.comm)
        # is_split semantics: the global array is the pid-ordered
        # concatenation of the per-process shards
        want = np.concatenate([local] * nproc, axis=0)
        self.assertEqual(gshape, (5 * nproc, 2))
        np.testing.assert_array_equal(_host_read(buf, 0)[: 5 * nproc], want)
        # is_split through the public factory agrees
        a = ht.array(local, is_split=0)
        self.assertEqual(a.shape, (5 * nproc, 2))
        np.testing.assert_array_equal(a.numpy(), want)

    def test_assemble_local_shards_split1(self):
        import jax

        nproc = jax.process_count()
        local = np.arange(12, dtype=np.float32).reshape(3, 4)
        buf, gshape = assemble_local_shards(local, 1, self.comm)
        want = np.concatenate([local] * nproc, axis=1)
        self.assertEqual(gshape, (3, 4 * nproc))
        np.testing.assert_array_equal(_host_read(buf, 1)[:, : 4 * nproc], want)


class TestUnevenExtentEndToEnd(TestCase):
    """The padded-buffer invariant, driven through public ops for every
    pathological extent (the layer this file guards is exactly what makes
    these exact)."""

    def test_reductions_every_extent(self):
        p = self.comm.size
        rng = np.random.default_rng(0)
        for n in _extent_battery(p):
            if n == 0:
                continue
            x = rng.normal(size=(n,)).astype(np.float32)
            a = ht.array(x, split=0)
            np.testing.assert_allclose(float(a.sum()), x.sum(), rtol=2e-4)
            np.testing.assert_allclose(float(a.max()), x.max(), rtol=1e-6)
            np.testing.assert_allclose(float(a.mean()), x.mean(), rtol=2e-4)

    def test_elementwise_preserves_padding_discipline(self):
        p = self.comm.size
        rng = np.random.default_rng(1)
        for n in (p + 1, 2 * p + 3):
            x = rng.normal(size=(n, 3)).astype(np.float32)
            a = ht.array(x, split=0)
            b = (a * 2 + 1).numpy()
            np.testing.assert_allclose(b, x * 2 + 1, rtol=1e-6)
            # the buffer stays padded and sharded after the op
            r = a * 2 + 1
            self.assertEqual(tuple(r.larray.shape), self.comm.padded_shape((n, 3), 0))

    def test_zero_size_axis(self):
        a = ht.zeros((0, 4), split=0)
        self.assertEqual(a.shape, (0, 4))
        self.assertEqual(a.numpy().shape, (0, 4))
        b = ht.ones((3, 0))
        self.assertEqual(float(b.sum()), 0.0)
