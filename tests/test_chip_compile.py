"""The chip's compiler, asked without the chip.

Interpret mode discharges a pallas kernel to plain jax on the CPU, so it
passes what Mosaic refuses: a scalar store to VMEM, a 64-bit index-map
literal, a slice off the (8, 128) tiling, a copy the layout forces. The
TPU compiler is installed here and compiles for a chip that is described
and not attached, so these tests lower the four registered kernels — at
``bench.py``'s shapes and at ``chip_smoke.py``'s — for device 0 of a
``v5e:2x2`` topology, and the two ``shard_map`` wrappers over a mesh of
its four devices. Nothing runs: a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture of this file,
which skips if it cannot be; nothing here touches ``topologies`` while a
module is imported (xdist workers all import every test file, and only
one process may load the TPU's library). Compiles run in the test's own
process with the persistent compilation cache off around them: an entry
written for a described device cannot be read back without the chip.
"""
from __future__ import annotations

import numpy as np
import pytest

import bench
import chip_smoke


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the library from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    from jax.sharding import Mesh

    from heat_tpu.core.communication import SPLIT_AXIS

    return Mesh(np.array(topo.devices), (SPLIT_AXIS,))


def _spec(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_kernel(lowered, max_temp_bytes: int):
    """Compile; the Mosaic kernel must be in the program, and XLA must not
    have had to copy the operand into another layout around it."""
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= max_temp_bytes, f"{temp} bytes of temporaries around the kernel"
    return compiled


# Each kernel is lowered through its own entry point, so the tiles under
# test are the ones dispatch gets. Beside the bench and smoke shapes: the
# corners of what dispatch admits (``kernel_fits``, the classifier's
# n_neighbors <= 64), where a tile sized by the feature count alone ran
# out of VMEM.
KMEANS_SHAPES = {
    "bench": (bench.N, bench.F, bench.K),
    "smoke": chip_smoke.SIZES["kmeans"][:3],
    "two_features": (1 << 20, 2, 3),  # the tile pads to 8 sublanes
    "many_centers": (1 << 20, 32, 256),
    "widest_most_centers": (1 << 20, 64, 1024),
    "narrow_most_centers": (1 << 20, 8, 1024),
}
MOMENTS_SHAPES = {
    "bench": (bench.MOM_N, bench.MOM_F),
    "smoke": chip_smoke.SIZES["moments"],
    "one_column": (1 << 22, 1),  # what a 1-D array is given as
    "widest": (1 << 22, 64),
}
_NQ, _NT, _F, _K = chip_smoke.SIZES["knn"]
KNN_SHAPES = {
    "smoke": (_NQ, _NT, _F, _K),  # bench-protocol sizes too: SUSY's 18 features, k = 5
    "most_neighbors": (_NQ, _NT, _F, 64),
    "wide": (_NQ, _NT, 128, _K),
    "wide_most_neighbors": (_NQ, _NT, 128, 64),
}


def test_shapes_are_what_dispatch_admits():
    from heat_tpu.core.kernels import lloyd, moments

    assert _F == bench.CDIST_F
    assert all(lloyd.kernel_fits(f, k) for _, f, k in KMEANS_SHAPES.values())
    assert all(moments.kernel_fits(f) for _, f in MOMENTS_SHAPES.values())
    # one past each bound is declined
    assert not lloyd.kernel_fits(lloyd.MAX_FEATURES + 1, 8)
    assert not lloyd.kernel_fits(32, lloyd.MAX_CLUSTERS + 1)
    assert not moments.kernel_fits(moments.MAX_FEATURES + 1)


@pytest.mark.parametrize("which", sorted(KMEANS_SHAPES))
def test_lloyd_fused_compiles(one_chip, which):
    import jax
    import jax.numpy as jnp

    from heat_tpu.core.kernels import lloyd_local

    n, f, k = KMEANS_SHAPES[which]
    lowered = jax.jit(lloyd_local).lower(
        _spec((n, f), jnp.float32, one_chip), _spec((k, f), jnp.float32, one_chip),
        _spec((), jnp.int32, one_chip),
    )
    # the (n, f) operand is consumed in place: no padded or transposed copy
    _compiled_kernel(lowered, max_temp_bytes=1 << 20)


@pytest.mark.parametrize("which", sorted(MOMENTS_SHAPES))
def test_moments_onepass_compiles(one_chip, which):
    import jax
    import jax.numpy as jnp

    from heat_tpu.core.kernels import moments_local

    n, f = MOMENTS_SHAPES[which]
    lowered = jax.jit(moments_local).lower(
        _spec((n, f), jnp.float32, one_chip), _spec((), jnp.int32, one_chip)
    )
    _compiled_kernel(lowered, max_temp_bytes=1 << 20)


@pytest.mark.parametrize("n", [chip_smoke.SIZES["chol"], 1000, 100])
def test_chol_panel_fused_compiles(one_chip, n):
    """The public path's shapes: 128-wide panels once the matrix has more
    than one, a single whole-matrix panel below that; n = 1000 pads."""
    import jax
    import jax.numpy as jnp

    from heat_tpu.core.kernels import panel_update

    assert chip_smoke.SIZES["chol"] == panel_update.MAX_FUSED_N
    lowered = jax.jit(panel_update.cholesky_blocked).lower(_spec((n, n), jnp.float32, one_chip))
    _compiled_kernel(lowered, max_temp_bytes=16 << 20)


@pytest.mark.parametrize("which", sorted(KNN_SHAPES))
def test_topk_distance_compiles(one_chip, which):
    import jax
    import jax.numpy as jnp

    from heat_tpu.core.kernels import nearest_neighbors

    nq, nt, f, k = KNN_SHAPES[which]
    lowered = jax.jit(lambda x, y: nearest_neighbors(x, y, k)).lower(
        _spec((nq, f), jnp.float32, one_chip), _spec((nt, f), jnp.float32, one_chip)
    )
    # row-tiled still: XLA may re-lay both (n, f) operands out to 128 lanes
    _compiled_kernel(lowered, max_temp_bytes=(nq + nt) * 128 * 4 * 2)


def test_lloyd_sharded_compiles_over_four_chips(four_chips):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from heat_tpu.core.communication import SPLIT_AXIS
    from heat_tpu.core.kernels import lloyd_sharded

    n, f, k = KMEANS_SHAPES["smoke"]
    rows, rep = NamedSharding(four_chips, P(SPLIT_AXIS, None)), NamedSharding(four_chips, P())
    lowered = jax.jit(lambda x, c, nv: lloyd_sharded(x, c, nv, four_chips)).lower(
        _spec((n, f), jnp.float32, rows), _spec((k, f), jnp.float32, rep), _spec((), jnp.int32, rep)
    )
    compiled = _compiled_kernel(lowered, max_temp_bytes=1 << 20)
    assert "all-reduce" in compiled.as_text()  # the psum of sums, counts, inertia
    # each chip holds its quarter of the rows, not a replica
    assert compiled.memory_analysis().argument_size_in_bytes < n * f * 4 // 4 + (1 << 20)


def test_moments_sharded_compiles_over_four_chips(four_chips):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from heat_tpu.core.communication import SPLIT_AXIS
    from heat_tpu.core.kernels import moments_sharded

    n, f = MOMENTS_SHAPES["smoke"]
    rows, rep = NamedSharding(four_chips, P(SPLIT_AXIS, None)), NamedSharding(four_chips, P())
    lowered = jax.jit(lambda x, nv: moments_sharded(x, nv, four_chips)).lower(
        _spec((n, f), jnp.float32, rows), _spec((), jnp.int32, rep)
    )
    compiled = _compiled_kernel(lowered, max_temp_bytes=1 << 20)
    assert "all-reduce" in compiled.as_text()  # the Chan combine's psums
    assert compiled.memory_analysis().argument_size_in_bytes < n * f * 4 // 4 + (1 << 20)


# The groupby's two programs at h2o question 5's widths: int32 key, two
# int32 sums and one f32 sum. An indexed read or write of a block-long
# column ran at 0.21 GB/s on the chip (PERF.md §6, PR 25), so none may come
# back: every column moves as an operand of a sort that the program runs
# anyway, and the rewrite may not hold more of them alive than the
# gathering program did (2.34 columns of temporaries at this size).
_Q5_ROWS = 1 << 20
_Q5_STATS = (("sum", 0, "int32"), ("sum", 1, "int32"), ("sum", 2, "float32"))


def _frame_mesh(mesh):
    """(comm, sharding of a column, sharding of a replicated vector) over the described ``mesh``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from heat_tpu.core.communication import SPLIT_AXIS, MeshCommunication

    comm = MeshCommunication(devices=list(mesh.devices.flat))
    return comm, NamedSharding(comm.mesh, P(SPLIT_AXIS)), NamedSharding(comm.mesh, P())


def _lower_frame_program(which: str, mesh, p: int):
    import jax.numpy as jnp

    from heat_tpu.frame import _shuffle

    comm, rows, rep = _frame_mesh(mesh)
    shape = (_Q5_ROWS * p,)
    if which == "plan":
        fn = _shuffle._plan_executable(
            shape, jnp.dtype("int32"), ("int32", "int32", "float32"), _Q5_STATS, p, "range", comm
        )
    else:
        fn = _shuffle._merge_executable(
            shape, jnp.dtype("int32"), tuple((kind, odt) for kind, _, odt in _Q5_STATS), p, comm
        )
    return fn.lower(
        _spec(shape, jnp.int32, rows), _spec((p,), jnp.int32, rep),
        *[_spec(shape, jnp.dtype(odt), rows) for _, _, odt in _Q5_STATS],
    )


def _indexed_ops(text: str, b: int):
    """The gather and scatter instructions of a compiled program whose result
    has ``b`` elements: a gather through a block-long index vector, a scatter
    into a block-long column."""
    import re

    found = []
    for line in text.splitlines():
        m = re.search(r"= (\S+) (gather|scatter)\((.*)", line)
        if m and re.search(rf"\[(\d+,)*{b}(,\d+)*\]", m.group(1) + m.group(3)):
            found.append(line.strip()[:160])
    return found


def _one_chip(topo):
    from jax.sharding import Mesh

    from heat_tpu.core.communication import SPLIT_AXIS

    return Mesh(np.array(topo.devices[:1]), (SPLIT_AXIS,))


_ONE_CHIP_GROUPBY = {}  # which -> the compiled program: two tests read each, one compile


def _one_chip_groupby(topo, which: str):
    if which not in _ONE_CHIP_GROUPBY:
        _ONE_CHIP_GROUPBY[which] = _lower_frame_program(which, _one_chip(topo), 1).compile()
    return _ONE_CHIP_GROUPBY[which]


@pytest.mark.parametrize("which", ["plan", "merge"])
def test_groupby_program_moves_no_column_through_an_index(topo, which):
    compiled = _one_chip_groupby(topo, which)
    text, column = compiled.as_text(), 4 * _Q5_ROWS
    assert _indexed_ops(text, _Q5_ROWS) == []
    assert " sort(" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= 2 * column, mem.temp_size_in_bytes / column
    assert mem.output_size_in_bytes <= 4.1 * column, mem.output_size_in_bytes / column


@pytest.mark.parametrize("which", ["plan", "merge"])
def test_groupby_program_keeps_its_instruction_mix(topo, which):
    """The join carries a right row along its run with the groupby's own scan (``_scan_runs``) and a
    combiner of its own, "first", beside sum, min and max. That is an entry more, not another loop:
    the groupby's programs hold what they held before the join used the scan (PR 25), the sort by key
    and the compaction's sort, and the scan's one loop over one switch."""
    text = _one_chip_groupby(topo, which).as_text()
    assert text.count(" sort(") == 2
    assert text.count(" while(") == 1
    assert text.count(" conditional(") == 1


def test_groupby_plan_compiles_over_four_chips(four_chips):
    compiled = _lower_frame_program("plan", four_chips, 4).compile()
    text = compiled.as_text()
    # the election's samples, the bucket matrix, the group counts: all_gathers of a
    # few words, which this compiler turns into all-reduces
    assert "all-gather" in text or "all-reduce" in text
    # the election reads 32 samples through an index and nothing longer
    assert _indexed_ops(text, _Q5_ROWS) == []
    # each chip sorts its quarter of the rows, not a replica
    assert compiled.memory_analysis().argument_size_in_bytes < 4 * 4 * _Q5_ROWS + (1 << 20)


# --- the join's match program at the widths of h2o.ai db-benchmark's join question 2 (PERF.md §4,
# `join-q2-medium-inner`): int32 key, five int32 and one f32 payload on the left, three int32 and
# one f32 on the right, a thousandth of the rows. It sorts the right block with the left block
# behind it (13 operands: key, side, 6 + 4 payloads, the index stability costs), carries each
# run's first row forward and compacts with a second sort of 13 operands: no search, no lookup,
# so no gather and no scatter over any of the three block lengths involved. A sort's compile time
# follows its operand count, not its rows: these two compiles are the slowest of the file, one over
# the four described chips and one over one chip, the cell's layout. The result's block is as long
# as both sides' blocks together: the concatenation is written into the result's buffers, and half
# of the first sort's columns leave them for temporaries until the second sort brings them back
# (sandbox compile, PR 28: temporaries 3.85 columns over the four chips and 3.84 over one at this
# size, where the compiler keeps some columns in another memory space; 7.27 at 1e8 rows on one
# chip, PERF.md §5).
_Q2_ROWS = 1 << 20
_Q2_RIGHT_ROWS = _Q2_ROWS // 1024
_Q2_LEFT = ("int32",) * 5 + ("float32",)
_Q2_RIGHT = ("int32",) * 3 + ("float32",)


def _compiled_join(mesh, p: int):
    import jax.numpy as jnp

    from heat_tpu.frame import _shuffle

    comm, rows, rep = _frame_mesh(mesh)
    left, right = (p * _Q2_ROWS,), (p * _Q2_RIGHT_ROWS,)
    fn = _shuffle._join_executable(left, right, jnp.dtype("int32"), _Q2_LEFT, _Q2_RIGHT, "inner", p, comm)
    return fn.lower(
        _spec(left, jnp.int32, rows), _spec((p,), jnp.int32, rep), *[_spec(left, jnp.dtype(d), rows) for d in _Q2_LEFT],
        _spec(right, jnp.int32, rows), _spec((p,), jnp.int32, rep), *[_spec(right, jnp.dtype(d), rows) for d in _Q2_RIGHT],
    ).compile()


def _join_matches_without_an_index(text: str):
    for block in (_Q2_ROWS + _Q2_RIGHT_ROWS, _Q2_ROWS, _Q2_RIGHT_ROWS):
        assert _indexed_ops(text, block) == [], block
    assert text.count(" sort(") == 2  # both sides together by key; the compaction


def test_join_program_compiles_over_four_chips_at_question_2s_widths(four_chips):
    compiled = _compiled_join(four_chips, 4)
    text, column = compiled.as_text(), 4 * _Q2_ROWS
    _join_matches_without_an_index(text)
    assert "all-gather" in text or "all-reduce" in text  # the row counts and the duplicate flag, a few words
    mem = compiled.memory_analysis()
    # a chip's arguments are its quarter: 7 left columns, 5 right ones a thousandth as long
    assert mem.argument_size_in_bytes < 7.01 * column + (1 << 20), mem.argument_size_in_bytes / column
    # eleven columns of both blocks' rows
    assert mem.output_size_in_bytes < 11 * (1 + 1 / 1024) * column + (1 << 20), mem.output_size_in_bytes / column
    assert mem.temp_size_in_bytes < 4.5 * column, mem.temp_size_in_bytes / column


def test_join_program_fits_one_chip_at_question_2s_widths(topo):
    """The cell's layout. Everything the program holds at once, in columns of the left table: 7 + 11
    + the temporaries (21.86 at this size: sandbox compile, PR 28); at 1e8 rows a column is 0.4 GB and
    the two tables stand beside the program."""
    compiled = _compiled_join(_one_chip(topo), 1)
    _join_matches_without_an_index(compiled.as_text())
    mem, column = compiled.memory_analysis(), 4 * _Q2_ROWS
    held = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    assert held < 22.5 * column, held / column
