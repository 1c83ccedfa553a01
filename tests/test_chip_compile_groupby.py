"""The chip's compiler, asked without the chip: the groupby's two programs.

``frame_plan`` and ``frame_merge`` compiled for one described v5e chip, the
cell's layout, and the plan over the four of a ``v5e:2x2``.
``tests/_chip_helpers.py`` says what a compile here shows and what it does
not. Three compiles of sorts with five and six operands are all this file
costs: each one-chip program is compiled once for the two tests that read it.
"""
from __future__ import annotations

import pytest

from ._chip_helpers import _frame_mesh, _indexed_ops, _one_chip, _spec, four_chips, topo  # noqa: F401 - fixtures


# The groupby's two programs at h2o question 5's widths: int32 key, two
# int32 sums and one f32 sum. An indexed read or write of a block-long
# column ran at 0.21 GB/s on the chip (PERF.md §6, PR 25), so none may come
# back: every column moves as an operand of a sort that the program runs
# anyway, or follows the compaction's word (``_compact_front``, PR 32),
# and the program may not hold more of them alive than it did with the
# compaction's sort: 3.52 columns of temporaries for the plan (6.01 with
# the sort) and 3.02 for the merge (4.51) at the cell's own 1e8 rows
# (sandbox compiles, PR 32). The rows are nearly the cell's since PR 32
# (2^20 before): 2^26, the power of two under 1e8, where the plan reads
# the same 3.52 and compiles in 110 s, not 147. A 4 MB column is short
# enough for the compiler to stage in its fast memory, where
# ``temp_size_in_bytes`` follows the staging and not what is held in HBM
# (``test_chip_compile_join.py`` has the readings).
_Q5_ROWS = {1: 1 << 26, 4: 25_000_000}  # chips -> rows a chip
_Q5_MOST_TEMPORARIES = {"plan": 4.0, "merge": 3.4}  # columns
_Q5_STATS = (("sum", 0, "int32"), ("sum", 1, "int32"), ("sum", 2, "float32"))


def _lower_frame_program(which: str, mesh, p: int):
    import jax.numpy as jnp

    from heat_tpu.frame import _shuffle

    comm, rows, rep = _frame_mesh(mesh)
    shape = (_Q5_ROWS[p] * p,)
    if which == "plan":
        fn = _shuffle._plan_executable(
            shape, jnp.dtype("int32"), ("int32", "int32", "float32"), _Q5_STATS, p, "range", comm
        )
    else:
        fn = _shuffle._merge_executable(
            shape, jnp.dtype("int32"), tuple((kind, odt) for kind, _, odt in _Q5_STATS), p, comm
        )
    steps_so_far = [] if which == "plan" else [_spec((), jnp.int32, rep)]  # the merge is handed the plan's
    return fn.lower(
        _spec(shape, jnp.int32, rows), _spec((p,), jnp.int32, rep), *steps_so_far,
        *[_spec(shape, jnp.dtype(odt), rows) for _, _, odt in _Q5_STATS],
    )


_ONE_CHIP_GROUPBY = {}  # which -> the compiled program: two tests read each, one compile


def _one_chip_groupby(topo, which: str):
    if which not in _ONE_CHIP_GROUPBY:
        _ONE_CHIP_GROUPBY[which] = _lower_frame_program(which, _one_chip(topo), 1).compile()
    return _ONE_CHIP_GROUPBY[which]


@pytest.mark.parametrize("which", ["plan", "merge"])
def test_groupby_program_moves_no_column_through_an_index(topo, which):
    compiled = _one_chip_groupby(topo, which)
    text, column = compiled.as_text(), 4 * _Q5_ROWS[1]
    assert _indexed_ops(text, _Q5_ROWS[1]) == []
    assert " sort(" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= _Q5_MOST_TEMPORARIES[which] * column, mem.temp_size_in_bytes / column
    assert mem.output_size_in_bytes <= 4.1 * column, mem.output_size_in_bytes / column


@pytest.mark.parametrize("which", ["plan", "merge"])
def test_groupby_program_keeps_its_instruction_mix(topo, which):
    """The join carries a right row along its run with the groupby's own scan (``_scan_runs``) and a
    combiner of its own, "first", beside sum, min and max. That is an entry more, not another loop:
    the scan is one loop over one switch, as before the join used it (PR 25). The compaction is no
    sort any more (PR 32; on one chip and in range mode the run ends stand in the order the exchange
    wants): ``_compact_front``'s loop over the displacements and one loop for every two columns
    (key + three totals: two), each loop two switches, its two steps a round."""
    text = _one_chip_groupby(topo, which).as_text()
    assert text.count(" sort(") == 1  # by key
    assert text.count(" while(") == 1 + 3
    assert text.count(" conditional(") == 1 + 3 * 2


def test_groupby_plan_compiles_over_four_chips(four_chips):
    compiled = _lower_frame_program("plan", four_chips, 4).compile()
    text = compiled.as_text()
    # the election's samples, the bucket matrix, the group counts: all_gathers of a
    # few words, which this compiler turns into all-reduces
    assert "all-gather" in text or "all-reduce" in text
    # the election reads 32 samples through an index and nothing longer
    assert _indexed_ops(text, _Q5_ROWS[4]) == []
    # each chip sorts its quarter of the rows, not a replica
    assert compiled.memory_analysis().argument_size_in_bytes < 4 * 4 * _Q5_ROWS[4] + (1 << 20)
