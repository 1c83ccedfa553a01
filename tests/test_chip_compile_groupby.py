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
# anyway, and the rewrite may not hold more of them alive than the
# gathering program did (2.34 columns of temporaries at this size).
_Q5_ROWS = 1 << 20
_Q5_STATS = (("sum", 0, "int32"), ("sum", 1, "int32"), ("sum", 2, "float32"))


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
