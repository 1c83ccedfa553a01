"""The chip's compiler, asked without the chip: the join's match program.

``frame_join`` compiled for one described v5e chip, the cell's layout, and
over the four of a ``v5e:2x2``. ``tests/_chip_helpers.py`` says what a
compile here shows and what it does not.
"""
from __future__ import annotations

import re

import pytest

from ._chip_helpers import _frame_mesh, _indexed_ops, _one_chip, _spec, four_chips, topo  # noqa: F401 - fixtures

# --- the join's match program (``_shuffle._join_executable``) on an int32 key, the right table a
# thousandth of the left one's rows as in h2o.ai db-benchmark's join question 2 (PERF.md §4,
# `join-q2-medium-inner`). It sorts the right block with the left block behind it by (key, place):
# the order a stable sort by the key gives, with no operand added for it and no side tag; both sides'
# payloads share operands width by width (PR 36: the key, the place and ``max(left, right)`` payload
# operands of each width; before, the key, a side tag, every payload of both sides and the index
# stability costs). It carries each run's first row forward and shifts the left rows to keep to the
# front in elementwise passes (``_compact_front``, PR 32; a second sort of as many operands before):
# ONE sort, no search, no lookup, so no gather and no scatter over any of the three block lengths
# involved. The result's block is as long as both sides' blocks together. The compaction holds,
# beside the columns it moves, the word that steers it and two columns more at a time (a loop's
# columns stand twice), whatever the table's width.
#
# A sort's compile time follows its operand count, not its rows, and what these tests ask does not
# follow it: the structure (one sort, nothing indexed, the collectives, a chip's arguments being
# its share) is the same with one payload a side as with six and four, and the memory bounds are
# stated in columns of the left table. So tier-1 compiles
# ``one_payload`` (one int32 payload left, one f32 right, in one operand as uint32: a sort of 3
# operands, 5 before PR 36) for both layouts, and a program that began to hold a second copy of its
# columns would show there as it would at question 2's widths. ``question_2`` (five int32 and one f32
# payload left, three int32 and one f32 right: a sort of 8 operands, 13 before PR 36) is the cell's own
# program, on one chip only and marked ``slow``: 229-230 s on the sandbox, 23.56 columns held
# (PR 36; 352-373 s alone with 13 operands, 463 s beside a full run with the two sorts it had, PR 29).
# Whoever changes ``_join_executable``, ``_carry_sort``, ``_scan_runs`` or ``_compact_front`` runs it:
#     pytest -m slow tests/test_chip_compile_join.py
#
# ``question_5`` (PR 31) is the four-chip cell's own program (PERF.md §4, `join-q5-big-inner-4chip`:
# h2o.ai's ``big inner on int``), over four chips only, where the cell runs: the right table as
# long as the left, five int32 and one f32 payload a side, a sort of 8 operands (15 before PR 36:
# every payload operand was half empty), the result's block twice the left one's: 195-204 s, 14.61
# columns of temporaries (PR 36; 602 s with 15 operands). Marked ``slow`` as question 2's is, and so is
# the partition program that feeds it (one stable sort of 9 operands by destination: 240-249 s, 2.00
# columns), a test of its own so that neither compile runs into the bound conftest gives a test. The
# three ``slow`` cases ran in 670 and 691 s together (PR 36; 1 393 s before).
#
# The rows are the cells' own, nearly (PR 32; 2^20 a chip before): 2^26 on one chip, the power of
# two under question 2's 1e8 (the plan of the groupby compiles in 110 s at 2^26 and 147 s at 1e8,
# alone on the sandbox, and reads the same 3.52 columns), and the 25 165 824 of question 5's
# receive block on each of four. At 2^20 rows a column is 4 MB, short enough for the compiler to
# stage whole columns in its fast memory: ``temp_size_in_bytes`` then follows that staging and not
# what the program holds in HBM (question 2's program read 12.04 columns of temporaries at 2^20
# rows and 5.54 at 1e8; with the two sorts it had before, 3.84 and 7.27).
_ROWS = {1: 1 << 26, 4: 25_165_824}  # chips -> rows a chip; every column here is 32 bits wide

# widths -> (left payloads, right payloads, left rows to a right row, the sort's operands, most
# temporaries over four chips, most held on one chip), the last two in columns of the left table, each
# pinned over the sandbox's compile (PR 36; the same shapes with every payload in an operand of its
# own, PR 32's tree, in brackets): one payload 4.04 [4.05] over four chips and 8.77 [8.52] held on one
# (2 + 3 + 3.77 of temporaries: the compaction's word, its two columns twice); question 2 23.56 [23.56]
# held (7 + 11.01 + 5.54: the compaction sets the high-water mark, not the sort); question 5 14.61
# [14.67] over four chips, of blocks of 0.1 GB, beside 14 of arguments and 26 of outputs (no one chip
# holds it). No bound moved with PR 36. The flags that say which side a sorted row is of stand behind
# a barrier in ``frame_join``: without it the sorted place, a whole column, stayed alive across the scan
# for the compaction's ``keep`` and question 2 held 24.31, question 5 16.11 of temporaries (PR 36)
_Q5_PAYLOADS = ("int32",) * 5 + ("float32",)
_WIDTHS = {
    "one_payload": (("int32",), ("float32",), 1024, 3, 4.5, 9.1),
    "question_2": (("int32",) * 5 + ("float32",), ("int32",) * 3 + ("float32",), 1024, 8, None, 24.2),
    "question_5": (_Q5_PAYLOADS, _Q5_PAYLOADS, 1, 8, 15.5, None),
}


def _compiled_join(mesh, p: int, left, right, ratio: int):
    """(compiled program, the operands ``hash_join`` would count for it)."""
    import jax.numpy as jnp

    from heat_tpu.frame import _shuffle

    comm, rows, rep = _frame_mesh(mesh)
    lshape, rshape = (p * _ROWS[p],), (p * (_ROWS[p] // ratio),)
    fn = _shuffle._join_executable(lshape, rshape, jnp.dtype("int32"), left, right, "inner", p, comm)
    return fn.lower(
        _spec(lshape, jnp.int32, rows), _spec((p,), jnp.int32, rep), *[_spec(lshape, jnp.dtype(d), rows) for d in left],
        _spec(rshape, jnp.int32, rows), _spec((p,), jnp.int32, rep), *[_spec(rshape, jnp.dtype(d), rows) for d in right],
    ).compile(), fn.sort_operands


def _join_matches_without_an_index(text: str, rows: int, ratio: int, operands: int, counted: int):
    for block in (rows + rows // ratio, rows, rows // ratio):
        assert _indexed_ops(text, block) == [], block
    (sort,) = [line for line in text.splitlines() if " sort(" in line]  # both sides together; the compaction is no sort
    # what the sort carries is what it was handed: the compiler added no index (the sort is not
    # stable, (key, place) orders the rows fully), and the gauge counts the same
    assert "is_stable=true" not in sort
    assert len(re.findall(r"%[\w.\-]+", sort.split(" sort(")[1].split("), dimensions=")[0])) == operands == counted, sort[:400]


@pytest.mark.parametrize("widths", [pytest.param("question_5", marks=pytest.mark.slow)])
def test_partition_program_sorts_once_over_four_chips(four_chips, widths):
    """``frame_partition`` compiled over the four chips, range mode: every row's destination from
    the block as it stands, ONE stable sort by it carrying the key and the payloads, the bucket
    matrix gathered; nothing block-long goes through an index, and the program holds its
    arguments, its outputs and the sort's two columns of temporaries."""
    import jax.numpy as jnp

    from heat_tpu.frame import _shuffle

    payloads = _WIDTHS[widths][0]
    comm, rows, rep = _frame_mesh(four_chips)
    shape, column = (4 * _ROWS[4],), 4 * _ROWS[4]
    fn = _shuffle._partition_executable(shape, jnp.dtype("int32"), payloads, 4, "range", comm)
    compiled = fn.lower(
        _spec(shape, jnp.int32, rows), _spec((4,), jnp.int32, rep), _spec((3,), jnp.int32, rep),
        *[_spec(shape, jnp.dtype(d), rows) for d in payloads],
    ).compile()
    text = compiled.as_text()
    assert text.count(" sort(") == 1
    assert _indexed_ops(text, _ROWS[4]) == []
    assert "all-gather" in text or "all-reduce" in text  # the bucket matrix, a few words
    mem = compiled.memory_analysis()
    # 2.00 at the cell's rows (PR 32; 2.22 at 2^20 rows a chip, PR 31: 177-186 s alone on the sandbox)
    assert mem.temp_size_in_bytes < 2.6 * column, mem.temp_size_in_bytes / column


@pytest.mark.parametrize("widths", ["one_payload", pytest.param("question_5", marks=pytest.mark.slow)])
def test_join_program_compiles_over_four_chips(four_chips, widths):
    left, right, ratio, operands, most_temp, _ = _WIDTHS[widths]
    compiled, counted = _compiled_join(four_chips, 4, left, right, ratio)
    text, column = compiled.as_text(), 4 * _ROWS[4]
    _join_matches_without_an_index(text, _ROWS[4], ratio, operands, counted)
    assert "all-gather" in text or "all-reduce" in text  # the row counts and the duplicate flag, a few words
    mem = compiled.memory_analysis()
    # a chip's arguments are its quarter: the left table's columns, the right one's as much shorter as the table
    arguments = (1 + len(left)) + (1 + len(right)) / ratio
    assert mem.argument_size_in_bytes < arguments * column + (1 << 20), mem.argument_size_in_bytes / column
    # the key and every payload, of both blocks' rows
    outputs = (1 + len(left) + len(right)) * (1 + 1 / ratio)
    assert mem.output_size_in_bytes < outputs * column + (1 << 20), mem.output_size_in_bytes / column
    assert mem.temp_size_in_bytes < most_temp * column, mem.temp_size_in_bytes / column


@pytest.mark.parametrize("widths", ["one_payload", pytest.param("question_2", marks=pytest.mark.slow)])
def test_join_program_fits_one_chip(topo, widths):
    """The cell's layout. Everything the program holds at once, in columns of the left table: its
    arguments, its outputs and its temporaries (7 + 11.01 + 5.54 at question 2's widths: a column is
    0.4 GB and the two tables stand beside the program)."""
    left, right, ratio, operands, _, most_held = _WIDTHS[widths]
    compiled, counted = _compiled_join(_one_chip(topo), 1, left, right, ratio)
    _join_matches_without_an_index(compiled.as_text(), _ROWS[1], ratio, operands, counted)
    mem, column = compiled.memory_analysis(), 4 * _ROWS[1]
    held = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    assert held < most_held * column, held / column
