"""The chip's compiler, asked without the chip: the Lasso fit's program.

``_cd_fit`` compiled for one described v5e chip at the benchmark cell's own
shape (``lasso-fit1-eurad-1e7``: x (1e7, 108) float32, PERF.md §4), and over
the four of a ``v5e:2x2`` with x split by rows. ``tests/_chip_helpers.py``
says what a compile here shows and what it does not. About 5 s a compile.

What is pinned (PR 34). The fit runs in the Gram matrix's space: x is read
twice a program, as it stands, and never inside a loop. The trap beside it:
a Gram built from row blocks, ``X.reshape(625, 16000, 108)`` and a batched
product, compiles to a second copy of x in another layout (4 368 674 304 B
of temporaries, where this program has 452 608 B): twice the cell's
``peak_hbm_GiB``. And the residual's sweep, for which ``X[:, j]`` is a
``dynamic-slice`` that reads eight columns of (8, 128) tiles for one, 108
times a sweep, must not come back for a tall table.
"""
from __future__ import annotations

import re

import numpy as np
import pytest

from ._chip_helpers import _collectives, _reached_from_loops, _spec, four_chips, one_chip, topo  # noqa: F401 - fixtures

ROWS, COLUMNS = 10_000_000, 108  # benchmarks/chip/configs/lasso-eurad-1e7.json
MAX_TEMP_BYTES = 64 << 20  # x is 4.5e9 B on the chip; G, q, the norms and theta are 50 KB


def _compiled_fit(rows_sharding, replicated):
    from heat_tpu.regression import lasso

    f32, i32 = np.float32, np.int32
    assert lasso._cd_path(ROWS, COLUMNS) == "gram"
    return lasso._cd_fit.lower(
        _spec((ROWS, COLUMNS), f32, rows_sharding), _spec((ROWS,), f32, rows_sharding),
        _spec((COLUMNS,), f32, replicated), _spec((), f32, replicated), _spec((), f32, replicated),
        _spec((), i32, replicated),
    ).compile()


def _slices_of_x(text: str, rows: int):
    """The ``dynamic-slice`` instructions, those inside fusions included, that cut a value of ``rows`` rows."""
    return [line.strip()[:160] for line in text.splitlines() if " dynamic-slice(" in line and f"[{rows}," in line]


def test_the_fit_holds_one_x_and_slices_no_column_of_it(one_chip):
    compiled = _compiled_fit(one_chip, one_chip)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < MAX_TEMP_BYTES, f"{memory.temp_size_in_bytes} B of temporaries: a second x?"
    text = compiled.as_text()
    assert not _slices_of_x(text, ROWS)
    assert text.count(" convolution(") == 1 and "operand_precision={highest,highest}" in text
    assert len(re.findall(r" while\(", text)) == 2  # the sweeps' and the columns': the readers' loops


def test_over_four_chips_a_chip_holds_its_rows_and_the_loops_no_collective(four_chips):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from heat_tpu.core.communication import SPLIT_AXIS

    compiled = _compiled_fit(NamedSharding(four_chips, P(SPLIT_AXIS)), NamedSharding(four_chips, P()))
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < MAX_TEMP_BYTES
    assert memory.argument_size_in_bytes < 1.2e9  # a quarter of x's 4.52e9 B
    text = compiled.as_text()
    assert not _slices_of_x(text, ROWS // 4)

    assert _collectives(text.splitlines()) and not _collectives(_reached_from_loops(text))
