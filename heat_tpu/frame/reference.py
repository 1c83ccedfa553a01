"""Plain references of the frame verbs: the same semantics in NumPy, nothing of the engine.

What the tests (and anyone who doubts a result) hold :class:`heat_tpu.frame.Frame` to. No jax,
no shard, no program: whole columns on the host, the straightforward way. Only the key is ever
sorted; every other column is read through the positions that sort gives.
"""
from __future__ import annotations

from typing import Dict, Mapping

import numpy as np

__all__ = ["join_m1"]


def _null_filled(col: np.ndarray, at: np.ndarray) -> np.ndarray:
    """``col[at]`` as a float column, NaN where ``at`` is negative. ``Frame.join`` promotes as
    jax does: a float column of 32 bits or more keeps its type, anything else becomes float32
    (so an integer past 2**24 does not survive a left join: docs/FRAME.md)."""
    wide = col.dtype.kind == "f" and col.dtype.itemsize >= 4
    out = np.full(at.shape, np.nan, col.dtype if wide else np.float32)
    out[at >= 0] = col[at[at >= 0]]
    return out


def join_m1(
    left: Mapping[str, np.ndarray],
    right: Mapping[str, np.ndarray],
    on: str,
    how: str = "inner",
    rsuffix: str = "_r",
) -> Dict[str, np.ndarray]:
    """The many-to-one join of two tables given as dicts of equal-length 1-D NumPy arrays.

    Every row of ``left`` whose key stands in ``right`` (``how="inner"``), or every row of
    ``left`` (``how="left"``, the right columns as floats, NaN where there is no match),
    beside the columns of its one match. Columns: the key, ``left``'s others in its order,
    ``right``'s others in its order, one whose name ``left`` has too with ``rsuffix``
    appended. Rows: in ascending key, ``left``'s own order within a key. A key twice in
    ``right`` raises ``ValueError``: the join is m:1.
    """
    if how not in ("inner", "left"):
        raise ValueError(f"how must be 'inner' or 'left', got {how!r}")
    lk, rk = left[on], right[on]
    by_key = np.argsort(rk, kind="stable")
    srk = rk[by_key]
    if (srk[1:] == srk[:-1]).any():
        raise ValueError("join requires unique keys on the right side (m:1)")
    order = np.argsort(lk, kind="stable")
    slk = lk[order]
    # where in ``right`` each left row's key stands, -1 where it does not
    pos = np.searchsorted(srk, slk, side="left")
    hit = pos < srk.size
    hit[hit] = srk[pos[hit]] == slk[hit]
    at = np.full(slk.shape, -1, np.int64)
    at[hit] = by_key[pos[hit]]
    if how == "inner":
        order, at = order[hit], at[hit]
    out = {on: lk[order]}
    for name, col in left.items():
        if name != on:
            out[name] = col[order]
    for name, col in right.items():
        if name != on:
            out[name + rsuffix if name in left else name] = col[at] if how == "inner" else _null_filled(col, at)
    return out
