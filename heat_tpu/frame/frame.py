""":class:`Frame` — a thin columnar container over split-0 DNDarrays.

Not a dataframe library: a Frame is a dict of equal-length, co-sharded
1-D columns plus the relational verbs the shuffle engine makes cheap —
``groupby(...).agg(...)``, ``value_counts``, hash/range ``join``, and
``filter``. Every verb is *local sort and scan of equal keys per shard → one bounded
exchange per operand → local merge* (or zero exchanges for ``filter``),
dispatched through cached jitted programs: warm repeats are 0-trace /
0-compile, and partition decisions are replicated so every verb is
lockstep-clean at ws>1.

Columns share ONE physical layout (identical per-shard valid counts):
results of the engine come back ragged-but-co-aligned, and mixed-layout
inputs are rebalanced to the canonical map at construction. That single
invariant is what lets every program treat the whole frame as parallel
buffers with one shared counts vector.
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Optional, Tuple

import numpy as np

from ..core import _hooks, factories, types
from ..core.dndarray import DNDarray
from ._shuffle import SHUFFLE_STATS, compact_rows, hash_join, shard_counts

__all__ = ["Frame"]


class Frame:
    """Named, equal-length, identically-sharded split-0 columns.

    Accepts DNDarrays (1-D, split 0) or anything ``heat_tpu.array``
    accepts (converted with ``split=0``). Columns with differing shard
    layouts are rebalanced to the canonical map so the frame invariant
    (one counts vector for all columns) holds.
    """

    def __init__(self, columns: Mapping[str, object]):
        if not columns:
            raise ValueError("Frame needs at least one column")
        cols: Dict[str, DNDarray] = {}
        n = None
        for name, col in columns.items():
            if not isinstance(col, DNDarray):
                col = factories.array(col, split=0)
            if col.ndim != 1:
                raise ValueError(f"column {name!r} must be 1-D, got {col.ndim}-D")
            if col.split != 0:
                raise ValueError(
                    f"column {name!r} must be split along axis 0 (got split={col.split})"
                )
            if n is None:
                n = col.gshape[0]
            elif col.gshape[0] != n:
                raise ValueError(
                    f"column {name!r} has {col.gshape[0]} rows, expected {n}"
                )
            cols[str(name)] = col
        if len({shard_counts(c) for c in cols.values()}) > 1:
            for c in cols.values():
                c.balance_()
        self._cols = cols

    @classmethod
    def _wrap(cls, cols: Dict[str, DNDarray]) -> "Frame":
        """Internal: adopt already co-aligned columns without checks."""
        out = cls.__new__(cls)
        out._cols = dict(cols)
        return out

    # ------------------------------------------------------------- container
    @property
    def columns(self) -> Tuple[str, ...]:
        return tuple(self._cols)

    @property
    def n_rows(self) -> int:
        return next(iter(self._cols.values())).gshape[0]

    @property
    def comm(self):
        return next(iter(self._cols.values())).comm

    def _counts(self) -> Tuple[int, ...]:
        return shard_counts(next(iter(self._cols.values())))

    def __getitem__(self, name: str) -> DNDarray:
        return self._cols[name]

    def __contains__(self, name: str) -> bool:
        return name in self._cols

    def __len__(self) -> int:
        return self.n_rows

    def __repr__(self) -> str:
        return f"Frame(columns={list(self._cols)}, n_rows={self.n_rows})"

    def to_dict(self) -> Dict[str, np.ndarray]:
        """Materialize every column as a host numpy array (logical rows,
        ragged padding trimmed). Test/debug convenience — syncs."""
        return _hooks.fetch({name: c._logical() for name, c in self._cols.items()}, "frame.to_dict")

    # ----------------------------------------------------------------- verbs
    def groupby(self, key: str, mode: str = "range"):
        """Group rows by a key column. ``mode="range"`` (default) emits
        groups in global key order via elected splitters; ``"hash"``
        only co-locates equal keys (cheaper election, unordered)."""
        from .groupby import FrameGroupBy

        if key not in self._cols:
            raise KeyError(f"no column {key!r} in {list(self._cols)}")
        return FrameGroupBy(self, key, mode)

    def value_counts(self, key: str, mode: str = "range") -> "Frame":
        """Occurrences per distinct key: ``groupby(key).count()`` with the
        count column named ``"count"``."""
        return self.groupby(key, mode=mode).count()

    def filter(self, mask) -> "Frame":
        """Rows where ``mask`` is True — per-shard compaction into a
        ragged layout, ZERO exchanges. ``mask`` is a boolean split-0
        DNDarray (a pending lazy column is forced transparently)."""
        if not isinstance(mask, DNDarray):
            mask = factories.array(mask, split=0)
        if mask.ndim != 1 or mask.gshape[0] != self.n_rows:
            raise ValueError(
                f"mask must be 1-D with {self.n_rows} rows, got shape {mask.gshape}"
            )
        if mask.dtype is not types.bool:
            raise TypeError(f"mask must be boolean, got {mask.dtype}")
        counts = self._counts()
        if shard_counts(mask) != counts:
            mask.balance_()
            for c in self._cols.values():
                c.balance_()
            counts = self._counts()
        names = list(self._cols)
        bufs, gvec = compact_rows(
            mask._raw, [self._cols[n]._raw for n in names], counts, self.comm
        )
        kept = int(gvec.sum())
        lcounts = tuple(int(c) for c in gvec)
        dev = next(iter(self._cols.values())).device
        return Frame._wrap(
            {
                n: DNDarray._from_ragged(
                    b, (kept,), b.dtype, 0, lcounts, device=dev, comm=self.comm
                )
                for n, b in zip(names, bufs)
            }
        )

    @_hooks.public_call("Frame.join")
    def join(
        self,
        other: "Frame",
        on: str,
        how: str = "inner",
        rsuffix: str = "_r",
        mode: str = "range",
    ) -> "Frame":
        """Join on a shared key column; right keys must be unique (the
        m:1 contract — duplicates raise ``ValueError``). On a mesh of
        more than one device both sides are co-partitioned by ONE shared
        splitter election and each side pays one bounded exchange per
        operand; on a mesh of one device equal keys are together already
        and nothing is elected, partitioned or moved. Then a device-local
        merge join matches rows: one sort of both sides together (by the
        key, then by where a row stood: what a stable sort gives), each
        right row's values carried along the left rows with its key.
        ``how="left"`` NaN-fills unmatched right values (right columns
        promote to float: float32 unless they are wider).

        Columns of the result: the key, this frame's others in its
        order, ``other``'s others in its order (``rsuffix`` appended to
        a name this frame has too). Rows: on each shard in ascending
        key, this frame's own order within a key; with ``mode="range"``
        the shards hold ascending key ranges in rank order, so the whole
        result is in that order, row for row what
        :func:`heat_tpu.frame.reference.join_m1` returns. ``"hash"`` only
        co-locates equal keys: each shard is ordered, the shards are
        not (on one device ``mode`` has no effect). Neither input is
        changed or consumed.

        Memory, a device: beside the two frames' own blocks a call on a
        mesh holds a moved copy of every column of both sides, each in
        blocks as long as the fullest destination (the mean times
        ``SHUFFLE_STATS["bucket_skew"]``, 1.0 on uniform keys; rounded
        up by at most a 64th, so that the merge is not compiled anew
        for every table's keys), and the
        result, whose block is as long as BOTH sides' blocks together
        whatever matches, for each of its columns; the sorts'
        temporaries come on top (docs/FRAME.md has the sum). Reading a
        result column through ``.larray`` rebalances it: one more copy
        of that column."""
        if on not in self._cols or on not in other._cols:
            raise KeyError(f"join key {on!r} must exist in both frames")
        lk, rk = self._cols[on], other._cols[on]
        if lk.dtype is not rk.dtype:
            raise TypeError(
                f"join key dtypes differ: {lk.dtype} vs {rk.dtype}"
            )
        l_names = [n for n in self._cols if n != on]
        r_names = [n for n in other._cols if n != on]
        out_names = [on] + l_names
        for n in r_names:
            name = n if n not in self._cols else f"{n}{rsuffix}"
            if name in out_names:
                raise ValueError(f"column name collision on {name!r} after rsuffix")
            out_names.append(name)
        bufs, gvec, dup = hash_join(
            lk,
            [self._cols[n]._raw for n in l_names],
            rk,
            [other._cols[n]._raw for n in r_names],
            how=how,
            mode=mode,
        )
        if dup:
            raise ValueError(
                "join requires unique keys on the right side (m:1); "
                "aggregate the right frame first"
            )
        n_out = int(gvec.sum())
        lcounts = tuple(int(c) for c in gvec)
        dev = lk.device
        return Frame._wrap(
            {
                name: DNDarray._from_ragged(
                    b, (n_out,), b.dtype, 0, lcounts, device=dev, comm=self.comm
                )
                for name, b in zip(out_names, bufs)
            }
        )
