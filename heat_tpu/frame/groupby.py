"""``Frame.groupby(...)`` — aggregation planning over the shuffle engine.

The planner turns user aggs (sum/mean/min/max/count/std) into the
minimal set of RAW associative statistics the engine must carry (a mean
needs a float sum and the group count; a std additionally a sum of
squares; duplicates are computed once). The engine moves exactly one
bounded exchange per raw statistic plus one for the keys; everything a
non-associative agg needs is *derived* afterwards from associative
pieces with plain DNDarray arithmetic — which keeps the finalize step
capturable by ``ht.lazy()``, so ``groupby → agg → filter`` chains fuse
into one replayed program.

``quantile`` is the one agg that is NOT associative in bounded memory,
so it does not ride the shuffle at all: each process folds its local
shard rows into one KLL sketch per (key, column) — a single vmapped
device dispatch per column — and ONE log-depth
:func:`~heat_tpu.core.communication.tree_merge` combines the per-key
sketch states across processes (``bucket_moves`` stays 0; only the
small key-union ragged allgather and the sketch-state butterfly move).
The answer is approximate within the KLL rank-error bound,
``(3 + ceil(log2 P)) / (2k)`` of each group's row count.
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Sequence, Tuple, Union

import numpy as np

from ..core import _hooks
from ..core.dndarray import DNDarray
from ._shuffle import groupby_reduce

__all__ = ["FrameGroupBy", "AGGS"]


def _grouped_kll_combine(a, b):
    """Per-column dict of vmapped KLL combines — the ``tree_merge``
    operand for :meth:`FrameGroupBy.quantile` (module-level: its identity
    keys the butterfly program cache)."""
    from ..stream.sketch.kll import grouped_merge_states

    return {c: grouped_merge_states(a[c], b[c]) for c in a}

AGGS = ("sum", "mean", "min", "max", "count", "std")

AggSpec = Union[str, Sequence[str], Mapping[str, Union[str, Sequence[str]]]]


def _sum_dtype(vdt: np.dtype) -> str:
    return "int32" if vdt == np.bool_ else str(vdt)


def _float_dtype(vdt: np.dtype) -> str:
    return str(np.promote_types(vdt, np.float32))


class FrameGroupBy:
    """Deferred groupby: holds (frame, key, partition mode) until an
    aggregation names the statistics to carry through the shuffle."""

    def __init__(self, frame, key: str, mode: str = "range"):
        self._frame = frame
        self._key = key
        self._mode = mode

    # ------------------------------------------------------------- plan+run
    @_hooks.public_call("groupby.agg")
    def agg(self, spec: AggSpec, ddof: int = 1):
        """Aggregate value columns per distinct key.

        ``spec`` is a single agg name (applied to every non-key column),
        a list of agg names, or a ``{column: agg | [aggs]}`` mapping.
        Returns a :class:`Frame` whose first column is the key (globally
        sorted in range mode); value columns keep their name for a
        single agg and gain a ``_<agg>`` suffix otherwise. ``count``
        needs no value column and lands in a column named ``"count"``
        when requested by name.
        """
        frame, key = self._frame, self._key
        value_cols = [n for n in frame.columns if n != key]
        # ---- normalize to ordered (column, agg, out_name) requests
        requests: List[Tuple[str, str]] = []
        if isinstance(spec, str):
            spec = [spec]
        if isinstance(spec, Mapping):
            for col, aggs in spec.items():
                if col not in frame.columns or col == key:
                    raise KeyError(f"cannot aggregate column {col!r}")
                for a in [aggs] if isinstance(aggs, str) else list(aggs):
                    requests.append((col, a))
        else:
            for a in list(spec):
                if a == "count":
                    requests.append((key, "count"))
                else:
                    requests.extend((c, a) for c in value_cols)
        if not requests:
            raise ValueError("empty aggregation spec")
        for col, a in requests:
            if a not in AGGS:
                raise ValueError(f"unknown agg {a!r}; choose from {AGGS}")
        multi = {c: n > 1 for c, n in _multiplicity(requests).items()}

        # ---- plan raw associative statistics (deduplicated)
        used_cols = sorted(
            {c for c, a in requests if a != "count"}, key=frame.columns.index
        )
        ci = {c: i for i, c in enumerate(used_cols)}
        vdts = {c: np.dtype(frame[c]._raw.dtype) for c in used_cols}
        raw: Dict[Tuple[str, int, str], int] = {}

        def need(kind: str, col: str) -> Tuple[str, int, str]:
            if kind == "count":
                k = ("count", 0, "int32")
            elif kind in ("min", "max"):
                k = (kind, ci[col], str(vdts[col]))
            elif kind == "sum":
                k = ("sum", ci[col], _sum_dtype(vdts[col]))
            elif kind == "fsum":
                k = ("sum", ci[col], _float_dtype(vdts[col]))
            else:  # fsumsq
                k = ("sumsq", ci[col], _float_dtype(vdts[col]))
            raw.setdefault(k, len(raw))
            return k

        plan: List[Tuple[str, str, str, List[Tuple[str, int, str]]]] = []
        for col, a in requests:
            if a == "count":
                slots = [need("count", col)]
            elif a in ("sum", "min", "max"):
                slots = [need(a if a != "sum" else "sum", col)]
            elif a == "mean":
                slots = [need("fsum", col), need("count", col)]
            else:  # std
                slots = [need("fsum", col), need("fsumsq", col), need("count", col)]
            name = "count" if a == "count" and col == key else (
                f"{col}_{a}" if multi[col] else col
            )
            plan.append((name, col, a, slots))

        # ---- one shuffle carries every raw statistic
        stats = tuple(sorted(raw, key=raw.get))
        mkeys, reduced, _ = groupby_reduce(
            frame[key],
            [frame[c]._raw for c in used_cols],
            tuple(str(vdts[c]) for c in used_cols),
            stats,
            mode=self._mode,
        )
        slot = {k: reduced[i] for i, k in enumerate(stats)}

        # ---- derive requested aggs (plain DNDarray ops: lazy-capturable)
        out: Dict[str, DNDarray] = {key: mkeys}
        for name, col, a, slots in plan:
            if name in out:
                raise ValueError(f"duplicate output column {name!r}")
            if a in ("sum", "min", "max", "count"):
                out[name] = slot[slots[0]]
            elif a == "mean":
                fsum, cnt = slot[slots[0]], slot[slots[1]]
                out[name] = fsum / cnt
            else:  # std
                fsum, fsumsq, cnt = (slot[s] for s in slots)
                mean = fsum / cnt
                var = (fsumsq / cnt - mean * mean) * (cnt / (cnt - ddof))
                out[name] = var.clip(0.0, None).sqrt()
        from .frame import Frame

        return Frame._wrap(out)

    # ------------------------------------------------- approximate quantile
    def quantile(self, q: float = 0.5, k: int = 256, levels: int = 8):
        """Approximate per-group quantile of every value column WITHOUT a
        shuffle (see the module docstring for the mechanism and bound).

        ``q`` is a fraction in [0, 1] (pandas convention). ``k`` /
        ``levels`` size the per-group KLL sketches. Returns a
        :class:`Frame` keyed by the sorted distinct keys, one column per
        value column, replicated-exact across processes.
        """
        q = float(q)
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"q must be a fraction in [0, 1], got {q}")
        frame, key = self._frame, self._key
        value_cols = [n for n in frame.columns if n != key]
        if not value_cols:
            raise ValueError("quantile needs at least one value column")
        import jax.numpy as jnp

        from ..core.communication import ragged_process_allgather, tree_merge
        from ..stream.sketch import kll

        # ---- host-local grouping: trimmed shard rows, bucketed by key
        def host_rows(col: str) -> np.ndarray:
            blocks = [
                np.asarray(sh)  # graftlint: host-sync - local shard staging
                for _, sh in frame[col]._iter_local_shards(dedup=True)
            ]
            dt = np.dtype(frame[col]._raw.dtype)
            return np.concatenate(blocks) if blocks else np.empty((0,), dt)

        keys_local = host_rows(key)
        uniq_local = np.unique(keys_local)
        union = np.unique(np.concatenate(ragged_process_allgather(uniq_local)))
        G = union.size
        order = np.argsort(keys_local, kind="stable")
        sorted_keys = keys_local[order]
        starts = np.searchsorted(sorted_keys, union, side="left")
        ends = np.searchsorted(sorted_keys, union, side="right")
        counts = (ends - starts).astype(np.int32)
        lmax = max(int(counts.max(initial=0)), 1)

        # ---- one vmapped KLL fold per column, one tree_merge for all
        state: Dict[str, tuple] = {}
        v0 = jnp.full((G, levels, k), jnp.inf, jnp.float32)
        w0 = jnp.zeros((G, levels, k), jnp.float32)
        prog = kll._grouped_fold_program(k, levels)
        for c in value_cols:
            rows = host_rows(c).astype(np.float32)[order]
            padded = np.zeros((G, lmax, 1), np.float32)
            for g in range(G):
                padded[g, : counts[g], 0] = rows[starts[g] : ends[g]]
            vals, wts = prog(jnp.asarray(padded), jnp.asarray(counts), v0, w0)
            state[c] = (
                jnp.asarray(counts),
                jnp.ones((G,), jnp.int32),
                vals,
                wts,
            )
        merged = tree_merge(
            state, _grouped_kll_combine, label="collective.groupby_quantile"
        )

        # ---- finalize: per-group quantile eval + replicated host columns
        from ..core import factories

        out: Dict[str, DNDarray] = {}
        out[key] = union
        qs = jnp.asarray([q], jnp.float32)
        for c in value_cols:
            _, _, vals, wts = merged[c]
            res = kll._grouped_quantile(vals, wts, qs)[:, 0]
            out[c] = _hooks.fetch(res, "groupby.finalize")  # O(G) finalize
        from .frame import Frame

        return Frame(
            {name: factories.array(colv, split=0) for name, colv in out.items()}
        )

    # -------------------------------------------------------- conveniences
    def sum(self):
        return self.agg("sum")

    def mean(self):
        return self.agg("mean")

    def min(self):
        return self.agg("min")

    def max(self):
        return self.agg("max")

    def std(self, ddof: int = 1):
        return self.agg("std", ddof=ddof)

    def count(self):
        return self.agg("count")


def _multiplicity(requests: List[Tuple[str, str]]) -> Dict[str, int]:
    m: Dict[str, int] = {}
    for col, _ in requests:
        m[col] = m.get(col, 0) + 1
    return m
