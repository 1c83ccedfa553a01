"""The frame layer's compaction (``_shuffle._compact_front``): kept rows shifted to the front in
log-shift passes, no sort. First the piece alone against ``x[keep]``, then through each verb that
runs it (``Frame.filter`` = ``compact_rows``, ``Frame.join`` inner and left, the groupby's plan and
merge) on meshes of 1, 4 and 8 devices, with the gauge ``SHUFFLE_STATS["compact_steps"]`` held to
what the data asks: the bit length of the most rows dropped ahead of a kept one.
"""
from __future__ import annotations

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.analysis.sanitizer import COMPILE_STATS, sanitizer
from heat_tpu.frame import SHUFFLE_STATS, Frame

from ._frame_helpers import _mesh_of, _release_executables  # noqa: F401 - fixtures

_KEEP = {
    "none": lambda b, rng: np.zeros(b, bool),
    "all": lambda b, rng: np.ones(b, bool),
    "first_only": lambda b, rng: np.arange(b) == 0,
    "last_only": lambda b, rng: np.arange(b) == b - 1,  # the largest displacement a block can ask, b - 1
    "one_in_a_hundred": lambda b, rng: rng.random(b) < 0.01,
    "nine_in_ten": lambda b, rng: rng.random(b) < 0.9,
    "alternating": lambda b, rng: np.arange(b) % 2 == 1,
}


def _columns(b: int, rng):
    """One column of each kind a frame carries: int32, f32 with NaNs, int8, bool."""
    f = rng.normal(size=b).astype(np.float32)
    f[rng.random(b) < 0.2] = np.nan
    return [rng.integers(-(1 << 30), 1 << 30, b).astype(np.int32), f, rng.integers(-100, 100, b).astype(np.int8), rng.random(b) < 0.5]


def _steps(keep: np.ndarray) -> int:
    """What a block asks of the mechanism: the bit length of the rows dropped ahead of its last kept row."""
    if not keep.any():
        return 0
    last = np.flatnonzero(keep)[-1]
    return int(last + 1 - keep.sum()).bit_length()


def _same(got, want, name=""):
    """Bit for bit: NaNs are equal to themselves and a dtype is part of the result."""
    got = np.asarray(got)
    assert got.dtype == want.dtype, (name, got.dtype, want.dtype)
    np.testing.assert_array_equal(got, want, err_msg=str(name))


def _frame_on(table, comm) -> Frame:
    return Frame({c: ht.array(a, split=0, comm=comm) for c, a in table.items()})


def _shard_blocks(n: int, devices: int):
    """The canonical split-0 layout: (start, stop) of each device's rows."""
    block = -(-n // devices)
    return [(min(r * block, n), min((r + 1) * block, n)) for r in range(devices)]


class TestCompactFront:
    @pytest.mark.parametrize("pattern", sorted(_KEEP))
    @pytest.mark.parametrize("rows", [1, 2, 3, 1_000, 4_097])
    def test_kept_rows_come_first_in_their_order(self, rows, pattern):
        import jax

        from heat_tpu.frame._shuffle import _compact_front

        rng = np.random.default_rng([32, rows])
        keep, cols = _KEEP[pattern](rows, rng), _columns(rows, rng)
        out, steps = jax.jit(_compact_front)(keep, cols)
        kept = int(keep.sum())
        for got, col in zip(out, cols):
            assert got.shape == col.shape
            _same(np.asarray(got)[:kept], col[keep], (pattern, col.dtype))
        assert int(steps) == _steps(keep)
        assert int(steps) <= (rows - 1).bit_length()
        if pattern in ("all", "none", "first_only"):
            assert int(steps) == 0  # nothing is dropped ahead of a kept row
        if pattern == "last_only":
            assert int(steps) == (rows - 1).bit_length()

    def test_no_sort_and_no_index_in_the_lowering(self):
        import re

        import jax

        from heat_tpu.frame._shuffle import _compact_front

        keep, cols = np.ones(4_097, bool), _columns(4_097, np.random.default_rng(32))
        text = jax.jit(_compact_front).lower(keep, cols).as_text()
        assert re.findall(r"stablehlo\.(?:sort|(?:dynamic_)?gather|scatter|dynamic_update_slice)", text) == []


class TestThroughTheVerbs:
    @pytest.mark.parametrize("devices", [1, 4, 8])
    @pytest.mark.parametrize("pattern", ["none", "last_only", "nine_in_ten", "alternating"])
    def test_filter_keeps_what_the_mask_says(self, pattern, devices):
        comm = _mesh_of(devices)
        n = 1_003
        rng = np.random.default_rng([33, devices])
        keep = _KEEP[pattern](n, rng)
        names = ("i", "f", "b", "flag")
        table = dict(zip(names, _columns(n, rng)))
        frame = _frame_on(table, comm)
        mask = ht.array(keep, split=0, comm=comm)
        frame.filter(mask)  # cold
        syncs = COMPILE_STATS["host_syncs"]
        with sanitizer("warm filter") as region:
            out = frame.filter(mask)
        assert region.compiles == 0 and region.traces == 0, region.stats()
        assert COMPILE_STATS["host_syncs"] - syncs == 1  # the counts, and the steps with them
        assert SHUFFLE_STATS["compact_steps"] == max(_steps(keep[a:b]) for a, b in _shard_blocks(n, devices))
        for name, got in out.to_dict().items():
            _same(got, table[name][keep], name)

    @pytest.mark.parametrize("mode", ["range", "hash"])
    @pytest.mark.parametrize("devices", [1, 4, 8])
    @pytest.mark.parametrize("how", ["inner", "left"])
    def test_join_equals_the_reference(self, how, devices, mode):
        from heat_tpu.frame.reference import join_m1

        comm = _mesh_of(devices)
        rng = np.random.default_rng([34, devices])
        n, m = 2_000, 300
        x = {"k": rng.integers(0, 400, n).astype(np.int32), "a": rng.integers(0, 9, n).astype(np.int8), "v": rng.normal(size=n).astype(np.float32)}
        y = {"k": rng.permutation(400)[:m].astype(np.int32), "w": rng.normal(size=m).astype(np.float32), "on": rng.random(m) < 0.5}
        left, right = _frame_on(x, comm), _frame_on(y, comm)
        left.join(right, on="k", how=how, mode=mode)  # cold
        syncs = COMPILE_STATS["host_syncs"]
        with sanitizer("warm join") as region:
            out = left.join(right, on="k", how=how, mode=mode)
        assert region.compiles == 0 and region.traces == 0, region.stats()
        assert COMPILE_STATS["host_syncs"] - syncs == (2 if devices == 1 else 4)
        got, want = out.to_dict(), join_m1(x, y, on="k", how=how)
        if mode == "hash" and devices > 1:  # the shards are ordered, the mesh is not
            order = np.argsort(got["k"], kind="stable")
            got = {c: a[order] for c, a in got.items()}
        for name in want:
            _same(got[name], want[name], name)
        steps = SHUFFLE_STATS["compact_steps"]
        if devices == 1:
            # the block: the right rows, then the left ones, sorted together by key, stably
            keys = np.concatenate([y["k"], x["k"]])
            is_left = np.arange(m + n) >= m
            order = np.argsort(keys, kind="stable")
            keep = is_left[order] & (np.isin(keys[order], y["k"]) if how == "inner" else True)
            assert steps == _steps(keep) > 0
        else:
            assert 0 < steps <= (n + m).bit_length()

    @pytest.mark.parametrize("mode", ["range", "hash"])
    @pytest.mark.parametrize("devices", [1, 4, 8])
    @pytest.mark.parametrize("groups", [1, 7, 1_500])
    def test_groupby_sums_are_numpys(self, groups, devices, mode):
        comm = _mesh_of(devices)
        rng = np.random.default_rng([35, devices, groups])
        n = 1_500
        k = rng.permutation(n).astype(np.int32) if groups == n else rng.integers(0, groups, n).astype(np.int32)
        v = rng.integers(-50, 50, n).astype(np.int32)
        frame = _frame_on({"k": k, "v": v}, comm)
        frame.groupby("k", mode=mode).agg({"v": ["sum", "count"]})  # cold
        syncs = COMPILE_STATS["host_syncs"]
        with sanitizer("warm groupby") as region:
            out = frame.groupby("k", mode=mode).agg({"v": ["sum", "count"]})
        assert region.compiles == 0 and region.traces == 0, region.stats()
        assert COMPILE_STATS["host_syncs"] - syncs == 2  # the bucket matrix, the group counts
        got = out.to_dict()
        order = np.argsort(got["k"], kind="stable")
        uniq, inverse, counts = np.unique(k, return_inverse=True, return_counts=True)
        if mode == "range":
            assert (order == np.arange(order.size)).all()  # the groups come out in key order over the mesh
        _same(got["k"][order], uniq, "k")
        _same(got["v_sum"][order], np.bincount(inverse, weights=v).astype(np.int32), "v_sum")
        np.testing.assert_array_equal(got["v_count"][order], counts)
        steps = SHUFFLE_STATS["compact_steps"]
        if devices == 1:
            # the plan: each run's last row kept among the n sorted ones; the merge drops nothing
            assert steps == (n - uniq.size).bit_length()
        else:
            assert 0 <= steps <= n.bit_length()
            if mode == "range" and groups == 7:  # every shard folds its ~190 rows into 7
                assert steps >= 7
