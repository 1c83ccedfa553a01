"""``Frame.join``: the verb's own contracts against numpy, then every
column against the plain reference (``heat_tpu/frame/reference.py``'s
``join_m1``) over meshes of 1, 4 and 8 devices, both partition modes and
the cases the merge makes delicate. The groupby, the container and the
engine's contracts are in ``tests/test_frame.py``; the world-size sweep
rides the same runs as that file's.
"""
from __future__ import annotations

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.analysis.sanitizer import sanitizer
from heat_tpu.frame import Frame
from heat_tpu.parallel.flatmove import MOVE_STATS

from ._frame_helpers import ROWS, _mesh_of, _release_executables, _sorted_dict, rng  # noqa: F401 - fixtures


class TestJoin:
    def test_inner_join_oracle(self, rng):
        lk = rng.integers(0, 30, size=ROWS).astype(np.int32)
        lx = rng.normal(size=ROWS).astype(np.float32)
        rk = np.arange(0, 20, dtype=np.int32)  # unique right keys 0..19
        ry = rng.normal(size=20).astype(np.float32)
        out = Frame({"k": lk, "x": lx}).join(Frame({"k": rk, "y": ry}), on="k")
        d = out.to_dict()
        keep = lk < 20
        assert len(d["k"]) == int(keep.sum())
        order = np.lexsort((d["x"], d["k"]))
        worder = np.lexsort((lx[keep], lk[keep]))
        np.testing.assert_array_equal(d["k"][order], lk[keep][worder])
        np.testing.assert_allclose(d["x"][order], lx[keep][worder], rtol=1e-6)
        np.testing.assert_allclose(d["y"][order], ry[lk[keep]][worder], rtol=1e-6)

    def test_left_join_nan_fills(self, rng):
        lk = np.array([0, 1, 5, 9, 3], np.int32)
        lx = np.arange(5, dtype=np.float32)
        rk = np.array([0, 1, 2, 3], np.int32)
        ry = np.array([10.0, 11.0, 12.0, 13.0], np.float32)
        out = Frame({"k": lk, "x": lx}).join(
            Frame({"k": rk, "y": ry}), on="k", how="left"
        )
        d = _sorted_dict(out, "k")
        np.testing.assert_array_equal(d["k"], [0, 1, 3, 5, 9])
        np.testing.assert_array_equal(d["x"], [0.0, 1.0, 4.0, 2.0, 3.0])
        np.testing.assert_allclose(d["y"][:3], [10.0, 11.0, 13.0])
        assert np.isnan(d["y"][3:]).all()  # unmatched left rows

    def test_join_exchange_budget(self, rng):
        lk = rng.integers(0, 16, size=100).astype(np.int32)
        f = Frame({"k": lk, "x": np.ones(100, np.float32)})
        small = Frame({"k": np.arange(16, dtype=np.int32), "y": np.ones(16, np.float32)})
        f.join(small, on="k")  # cold
        before = MOVE_STATS["bucket_moves"]
        f.join(small, on="k")
        # each side ships key + payload once: (1+1) + (1+1)
        assert MOVE_STATS["bucket_moves"] - before == 4

    def test_duplicate_right_keys_raise(self):
        f = Frame({"k": np.array([0, 1], np.int32), "x": np.ones(2, np.float32)})
        dup = Frame({"k": np.array([1, 1], np.int32), "y": np.ones(2, np.float32)})
        with pytest.raises(ValueError, match="unique keys"):
            f.join(dup, on="k")

    def test_join_validation(self):
        f = Frame({"k": np.array([0, 1], np.int32), "x": np.ones(2, np.float32)})
        g = Frame({"k": np.array([0, 1], np.float32), "x": np.ones(2, np.float32)})
        with pytest.raises(KeyError, match="join key"):
            f.join(g, on="missing")
        with pytest.raises(TypeError, match="dtypes differ"):
            f.join(g, on="k")
        h = Frame({"k": np.array([0, 1], np.int32), "x_r": np.ones(2, np.float32),
                   "x": np.ones(2, np.float32)})
        with pytest.raises(ValueError, match="collision"):
            f.join(h, on="k")
        # default rsuffix disambiguates the shared value-column name
        out = f.join(
            Frame({"k": np.array([0, 1], np.int32), "x": np.ones(2, np.float32)}),
            on="k",
        )
        assert set(out.columns) == {"k", "x", "x_r"}


# --- Frame.join against the plain reference (heat_tpu/frame/reference.py), at the shapes of
# h2o.ai db-benchmark's join question 2 ("medium inner on int"), cut to 2e4 rows: x with seven
# columns, ``medium`` (rows/1000 rows, its id2 unique) with five, three names in both; a key
# column's values come from a shuffled pool of 1.1 n, the first 0.9 n on both sides, the next
# 0.1 n in x only, the last 0.1 n in ``medium`` only, so about nine rows in ten of x match.
_J_ROWS, _J_MEDIUM = 20_000, 20


def _h2o_pool(rng, n):
    """The source's ``split_xlr(n)``: 1..1.1n shuffled, as (values of x, values of the right table)."""
    values = (rng.permutation(n + n // 10) + 1).astype(np.int32)
    return values[:n], np.concatenate([values[: n - n // 10], values[n:]])


def _h2o_join_tables(rng):
    x1, m1 = _h2o_pool(rng, 10)
    x2, m2 = _h2o_pool(rng, _J_MEDIUM)
    x = {"id1": rng.choice(x1, _J_ROWS), "id2": rng.choice(x2, _J_ROWS),
         "id3": rng.integers(1, _J_ROWS + 1, _J_ROWS).astype(np.int32)}
    x.update(id4=x["id1"].copy(), id5=x["id2"].copy(), id6=x["id3"].copy(),
             v1=rng.uniform(0, 100, _J_ROWS).astype(np.float32))
    medium = {"id1": rng.choice(m1, _J_MEDIUM), "id2": rng.permutation(m2)}
    medium.update(id4=medium["id1"].copy(), id5=medium["id2"].copy(),
                  v2=rng.uniform(0, 100, _J_MEDIUM).astype(np.float32))
    return x, medium

_MERGE_ROWS, _MERGE_RIGHT = 300, 48  # one size a side: one set of programs a mesh, a ``how`` and a key type


def _frame_on(table, comm) -> Frame:
    return Frame({c: ht.array(a, split=0, comm=comm) for c, a in table.items()})


def _assert_columns_equal(got, want):
    """Every column of ``want`` in ``got`` with its dtype, bit for bit (NaN equals NaN, -0.0 equals 0.0)."""
    for name in want:
        assert got[name].dtype == want[name].dtype, name
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


def _merge_tables(rng, lk, rk):
    """(left, right) around two key columns: ``a`` on both sides, ``v`` left, ``w`` right."""
    lk, rk = np.asarray(lk), np.asarray(rk)
    rk = rk.astype(lk.dtype)
    assert lk.shape == (_MERGE_ROWS,) and rk.shape == (_MERGE_RIGHT,)
    left = {"k": lk, "a": rng.integers(-9, 9, lk.size).astype(np.int32), "v": rng.normal(size=lk.size).astype(np.float32)}
    right = {"k": rk, "a": rng.integers(100, 999, rk.size).astype(np.int32), "w": rng.normal(size=rk.size).astype(np.float32)}
    return left, right


def _max_key_case(with_right_row: bool):
    def make(rng):
        top = np.iinfo(np.int32).max
        lk = rng.integers(top - 40, top, _MERGE_ROWS, dtype=np.int64)
        lk[rng.permutation(_MERGE_ROWS)[:25]] = top  # on every shard, beside its pads
        rk = top - 1 - rng.permutation(60)[:_MERGE_RIGHT]
        if with_right_row:
            rk[rng.integers(_MERGE_RIGHT)] = top
        return _merge_tables(rng, lk.astype(np.int32), rk)

    return make


def _nan_case(rng):
    lk = rng.integers(0, 40, _MERGE_ROWS).astype(np.float32)
    lk[rng.permutation(_MERGE_ROWS)[:30]] = np.nan
    lk[rng.permutation(_MERGE_ROWS)[:10]] = np.inf
    rk = rng.permutation(60)[:_MERGE_RIGHT].astype(np.float32)
    rk[[2, 17, 30]] = np.nan  # three NaNs are no duplicate: none equals another
    rk[5] = np.inf
    return _merge_tables(rng, lk, rk)


def _zeros_case(rng):
    lk = rng.integers(-3, 4, _MERGE_ROWS).astype(np.float32)
    lk[(lk == 0) & (rng.random(_MERGE_ROWS) < 0.5)] = -0.0
    assert np.signbit(lk[lk == 0]).any() and not np.signbit(lk[lk == 0]).all()
    rk = np.arange(10, 10 + _MERGE_RIGHT, dtype=np.float32)
    rk[[40, 3, 11, 25]] = [2.0, -0.0, -1.0, 5.0]  # the right row's zero is the negative one
    return _merge_tables(rng, lk, rk)


def _long_run_case(rng):
    """One key on 2**7 + 3 rows, from the middle of the first shard's block (on eight devices: 38
    rows) over the next ones, so the run crosses shards before the exchange and a power of two
    after it; its right row exists. A second run of 2**6 + 1 rows finds none."""
    lk = rng.integers(0, 30, _MERGE_ROWS)
    lk[19 : 19 + 131] = 77
    lk[200:265] = 88
    return _merge_tables(rng, lk.astype(np.int32), np.concatenate([rng.permutation(70)[: _MERGE_RIGHT - 1], [77]]))


_MERGE_CASES = {
    "max_key_with_its_right_row": _max_key_case(True),
    "max_key_without_a_right_row": _max_key_case(False),
    "nan_keys": _nan_case,
    "signed_zeros": _zeros_case,
    "none_matched": lambda rng: _merge_tables(rng, 2 * rng.integers(0, 50, _MERGE_ROWS), 2 * rng.permutation(50)[:_MERGE_RIGHT] + 1),
    "all_matched": lambda rng: _merge_tables(rng, rng.integers(0, 37, _MERGE_ROWS), rng.permutation(_MERGE_RIGHT)),
    "a_run_across_a_power_of_two": _long_run_case,
}


class TestJoinAgainstReference:
    @pytest.mark.parametrize("mode", ["range", "hash"])
    @pytest.mark.parametrize("devices", [1, 4, 8])
    @pytest.mark.parametrize("how", ["inner", "left"])
    def test_every_column_equals_the_references(self, how, devices, mode):
        from heat_tpu.frame.reference import join_m1
        from heat_tpu.frame._shuffle import shard_counts

        comm = _mesh_of(devices)
        x, medium = _h2o_join_tables(np.random.default_rng([27, devices]))
        # x as a filter leaves it: ragged, and on a mesh its second shard empty (the rows
        # it drops stay behind the kept ones as the pads' content, keys that would match)
        keep = np.random.default_rng(devices).random(_J_ROWS) < 0.9
        block = -(-_J_ROWS // devices)
        if devices > 1:
            keep[block : 2 * block] = False
        full = Frame({c: ht.array(a, split=0, comm=comm) for c, a in x.items()})
        left = full.filter(ht.array(keep, split=0, comm=comm))
        assert shard_counts(left["id2"]) == tuple(int(keep[r * block : (r + 1) * block].sum()) for r in range(devices))
        right = Frame({c: ht.array(a, split=0, comm=comm) for c, a in medium.items()})
        xk = {c: a[keep] for c, a in x.items()}
        matched = np.isin(xk["id2"], medium["id2"])
        assert 0.85 < matched.mean() < 0.95 and not np.isin(medium["id2"], xk["id2"]).all()

        want = join_m1(xk, medium, on="id2", how=how)
        out = left.join(right, on="id2", how=how, mode=mode)
        got = out.to_dict()
        assert out.columns == tuple(want) == ("id2", "id1", "id3", "id4", "id5", "id6", "v1", "id1_r", "id4_r", "id5_r", "v2")
        if mode == "hash":
            # equal keys share a shard and each shard is in the promised order, the shards are
            # not: one stable sort by key of the shards laid end to end is the promised order
            order = np.argsort(got["id2"], kind="stable")
            got = {c: a[order] for c, a in got.items()}
        _assert_columns_equal(got, want)  # range: row for row as it stands, over the whole mesh

    def test_a_second_call_compiles_nothing_and_leaves_both_frames_as_they_were(self):
        from heat_tpu.frame.reference import join_m1

        x, medium = _h2o_join_tables(np.random.default_rng(28))
        left, right = Frame(x), Frame(medium)
        first = left.join(right, on="id2").to_dict()  # cold
        with sanitizer("warm frame join") as region:
            again = left.join(right, on="id2")
        assert region.compiles == 0, region.stats()
        assert region.traces == 0, region.stats()
        want = join_m1(x, medium, on="id2")
        for name, col in again.to_dict().items():
            np.testing.assert_array_equal(col, first[name], err_msg=name)
            np.testing.assert_array_equal(col, want[name], err_msg=name)
        # what a call may never do, with or without donated buffers: touch a user's column
        for frame, table in ((left, x), (right, medium)):
            for name, col in frame.to_dict().items():
                np.testing.assert_array_equal(col, table[name], err_msg=name)

    # --- what the merge makes delicate: one stable sort of the right block with the left block
    # behind it, the pads of both inside it under the key nothing sorts after, the right row's
    # values carried forward along each run of equal keys. Tables of ``_MERGE_ROWS`` left rows
    # over two columns a side, one name in both; each case builds (left, right) as NumPy dicts.
    @pytest.mark.parametrize("devices", [1, 4, 8])
    @pytest.mark.parametrize("how", ["inner", "left"])
    @pytest.mark.parametrize("case", sorted(_MERGE_CASES))
    def test_the_merge_equals_the_reference(self, case, how, devices):
        from heat_tpu.frame.reference import join_m1

        comm = _mesh_of(devices)
        x, y = _MERGE_CASES[case](np.random.default_rng([28, devices]))
        want = join_m1(x, y, on="k", how=how)
        out = _frame_on(x, comm).join(_frame_on(y, comm), on="k", how=how)
        got = out.to_dict()
        assert out.columns == tuple(want) == ("k", "a", "v", "a_r", "w")
        _assert_columns_equal(got, want)
        if case == "signed_zeros":  # the key a row comes out with is its own, sign and all
            np.testing.assert_array_equal(np.signbit(got["k"]), np.signbit(want["k"]))
        if case in ("none_matched", "all_matched"):  # the case is what its name says
            assert np.isin(x["k"], y["k"]).all() == (case == "all_matched") == np.isin(x["k"], y["k"]).any()

    @pytest.mark.parametrize("devices", [1, 4, 8])
    @pytest.mark.parametrize("how", ["inner", "left"])
    @pytest.mark.parametrize("side", ["left", "right"])
    def test_an_empty_shard_on_either_side(self, side, how, devices):
        """A side whose second shard (its only one, on one device) holds no row: a filter's result."""
        from heat_tpu.frame.reference import join_m1
        from heat_tpu.frame._shuffle import shard_counts

        comm = _mesh_of(devices)
        rng = np.random.default_rng([29, devices])
        x, y = _merge_tables(rng, rng.integers(0, 60, _MERGE_ROWS), rng.permutation(80)[:_MERGE_RIGHT])
        tables, keep = {"left": x, "right": y}, {}
        for name, table in tables.items():
            n = len(table["k"])
            keep[name] = np.ones(n, bool)
            if name == side:
                block = -(-n // devices)
                keep[name][block : 2 * block] = False
                if devices == 1:
                    keep[name][:] = False
        frames = {name: _frame_on(table, comm).filter(ht.array(keep[name], split=0, comm=comm))
                  for name, table in tables.items()}
        assert 0 in shard_counts(frames[side]["k"])
        want = join_m1(*({c: a[keep[name]] for c, a in tables[name].items()} for name in ("left", "right")), on="k", how=how)
        _assert_columns_equal(frames["left"].join(frames["right"], on="k", how=how).to_dict(), want)

    @pytest.mark.parametrize("devices", [1, 4, 8])
    @pytest.mark.parametrize("how", ["inner", "left"])
    @pytest.mark.parametrize("where", ["adjacent", "first_and_last", "the_maximum_twice"])
    def test_a_key_twice_on_the_right_raises(self, where, how, devices):
        from heat_tpu.frame.reference import join_m1

        comm = _mesh_of(devices)
        rng = np.random.default_rng([30, devices])
        rk = rng.permutation(200)[:_MERGE_RIGHT].astype(np.int32)
        if where == "adjacent":
            rk[21] = rk[20]
        elif where == "first_and_last":  # of the right table, so on a mesh in two shards before the exchange
            rk[-1] = rk[0]
        else:  # beside the pads, which carry that key too
            rk[3] = rk[40] = np.iinfo(np.int32).max
        x, y = _merge_tables(rng, rng.integers(0, 200, _MERGE_ROWS), rk)
        with pytest.raises(ValueError, match="unique keys"):
            join_m1(x, y, on="k", how=how)
        with pytest.raises(ValueError, match="unique keys"):
            _frame_on(x, comm).join(_frame_on(y, comm), on="k", how=how)

    @pytest.mark.parametrize("devices", [1, 4, 8])
    def test_a_left_join_promotes_the_right_columns_as_the_docs_say(self, devices):
        """docs/FRAME.md: a float column of 32 bits or more keeps its type, anything else becomes
        float32; the key and the left frame's columns keep theirs; an inner join changes none."""
        comm = _mesh_of(devices)
        n = 64
        right = {"k": np.arange(n, dtype=np.int32), "i8": np.arange(n, dtype=np.int8), "i32": np.arange(n, dtype=np.int32),
                 "b": np.arange(n) % 2 == 0, "f16": np.arange(n, dtype=np.float16), "f32": np.arange(n, dtype=np.float32)}
        left = {"k": (np.arange(3 * n, dtype=np.int32) * 7) % (n + 9), "u8": np.arange(3 * n, dtype=np.uint8)}
        lf, rf = _frame_on(left, comm), _frame_on(right, comm)
        inner, outer = lf.join(rf, on="k").to_dict(), lf.join(rf, on="k", how="left").to_dict()
        assert {c: str(a.dtype) for c, a in inner.items()} == {
            "k": "int32", "u8": "uint8", "i8": "int8", "i32": "int32", "b": "bool", "f16": "float16", "f32": "float32"}
        assert {c: str(a.dtype) for c, a in outer.items()} == {
            "k": "int32", "u8": "uint8", "i8": "float32", "i32": "float32", "b": "float32", "f16": "float32", "f32": "float32"}
        assert np.isnan(outer["i32"][outer["k"] >= n]).all() and not np.isnan(outer["i32"][outer["k"] < n]).any()
        np.testing.assert_array_equal(outer["i32"][outer["k"] < n], outer["k"][outer["k"] < n])

    @pytest.mark.parametrize("devices", [1, 4, 8])
    @pytest.mark.parametrize("how", ["inner", "left"])
    def test_no_buffer_of_either_frame_is_changed_or_deleted(self, how, devices):
        """Whatever the library donates between its own programs, a caller's column is none of
        it: after the call every buffer of both frames is alive, the same object, and holds
        what it held."""
        comm = _mesh_of(devices)
        x, y = _MERGE_CASES["all_matched"](np.random.default_rng([31, devices]))
        frames = [(_frame_on(x, comm), x), (_frame_on(y, comm), y)]
        held = [[f[c]._raw for c in f.columns] for f, _ in frames]
        for _ in range(2):
            frames[0][0].join(frames[1][0], on="k", how=how).to_dict()
        for (f, table), bufs in zip(frames, held):
            for c, buf in zip(f.columns, bufs):
                assert f[c]._raw is buf and not buf.is_deleted(), c
                np.testing.assert_array_equal(np.asarray(f[c].numpy()), table[c], err_msg=c)


# --- what the merged sort is handed (PR 36): a row is one side's, so a left payload and a right payload
# of one byte width ride in one operand (columns of one type pair first; the rest under the unsigned
# integer of their width), and a row's side is read off where it stood (the sort's second key, no tag).
# Each case is (left payload dtypes, right payload dtypes); the key is an int32. Floats carry NaNs of two
# bit patterns, ``-0.0``, an infinity and a denormal: nothing converts a value, so every column comes
# out bit for bit (a left join's right columns as floats, as the docs say).
_PAYLOAD_CASES = {
    "every_width_pairs_across_types": (("int32", "int32", "bool", "bfloat16"), ("float32", "float32", "int8", "int16")),
    "and_the_other_way_round": (("float32", "int8", "int16", "float32"), ("int32", "bool", "bfloat16", "float32")),
    "more_left_than_right": (("float32", "int32", "float32", "int16"), ("float32",)),
    "more_right_than_left": (("int8",), ("bool", "int8", "float32", "float32")),
    "no_left_payload": ((), ("float32", "int16")),
    "no_right_payload": (("float32", "bool"), ()),
    "widths_that_do_not_pair": (("int8",), ("float32", "float32")),
}
_FLOAT_BITS = np.array([0x7FC00001, 0xFFC12345, 0x80000000, 0x00000001, 0xFF800000], np.uint32)


def _payload_column(rng, dtype: str, n: int) -> np.ndarray:
    import ml_dtypes

    if dtype == "bool":
        return rng.random(n) < 0.5
    if dtype == "float32":
        col = rng.normal(size=n).astype(np.float32)
        col[rng.permutation(n)[: n // 3]] = rng.choice(_FLOAT_BITS, n // 3).view(np.float32)
        return col
    if dtype == "bfloat16":
        col = rng.normal(size=n).astype(ml_dtypes.bfloat16)
        col[rng.permutation(n)[: n // 4]] = -0.0
        return col
    info = np.iinfo(dtype)
    return rng.integers(info.min, info.max, n, dtype=np.int64, endpoint=True).astype(dtype)


def _bits(col: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(col).view(f"uint{8 * col.dtype.itemsize}")


class TestTheSortsOperands:
    @pytest.mark.parametrize("devices", [1, 4, 8])
    @pytest.mark.parametrize("how", ["inner", "left"])
    @pytest.mark.parametrize("case", sorted(_PAYLOAD_CASES))
    def test_both_sides_share_operands_and_no_bit_changes(self, case, how, devices):
        from heat_tpu.frame import SHUFFLE_STATS
        from heat_tpu.frame.reference import join_m1

        comm = _mesh_of(devices)
        rng = np.random.default_rng([36, devices])
        ldt, rdt = _PAYLOAD_CASES[case]
        x = {"k": rng.integers(0, 60, _MERGE_ROWS).astype(np.int32)}
        y = {"k": rng.permutation(80)[:_MERGE_RIGHT].astype(np.int32)}
        x.update({f"l{j}": _payload_column(rng, d, _MERGE_ROWS) for j, d in enumerate(ldt)})
        y.update({f"r{j}": _payload_column(rng, d, _MERGE_RIGHT) for j, d in enumerate(rdt)})
        want = join_m1(x, y, on="k", how=how)
        assert 0 < len(want["k"]) and (how == "inner") == (len(want["k"]) < _MERGE_ROWS)  # some rows match, some do not
        out = _frame_on(x, comm).join(_frame_on(y, comm), on="k", how=how)
        got = out.to_dict()
        assert out.columns == tuple(want)
        for name, col in want.items():
            assert got[name].dtype == col.dtype, name
            np.testing.assert_array_equal(_bits(got[name]), _bits(col), err_msg=name)
        # the key, the row's place, and of each width as many operands as the side with more columns of it
        widths = [[col.dtype.itemsize for name, col in want.items() if name.startswith(side)] for side in "lr"]
        assert SHUFFLE_STATS["join_sort_operands"] == 2 + sum(max(w.count(b) for w in widths) for b in {*widths[0], *widths[1]})


# --- what the co-partitioning does and does not do (PR 30). The merge sorts both sides by key
# itself, so the partition makes no key order; and on a mesh of one device there is nothing to
# bring together: the join program reads the callers' buffers under their own counts.
class TestCoPartitioning:
    @pytest.mark.parametrize("mode", ["range", "hash"])
    @pytest.mark.parametrize("devices", [1, 4])
    def test_what_a_call_dispatches(self, devices, mode):
        """One device: two fetches (the duplicate flag, the counts), no bucket move, no election
        or partition program for that mesh, no row shuffle counted. A mesh: both sides shuffled,
        one bucket move an operand a side, the partition's fetch a side on top."""
        from heat_tpu.analysis.sanitizer import COMPILE_STATS
        from heat_tpu.frame import SHUFFLE_STATS, _shuffle

        comm = _mesh_of(devices)
        x, y = _MERGE_CASES["all_matched"](np.random.default_rng([32, devices]))
        left, right = _frame_on(x, comm), _frame_on(y, comm)
        counted = lambda: (MOVE_STATS["bucket_moves"], COMPILE_STATS["host_syncs"], SHUFFLE_STATS["row_shuffles"], SHUFFLE_STATS["joins"])
        left.join(right, on="k", mode=mode)  # cold
        before = counted()
        left.join(right, on="k", mode=mode)
        moves, syncs, shuffles, joins = (a - b for a, b in zip(counted(), before))
        # every program this file's tests have built for such a mesh, by kind
        built = {key[0] for key in _shuffle._PROGRAMS if key[-1] == comm.mesh}
        assert joins == 1 and "join" in built
        if devices == 1:
            assert (moves, syncs, shuffles) == (0, 2, 0)
            assert not built & {"elect", "part"}
        else:
            assert (moves, syncs, shuffles) == (len(x) + len(y), 4, 2)
            assert built >= ({"part", "elect"} if mode == "range" else {"part"})

    @pytest.mark.parametrize("mode", ["range", "hash"])
    def test_the_partition_program_sorts_once_and_moves_nothing_through_an_index(self, mode):
        """Lowered for four devices, not compiled: the one sort is the stable partition by
        destination (the parent sorted by key first), and no gather or scatter stands in it."""
        import re

        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        from heat_tpu.core.communication import SPLIT_AXIS
        from heat_tpu.frame import _shuffle

        comm = _mesh_of(4)
        rows, rep = NamedSharding(comm.mesh, PartitionSpec(SPLIT_AXIS)), NamedSharding(comm.mesh, PartitionSpec())
        payloads = ("int32", "float32")
        fn = _shuffle._partition_executable((4 * 64,), jnp.dtype("int32"), payloads, 4, mode, comm)
        text = fn.lower(
            jax.ShapeDtypeStruct((4 * 64,), jnp.int32, sharding=rows),
            jax.ShapeDtypeStruct((4,), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((3,), jnp.int32, sharding=rep),
            *[jax.ShapeDtypeStruct((4 * 64,), jnp.dtype(d), sharding=rows) for d in payloads],
        ).as_text()
        assert len(re.findall(r"stablehlo\.sort", text)) == 1, text
        assert re.findall(r"stablehlo\.(?:dynamic_)?gather|stablehlo\.scatter", text) == []

    @pytest.mark.parametrize("how", ["inner", "left"])
    def test_both_sides_ragged_on_one_device(self, how):
        """Two filters' results on one device: the counts go with the buffers, and the rows each
        filter dropped stay behind the kept ones as the pads' content, keys that would match."""
        from heat_tpu.frame._shuffle import shard_counts
        from heat_tpu.frame.reference import join_m1

        comm = _mesh_of(1)
        rng = np.random.default_rng([33, how == "left"])
        x, y = _merge_tables(rng, rng.integers(0, 60, _MERGE_ROWS), rng.permutation(60)[:_MERGE_RIGHT])
        keep = {"left": rng.random(_MERGE_ROWS) < 0.7, "right": rng.random(_MERGE_RIGHT) < 0.7}
        left = _frame_on(x, comm).filter(ht.array(keep["left"], split=0, comm=comm))
        right = _frame_on(y, comm).filter(ht.array(keep["right"], split=0, comm=comm))
        assert shard_counts(left["k"]) == (int(keep["left"].sum()),) and left["k"]._raw.shape == (_MERGE_ROWS,)
        assert shard_counts(right["k"]) == (int(keep["right"].sum()),) and right["k"]._raw.shape == (_MERGE_RIGHT,)
        xk, yk = ({c: a[keep[s]] for c, a in t.items()} for s, t in (("left", x), ("right", y)))
        dropped = y["k"][~keep["right"]]
        assert np.isin(xk["k"], dropped).any() and np.isin(xk["k"], yk["k"]).any()
        buffers = lambda: [f[c]._raw for f in (left, right) for c in f.columns]
        held = buffers()
        want = join_m1(xk, yk, on="k", how=how)
        for _ in range(2):
            _assert_columns_equal(left.join(right, on="k", how=how).to_dict(), want)
        # the join read the filters' own buffers and left them alone (reading a ragged column
        # out rebalances it, so the buffers are looked at first)
        assert all(b is now and not b.is_deleted() for b, now in zip(held, buffers()))
        for f, t in ((left, xk), (right, yk)):
            _assert_columns_equal(f.to_dict(), t)


# --- both sides long (PR 31): h2o.ai db-benchmark's join question 5, ``big inner on int``
# (x join big on id3), cut to 6 000 rows a side: seven columns each, six names in both, ``big``'s
# id3 unique. id3's values come from a shuffled pool of 1.1 n as the source draws them: the first
# 0.9 n on both sides, the next 0.1 n in x only, the last 0.1 n in ``big`` only; x draws its id3
# with replacement from its n values, ``big`` holds each of its own once. On a mesh the whole-row
# shuffle moves both tables and a run of equal keys is a right row and a left row or two.
_BIG_ROWS = 6_000
_BIG_OUT = ("id3", "id1", "id2", "id4", "id5", "id6", "v1", "id1_r", "id2_r", "id4_r", "id5_r", "id6_r", "v2")


def _h2o_big_tables(rng, n: int = _BIG_ROWS):
    (x1, b1), (x2, b2), (x3, b3) = (_h2o_pool(rng, m) for m in (10, n // 1000, n))
    x = {"id1": rng.choice(x1, n), "id2": rng.choice(x2, n), "id3": rng.choice(x3, n)}
    x.update(id4=x["id1"].copy(), id5=x["id2"].copy(), id6=x["id3"].copy(), v1=rng.uniform(0, 100, n).astype(np.float32))
    big = {"id1": rng.choice(b1, n), "id2": rng.choice(b2, n), "id3": rng.permutation(b3)}
    big.update(id4=big["id1"].copy(), id5=big["id2"].copy(), id6=big["id3"].copy(), v2=rng.uniform(0, 100, n).astype(np.float32))
    return x, big


class TestBothSidesLong:
    @pytest.mark.parametrize("mode", ["range", "hash"])
    @pytest.mark.parametrize("devices", [4, 8])
    @pytest.mark.parametrize("how", ["inner", "left"])
    def test_every_column_equals_the_references(self, how, devices, mode):
        from heat_tpu.frame import SHUFFLE_STATS
        from heat_tpu.frame.reference import join_m1

        comm = _mesh_of(devices)
        x, big = _h2o_big_tables(np.random.default_rng([31, devices]))
        matched = np.isin(x["id3"], big["id3"]).mean()
        assert 0.88 < matched < 0.92 and np.unique(big["id3"]).size == _BIG_ROWS
        want = join_m1(x, big, on="id3", how=how)
        out = _frame_on(x, comm).join(_frame_on(big, comm), on="id3", how=how, mode=mode)
        got = out.to_dict()
        assert out.columns == tuple(want) == _BIG_OUT
        if mode == "hash":
            # each shard in the promised order, the shards not: their rows as multisets are the
            # reference's, and one stable sort by key of the shards end to end is its order
            order = np.argsort(got["id3"], kind="stable")
            got = {c: a[order] for c, a in got.items()}
        else:  # the range election left no destination more than a quarter over the mean
            assert SHUFFLE_STATS["bucket_skew"] <= 1.25
        _assert_columns_equal(got, want)  # range: row for row in the reference's order over the whole mesh

    @pytest.mark.parametrize("devices", [4, 8])
    @pytest.mark.parametrize("side", ["left", "right"])
    def test_an_empty_shard_on_either_long_side(self, side, devices):
        """The second shard of one side holds no row, as a filter leaves it; what it dropped stays
        behind as the pads' content, keys that would match."""
        from heat_tpu.frame._shuffle import shard_counts
        from heat_tpu.frame.reference import join_m1

        comm = _mesh_of(devices)
        tables = dict(zip(("left", "right"), _h2o_big_tables(np.random.default_rng([32, devices]))))
        block = -(-_BIG_ROWS // devices)
        keep = {name: np.ones(_BIG_ROWS, bool) for name in tables}
        keep[side][block : 2 * block] = False
        frames = {name: _frame_on(t, comm).filter(ht.array(keep[name], split=0, comm=comm)) for name, t in tables.items()}
        assert shard_counts(frames[side]["id3"])[1] == 0
        want = join_m1(*({c: a[keep[name]] for c, a in tables[name].items()} for name in ("left", "right")), on="id3")
        assert 0 < want["id3"].size < _BIG_ROWS
        _assert_columns_equal(frames["left"].join(frames["right"], on="id3").to_dict(), want)

    # --- a receive block's length does not follow the keys (PR 31): every run of the question
    # at 1e8 rows compiled ``frame_join`` anew, because the fullest bucket sized the moved blocks.
    @pytest.mark.parametrize("fullest, rows", [
        (0, 1), (1, 1), (127, 127), (128, 128), (129, 130), (1_001, 1_008), (2**20, 2**20), (2**20 + 1, 2**20 + 2**14),
        (25_005_753, 25_165_824), (25_017_842, 25_165_824),  # both ends of what six seeds of the question elect
    ])
    def test_a_receive_block_is_one_of_64_lengths_an_octave(self, fullest, rows):
        from heat_tpu.frame._shuffle import _receive_rows

        assert _receive_rows(fullest) == rows
        assert max(fullest, 1) <= rows <= max(fullest + fullest // 64, 1)
        top = 1 << max(fullest, 1).bit_length()  # the octave above: 64 lengths in it, and its end
        assert len({_receive_rows(n) for n in range(top, 2 * top, max(top // 512, 1))}) <= 65

    def test_another_table_as_long_finds_the_join_compiled(self):
        """Two pairs of tables drawn apart: other keys, another fullest bucket, one ``frame_join``."""
        from heat_tpu.frame import SHUFFLE_STATS, _shuffle
        from heat_tpu.frame.reference import join_m1

        comm = _mesh_of(4)
        joins = lambda: [key[1:3] for key in _shuffle._PROGRAMS if key[0] == "join"]
        fullest = []
        for draw in (1, 5):
            x, big = _h2o_big_tables(np.random.default_rng([33, draw]))
            before = joins()
            _assert_columns_equal(_frame_on(x, comm).join(_frame_on(big, comm), on="id3").to_dict(), join_m1(x, big, on="id3"))
            fullest.append(round(SHUFFLE_STATS["bucket_skew"] * _BIG_ROWS / 4))  # of ``big``, the side moved last
        assert fullest[0] != fullest[1] and joins() == before, (fullest, before, joins())
