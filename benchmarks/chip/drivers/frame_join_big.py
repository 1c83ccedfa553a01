"""``Frame.join(big, on="id3")``: h2o.ai db-benchmark's join question 5, ``big inner on int``.

The benchmark's tables ``J1_<rows>_NA_0_0`` (no NAs, unsorted) as its generator draws them, both
resident on the mesh: x with id1, id2, id3, id4, id5, id6, v1 and ``big``, as long as x and its id3
unique, with id1, id2, id3, id4, id5, id6, v2. A key column's values come from a shuffled pool of
1.1 n: the first 0.9 n on both sides, the next 0.1 n in x only, the last 0.1 n on the right only,
so about nine rows in ten of x find their match, and a run of equal keys is one row of ``big`` and a
row or two of x. The source's factors id4..id6 are the int32 codes of id1..id3 here, and v1, v2
are f32. At 1e8 rows the two tables and the result (13 columns of 9e7 rows) are 10.3 GB before a
working copy: no single chip holds the question, so every column is born split over the mesh.
"""
from __future__ import annotations

import numpy as np

from harness.data import on_mesh, prng_key

ON = "id3"
X_COLUMNS = ("id1", "id2", "id3", "id4", "id5", "id6", "v1")
BIG_COLUMNS = ("id1", "id2", "id3", "id4", "id5", "id6", "v2")
OUT_COLUMNS = ("id3", "id1", "id2", "id4", "id5", "id6", "v1", "id1_r", "id2_r", "id4_r", "id5_r", "id6_r", "v2")
CODES = {"id4": "id1", "id5": "id2", "id6": "id3"}  # a factor column holds its integer column's codes


def _pool(key, n: int):
    """The source's ``split_xlr(n)``: 1..1.1n shuffled, as (values of x, values of the right table)."""
    import jax
    import jax.numpy as jnp

    shared = n * 9 // 10
    values = jax.random.permutation(key, n + (n - shared)).astype(jnp.int32) + 1
    return values[:n], jnp.concatenate([values[:shared], values[n:]])


def recipes(config: dict) -> dict:
    """``table.column`` -> (rows, which pool's key or None, function of (pool key,) draw key): what
    each drawn column is made by, jitted with its result born split (``build``), nothing run here."""
    import jax
    import jax.numpy as jnp

    rows, big_rows = config["sizes"]["rows"], config["sizes"]["big_rows"]
    pools = {"id1": max(rows // 1_000_000, 10), "id2": max(rows // 1_000, 10), "id3": rows}

    def uniform(n):
        return lambda kd: jax.random.uniform(kd, (n,), jnp.float32, 0.0, 100.0)

    def drawn(side, name, n):  # with replacement from that side's values of the column's pool
        def draw(kp, kd):
            values = _pool(kp, pools[name])[side]
            return values[jax.random.randint(kd, (n,), 0, values.shape[0], jnp.int32)]
        return draw

    def each_once(kp, kd):  # big's key: its side of the pool, shuffled
        return jax.random.permutation(kd, _pool(kp, pools[ON])[1])

    return {
        "x.id1": (rows, "id1", drawn(0, "id1", rows)), "x.id2": (rows, "id2", drawn(0, "id2", rows)),
        "x.id3": (rows, "id3", drawn(0, "id3", rows)), "x.v1": (rows, None, uniform(rows)),
        "big.id1": (big_rows, "id1", drawn(1, "id1", big_rows)), "big.id2": (big_rows, "id2", drawn(1, "id2", big_rows)),
        "big.id3": (big_rows, "id3", each_once), "big.v2": (big_rows, None, uniform(big_rows)),
    }


def build(config: dict, seed: int, comm) -> dict:
    import jax

    import heat_tpu as ht

    if config["sizes"]["big_rows"] != config["sizes"]["rows"]:
        raise SystemExit("question 5's big is as long as x: its keys are its side of x's own pool")
    made = recipes(config)
    pool_keys = dict(zip(("id1", "id2", "id3"), jax.random.split(prng_key(seed), 3)))
    draw_keys = jax.random.split(jax.random.fold_in(prng_key(seed), 5), len(made))
    col = {}
    for kd, (name, (n, pool, fn)) in zip(draw_keys, made.items()):
        args = (kd,) if pool is None else (pool_keys[pool], kd)
        col[name] = on_mesh(comm, (n,), fn, *args)
    for table in ("x", "big"):  # a factor column is a buffer of its own, as a deployment holds it
        for factor, of in CODES.items():
            col[f"{table}.{factor}"] = on_mesh(comm, col[f"{table}.{of}"].shape, lambda a: a + 0, col[f"{table}.{of}"])
    frame = lambda table, names: ht.frame.Frame({c: ht.array(col[f"{table}.{c}"], split=0) for c in names})
    return {"x": frame("x", X_COLUMNS), "big": frame("big", BIG_COLUMNS)}


def call(state: dict) -> dict:
    out = state["x"].join(state["big"], on=ON)
    return {name: out[name] for name in out.columns}


def reference_rows(x_key: np.ndarray, big_key: np.ndarray) -> tuple:
    """The inner m:1 join in plain NumPy, by this file alone, as two row lists: x's rows in
    ascending key, x's order kept within a key (one stable argsort of the key), and beside each
    the row of ``big`` that holds its key, found through a table of where in ``big`` each key
    stands; rows without a match left out. (rows of x, rows of big, big's keys all distinct)."""
    where = np.full(int(max(x_key.max(), big_key.max())) + 1, -1, np.int64)
    where[big_key] = np.arange(big_key.size)
    distinct = int(np.count_nonzero(where >= 0)) == big_key.size
    order = np.argsort(x_key, kind="stable")
    at = where[x_key[order]]
    return order[at >= 0], at[at >= 0], distinct


def check(state: dict, result: dict) -> dict:
    """Nothing is computed by a join, so nothing is tolerated: names and order of the columns,
    the number of rows, then every column equal to the reference's, bit for bit. One column of
    the result is on the host at a time, beside the two tables and the reference's two row lists."""
    if tuple(result) != OUT_COLUMNS:
        return {"ok": False, "columns": list(result)}
    x = {name: state["x"][name].numpy() for name in X_COLUMNS}
    big = {name: state["big"][name].numpy() for name in BIG_COLUMNS}
    x_rows, big_rows, distinct = reference_rows(x[ON], big[ON])
    if not distinct:
        return {"ok": False, "duplicate_keys_in_big": True}
    wanted = {ON: (x, ON, x_rows)}
    wanted.update({name: (x, name, x_rows) for name in X_COLUMNS if name != ON})
    wanted.update({name + "_r" if name in x else name: (big, name, big_rows) for name in BIG_COLUMNS if name != ON})
    rows_out, differs = int(result[ON].shape[0]), None
    if rows_out == x_rows.size:
        for name in OUT_COLUMNS:
            table, source, at = wanted[name]
            got, want = result[name].numpy(), table[source][at]
            if got.dtype != want.dtype or not np.array_equal(got, want):
                differs = name
                break
    return {"ok": rows_out == x_rows.size and differs is None, "rows_out": rows_out, "rows_wanted": int(x_rows.size),
            "match_share": rows_out / x[ON].size, "first_column_that_differs": differs}


def work(config: dict) -> dict:
    """Least work of one call: read the seven 4-byte columns of both tables once, write thirteen
    columns of the matched rows (nine in ten of x's, the source's share); one comparison a row."""
    rows, big_rows = config["sizes"]["rows"], config["sizes"]["big_rows"]
    return {"flops": rows, "bytes": 4 * (7 * rows + 7 * big_rows + 13 * (rows * 9 // 10)), "kernels": {}}
