"""``Frame.join(medium, on="id2")``: h2o.ai db-benchmark's join question 2, ``medium inner on int``.

The benchmark's tables ``J1_<rows>_NA_0_0`` (no NAs, unsorted) as its generator draws them, both
resident on the device: x with id1, id2, id3, id4, id5, id6, v1 and ``medium`` (rows/1000 rows, its
id2 unique) with id1, id2, id4, id5, v2. A key column's values come from a shuffled pool of 1.1 n:
the first 0.9 n on both sides, the next 0.1 n in x only, the last 0.1 n in ``medium`` only, so about
nine rows in ten of x find their match. The source's factors id4..id6 are the int32 codes of
id1..id3 here, and v1, v2 are f32.
"""
from __future__ import annotations

import functools

import numpy as np

from harness.data import on_mesh, prng_key

ON = "id2"
X_COLUMNS = ("id1", "id2", "id3", "id4", "id5", "id6", "v1")
MEDIUM_COLUMNS = ("id1", "id2", "id4", "id5", "v2")
OUT_COLUMNS = ("id2", "id1", "id3", "id4", "id5", "id6", "v1", "id1_r", "id4_r", "id5_r", "v2")


def _pool(key, n: int):
    """The source's ``split_xlr(n)``: 1..1.1n shuffled, as (values of x, values of the right table)."""
    import jax
    import jax.numpy as jnp

    shared = n * 9 // 10
    values = jax.random.permutation(key, n + (n - shared)).astype(jnp.int32) + 1
    return values[:n], jnp.concatenate([values[:shared], values[n:]])


def build(config: dict, seed: int, comm) -> dict:
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    rows, m = config["sizes"]["rows"], config["sizes"]["medium_rows"]
    n1 = max(rows // 1_000_000, 10)
    pool1, pool2, *k = jax.random.split(prng_key(seed), 9)
    uniform = lambda key, n: jax.random.uniform(key, (n,), jnp.float32, 0.0, 100.0)
    draw = lambda key, values, n: values[jax.random.randint(key, (n,), 0, values.shape[0], jnp.int32)]
    made = {
        "x.id1": on_mesh(comm, (rows,), lambda kp, kd: draw(kd, _pool(kp, n1)[0], rows), pool1, k[0]),
        "x.id2": on_mesh(comm, (rows,), lambda kp, kd: draw(kd, _pool(kp, m)[0], rows), pool2, k[1]),
        "x.id3": on_mesh(comm, (rows,), lambda kd: jax.random.randint(kd, (rows,), 1, rows + 1, jnp.int32), k[2]),
        "x.v1": on_mesh(comm, (rows,), lambda kd: uniform(kd, rows), k[3]),
        "m.id1": on_mesh(comm, (m,), lambda kp, kd: draw(kd, _pool(kp, n1)[1], m), pool1, k[4]),
        "m.id2": on_mesh(comm, (m,), lambda kp, kd: jax.random.permutation(kd, _pool(kp, m)[1]), pool2, k[5]),
        "m.v2": on_mesh(comm, (m,), lambda kd: uniform(kd, m), k[6]),
    }
    # a factor column is a buffer of its own, as a deployment holds it, with its integer column's codes
    codes = lambda name: ht.array(on_mesh(comm, made[name].shape, lambda a: a + 0, made[name]), split=0)
    col = {name: ht.array(a, split=0) for name, a in made.items()}
    x = {"id1": col["x.id1"], "id2": col["x.id2"], "id3": col["x.id3"], "id4": codes("x.id1"),
         "id5": codes("x.id2"), "id6": codes("x.id3"), "v1": col["x.v1"]}
    medium = {"id1": col["m.id1"], "id2": col["m.id2"], "id4": codes("m.id1"), "id5": codes("m.id2"),
              "v2": col["m.v2"]}
    return {"x": ht.frame.Frame(x), "medium": ht.frame.Frame(medium)}


@functools.lru_cache(maxsize=None)
def _public_join():
    """``Frame.join`` inside the span ``ht.call:Frame.join``. Where the program has made it a public
    call, that is the method as it stands and the span is the program's. A program from before that
    (``Frame.join`` undecorated: no ``__wrapped__``) gets the same span from the program's own
    ``public_call`` here, around the same call, so that the metrics the benchmark reads from
    ``ht.call:*`` spans in every cell (``call_self_ms.call``, ``fetch_ms.call``, ``exchanges.call``,
    ``exchange_ms.call``) have a call to read on both sides of a comparison."""
    import heat_tpu as ht
    from heat_tpu.core import _hooks

    join = ht.frame.Frame.join
    return join if hasattr(join, "__wrapped__") else _hooks.public_call("Frame.join")(join)


def call(state: dict) -> dict:
    out = _public_join()(state["x"], state["medium"], on=ON)
    return {name: out[name] for name in out.columns}


def reference(x: dict, medium: dict) -> dict:
    """The inner m:1 join in plain NumPy, by this file alone: x's rows in ascending key, x's order
    kept within a key (one stable argsort of the key), each with its match's columns read through a
    table of where in ``medium`` each key stands; rows without a match left out."""
    where = np.full(int(max(x[ON].max(), medium[ON].max())) + 1, -1, np.int64)
    where[medium[ON]] = np.arange(medium[ON].size)
    order = np.argsort(x[ON], kind="stable")
    at = where[x[ON][order]]
    order, at = order[at >= 0], at[at >= 0]
    out = {ON: x[ON][order]}
    out.update({name: x[name][order] for name in X_COLUMNS if name != ON})
    out.update({name + "_r" if name in x else name: medium[name][at] for name in MEDIUM_COLUMNS if name != ON})
    return out


def check(state: dict, result: dict) -> dict:
    """Nothing is computed by a join, so nothing is tolerated: names and order of the columns,
    the number of rows, then every column equal to the reference's, bit for bit."""
    if tuple(result) != OUT_COLUMNS:
        return {"ok": False, "columns": list(result)}
    x = {name: state["x"][name].numpy() for name in X_COLUMNS}
    medium = {name: state["medium"][name].numpy() for name in MEDIUM_COLUMNS}
    if np.unique(medium[ON]).size != medium[ON].size:
        return {"ok": False, "duplicate_keys_in_medium": True}
    want = reference(x, medium)
    rows_out, differs = int(result[ON].shape[0]), None
    for name in OUT_COLUMNS:
        got = result[name].numpy()
        if got.dtype != want[name].dtype or not np.array_equal(got, want[name]):
            differs = name
            break
    return {"ok": rows_out == want[ON].size and differs is None, "rows_out": rows_out, "rows_wanted": int(want[ON].size),
            "match_share": rows_out / x[ON].size, "first_column_that_differs": differs}


def work(config: dict) -> dict:
    """Least work of one call: read x's seven 4-byte columns and ``medium``'s five once, write eleven
    columns of the matched rows (nine in ten of x's, the source's share); one comparison a row."""
    rows, m = config["sizes"]["rows"], config["sizes"]["medium_rows"]
    return {"flops": rows, "bytes": 4 * (7 * rows + 5 * m + 11 * (rows * 9 // 10)), "kernels": {}}
