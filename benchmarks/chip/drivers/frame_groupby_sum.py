"""``Frame.groupby("id6").agg(sum of v1, v2, v3)``: h2o.ai db-benchmark's groupby question 5.

The benchmark's table ``G1_<rows>_<K>_0_0`` (no NAs, unsorted) as its generator draws it, all nine
columns resident on the device as a deployment holds them: id1, id2, id4, id5 uniform over 1..K,
id3 and id6 over 1..rows/K, v1 over 1..5, v2 over 1..15, v3 uniform on (0, 100). The source's
id1..id3 are strings ("id042"); here they are their int32 codes, and v3 is f32. Question 5,
``sum v1 v2 v3 by id6``, reads four of the nine.
"""
from __future__ import annotations

import numpy as np

from harness.data import on_mesh, prng_key

KEY, VALUES = "id6", ("v1", "v2", "v3")


def build(config: dict, seed: int, comm) -> dict:
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    rows, K = config["sizes"]["rows"], config["sizes"]["K"]
    groups = rows // K
    ints = lambda hi: (lambda k: jax.random.randint(k, (rows,), 1, hi + 1, jnp.int32))
    draw = {"id1": ints(K), "id2": ints(K), "id3": ints(groups), "id4": ints(K), "id5": ints(K), "id6": ints(groups),
            "v1": ints(5), "v2": ints(15), "v3": lambda k: jax.random.uniform(k, (rows,), jnp.float32, 0.0, 100.0)}
    keys = jax.random.split(prng_key(seed), len(draw))
    cols = {name: ht.array(on_mesh(comm, (rows,), draw[name], k), split=0) for name, k in zip(draw, keys)}
    return {"frame": ht.frame.Frame(cols), "groups": groups}


def call(state: dict) -> dict:
    out = state["frame"].groupby(KEY).agg({name: "sum" for name in VALUES})
    return {name: out[name] for name in out.columns}


def check(state: dict, result: dict) -> dict:
    frame, groups = state["frame"], state["groups"]
    if tuple(result) != (KEY, *VALUES):
        return {"ok": False, "columns": list(result)}
    kh = frame[KEY].numpy()
    count = np.bincount(kh, minlength=groups + 1)
    present = np.flatnonzero(count)
    got = {name: col.numpy() for name, col in result.items()}
    verdict = {"groups": int(present.size), "out_dtypes": {n: str(a.dtype) for n, a in got.items()}}
    # keys exact and in order; the integer sums exact (bincount adds in f64: exact far past these sums)
    exact = got[KEY].shape == present.shape and bool((got[KEY] == present).all())
    for name in ("v1", "v2"):
        want = np.bincount(kh, weights=frame[name].numpy(), minlength=groups + 1)[present]
        exact = exact and got[name].shape == want.shape and bool((got[name] == want.astype(np.int64)).all())
    # v3: an f32 sum of c positive terms, in any order, is within c·2^-24 of the exact sum,
    # relatively; c is the fullest group's count. Twice that, for the reference's own rounding to f32.
    want = np.bincount(kh, weights=frame["v3"].numpy().astype(np.float64), minlength=groups + 1)[present]
    rtol = 2.0 * count.max() * 2.0**-24
    rel = np.abs(got["v3"].astype(np.float64) - want) / want if exact else np.array([np.inf])
    verdict.update(ok=bool(exact and (rel <= rtol).all()), keys_and_integer_sums_exact=bool(exact),
                   v3_max_rel_err=float(rel.max()), v3_rtol=float(rtol))
    return verdict


def work(config: dict) -> dict:
    """Least work of one call: read the four 4-byte columns it needs once, write four a group; one add a value."""
    rows = config["sizes"]["rows"]
    return {"flops": 3 * rows, "bytes": 16 * rows + 16 * (rows // config["sizes"]["K"]), "kernels": {}}
