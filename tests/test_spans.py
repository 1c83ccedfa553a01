"""The program's spans, read back from a profiler trace on the CPU mesh.

One traced slice makes every public call and device read below once (two
rounds of the three calls the chip benchmark times, so ``call`` can be seen
to rise), and each test reads the host plane with ``ProfileData``: the tree
of ``ht.call:*`` / ``ht.fetch:*`` / ``ht.exchange:*`` by time and by the
``call`` attribute, against ``COMPILE_STATS["host_syncs"]`` and ``MOVE_STATS``.
"""
import glob
import os

import jax
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.core import _hooks
from heat_tpu.parallel.flatmove import MOVE_STATS

ROUNDS = 2


@_hooks.public_call("test.inner")
def _inner(x):
    return x + 1


@_hooks.public_call("test.outer")
def _outer(x):
    return _inner(x) * 2


def _operands():
    rng = np.random.default_rng(24)
    four = ht.MeshCommunication(devices=jax.devices()[:4])
    n = 64
    frame = ht.frame.Frame({
        "k": ht.array(rng.integers(0, 9, n).astype(np.int32), split=0, comm=four),
        **{v: ht.array(rng.normal(size=n).astype(np.float32), split=0, comm=four) for v in ("v1", "v2", "v3")},
    })
    right = ht.frame.Frame({
        "k": ht.array(np.arange(9, dtype=np.int32), split=0, comm=four),
        "w": ht.array(np.arange(9, dtype=np.float32), split=0, comm=four),
    })
    x = ht.array(rng.normal(size=(96, 4)).astype(np.float32), split=0)
    return {"x": x, "frame": frame, "right": right, "mask": frame["v1"] > 0,
            "ints": ht.array(rng.integers(0, 5, 40).astype(np.int32), split=0)}


def _the_three_calls(ops):
    ht.cluster.KMeans(n_clusters=3, max_iter=4, random_state=0).fit(ops["x"])
    ht.spatial.cdist(ops["x"], ops["x"], quadratic_expansion=True)
    ops["frame"].groupby("k").sum()  # the shorthand reaches agg: one ht.call, not two


def _the_other_reads(ops):
    x, frame = ops["x"], ops["frame"]
    x.numpy()
    x[0, 0].item()
    float(x[1, 1])
    ht.unique(ops["ints"])
    frame.to_dict()
    frame.filter(ops["mask"])
    frame.join(ops["right"], on="k")
    frame.groupby("k").quantile(0.5, k=16, levels=2)
    ht.cluster.KMedians(n_clusters=2, max_iter=3, random_state=0).fit(x)
    ht.cluster.KMedoids(n_clusters=2, max_iter=3, random_state=0).fit(x)
    ht.regression.Lasso(lam=0.1, max_iter=3).fit(x[:, :3], x[:, 3:])
    ht.regression.Lasso(lam=0.1, max_iter=4, tol=0.0).fit(
        x[:, :3], x[:, 3:], supervisor=ht.resilience.Supervisor(), block_iters=2)
    ht.cluster.KMeans(n_clusters=2, init="random", max_iter=4, tol=0.0, random_state=0).fit(
        x, supervisor=ht.resilience.Supervisor(), block_iters=2)  # two chunks of two, then inertia
    return _outer(3)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    from jax.profiler import ProfileData

    ops = _operands()
    _the_three_calls(ops)  # compile outside the trace
    _the_other_reads(ops)
    directory = str(tmp_path_factory.mktemp("spans"))
    before = {"syncs": ht.COMPILE_STATS["host_syncs"], "moves": dict(MOVE_STATS)}
    with ht.utils.profiling.trace(directory):
        nested = _the_other_reads(ops)
        for _ in range(ROUNDS):
            _the_three_calls(ops)
    counted = {"syncs": ht.COMPILE_STATS["host_syncs"] - before["syncs"],
               "bucket_moves": MOVE_STATS["bucket_moves"] - before["moves"]["bucket_moves"]}
    (path,) = glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("ht."):
                        spans.append({"name": ev.name, "start": ev.start_ns, "end": ev.start_ns + ev.duration_ns,
                                      **dict(ev.stats)})
    spans.sort(key=lambda s: (s["start"], -s["end"]))
    return {"spans": spans, "counted": counted, "nested": nested}


def _named(traced, name):
    return [s for s in traced["spans"] if s["name"] == name]


def _inside(traced, outer):
    return [s for s in traced["spans"] if s is not outer and outer["start"] <= s["start"] and s["end"] <= outer["end"]]


def _outermost_calls(traced):
    calls = [s for s in traced["spans"] if s["name"].startswith("ht.call:")]
    return [c for c in calls if not any(c in _inside(traced, o) for o in calls)]


def test_one_outermost_call_span_a_public_call_with_increasing_call(traced):
    outer = _outermost_calls(traced)
    names = [c["name"] for c in outer]
    assert names == ["ht.call:Frame.join", "ht.call:Lasso.fit", "ht.call:Lasso.fit", "ht.call:KMeans.fit", "ht.call:test.outer"] + ["ht.call:KMeans.fit", "ht.call:cdist", "ht.call:groupby.agg"] * ROUNDS
    numbers = [c["call"] for c in outer]
    assert numbers == list(range(numbers[0], numbers[0] + len(outer)))  # test.inner, nested, drew none


def test_a_nested_public_call_opens_a_child_with_its_parents_number(traced):
    (outer,), (inner,) = _named(traced, "ht.call:test.outer"), _named(traced, "ht.call:test.inner")
    assert inner in _inside(traced, outer) and inner["call"] == outer["call"]
    assert outer in _outermost_calls(traced) and inner not in _outermost_calls(traced)
    assert traced["nested"] == 8  # and the wrapped functions' results came through


def test_the_counter_hears_every_fetch_span(traced):
    fetches = [s for s in traced["spans"] if s["name"].startswith("ht.fetch:")]
    assert traced["counted"]["syncs"] == len(fetches) > 0


def test_four_bucket_moves_inside_the_groupby_on_four_devices(traced):
    for agg in _named(traced, "ht.call:groupby.agg"):
        moves = [s for s in _inside(traced, agg) if s["name"].startswith("ht.exchange:")]
        assert [m["name"] for m in moves] == ["ht.exchange:bucket_move"] * 4  # the keys and three sums
        assert all(m["p"] == 4 and m["call"] == agg["call"] for m in moves)
    exchanges = [s for s in traced["spans"] if s["name"] == "ht.exchange:bucket_move"]
    assert len(exchanges) == traced["counted"]["bucket_moves"]  # span and MOVE_STATS sit at one boundary


# (fetch site, the public call it must lie inside or None, how many the slice makes)
SITES = [
    ("kmeans.inertia", "ht.call:KMeans.fit", ROUNDS + 1),
    ("kcluster.shift", "ht.call:KMeans.fit", 2),
    ("kcluster.iters", "ht.call:KMeans.fit", 2),
    ("lasso.diff", "ht.call:Lasso.fit", 2),
    ("lasso.sweeps", "ht.call:Lasso.fit", 2),
    ("kmeans.n_iter", "ht.call:KMeans.fit", ROUNDS),
    ("groupby.bucket_matrix", "ht.call:groupby.agg", ROUNDS),
    ("groupby.group_counts", "ht.call:groupby.agg", ROUNDS),
    ("groupby.finalize", None, 3),
    ("shuffle.bucket_matrix", None, 2),
    ("shuffle.join_dup", None, 1),
    ("shuffle.join_counts", None, 1),
    ("shuffle.compact_counts", None, 1),
    ("frame.to_dict", None, 1),
    ("kmedians.n_iter", None, 1),
    ("kmedoids.n_iter", None, 1),
    ("lasso.n_iter", "ht.call:Lasso.fit", 1),
    ("dndarray.gather", None, None),
    ("dndarray.item", None, None),
    ("dndarray.scalar", None, None),
    ("dscan.found", None, None),
]


@pytest.mark.parametrize("site,parent,count", SITES, ids=[s[0] for s in SITES])
def test_fetch_site(traced, site, parent, count):
    found = _named(traced, "ht.fetch:" + site)
    assert found if count is None else len(found) == count
    if parent is not None:
        for f in found:
            (call,) = [c for c in _named(traced, parent) if f in _inside(traced, c)]
            assert f["call"] == call["call"]


def test_lasso_fit_opens_its_span_once_a_fit(traced):
    plain, supervised = _named(traced, "ht.call:Lasso.fit")  # one a fit, in the order _the_other_reads makes them
    assert plain in _outermost_calls(traced) and supervised in _outermost_calls(traced)
    assert [s["name"] for s in _inside(traced, plain)] == ["ht.fetch:lasso.n_iter"]  # the fit's one fetch
    assert {s["name"] for s in _inside(traced, supervised)} == {"ht.fetch:lasso.diff", "ht.fetch:lasso.sweeps"}
    assert supervised["call"] == plain["call"] + 1


def test_no_fetch_inside_cdist(traced):
    for call in _named(traced, "ht.call:cdist"):
        assert _inside(traced, call) == []


def test_without_a_session_results_pass_through_and_nothing_is_kept(traced):
    with _hooks.span("ht.fetch:nobody.listens", n=1):
        pass
    assert _outer(1) == 4 and _hooks.fetch(jax.numpy.arange(3), "test.site").tolist() == [0, 1, 2]
    assert ht.utils.profiling.annotate is _hooks.span
    # the warm-up round ran the same calls before the session opened: none of its spans is in the trace
    assert len(_named(traced, "ht.call:test.outer")) == 1 and len(_named(traced, "ht.call:cdist")) == ROUNDS


def test_one_span_mechanism_under_heat_tpu():
    root = os.path.dirname(os.path.abspath(ht.__file__))
    holders = []
    for base, _, names in os.walk(root):
        for name in names:
            if name.endswith(".py"):
                with open(os.path.join(base, name), encoding="utf-8") as fh:
                    if "TraceAnnotation" in fh.read():
                        holders.append(os.path.relpath(os.path.join(base, name), root))
    assert holders == [os.path.join("core", "_hooks.py")]
