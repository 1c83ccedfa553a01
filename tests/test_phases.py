"""The ``ht.phase:<name>`` scopes inside the compiled programs (``core/_hooks.py::phase``).

A scope is a ``jax.named_scope`` entered while a program is traced: every operation traced inside
carries it in its metadata (the compiled text's ``op_name``, a profiler trace's ``tf_op``), and a
call of the cached program pays nothing for it. Here each frame program and both paths of
``_cd_fit`` are compiled at small shapes for meshes of 1 and 4 devices and their sorts, loops and
matmul are looked up by scope; then the public calls that run them are made twice, the second
traces and compiles nothing, and the results equal the references the suite has
(``frame/reference.py``, ``regression/reference.py``, NumPy's ``bincount``).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

import heat_tpu as ht
from heat_tpu.analysis.sanitizer import sanitizer
from heat_tpu.core import _hooks
from heat_tpu.core.communication import SPLIT_AXIS
from heat_tpu.frame import Frame, _shuffle
from heat_tpu.frame.reference import join_m1
from heat_tpu.regression import lasso
from heat_tpu.regression.reference import lasso_cd

from ._frame_helpers import _release_executables  # noqa: F401  (autouse: this module's programs go when it ends)

SCOPE = re.compile(r"(?:^|/)ht\.phase:([^/:]+)")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([\w\-]+)\(")
B = 64  # rows a shard


def _comm(devices: int):
    if devices > len(jax.devices()):
        pytest.skip(f"no mesh of {devices} devices here")
    return ht.MeshCommunication(devices=jax.devices()[:devices])


def _phases_by_opcode(compiled_text: str) -> dict:
    """opcode -> the innermost phase (None: no scope) of each instruction of it, sorted."""
    found = {}
    for line in compiled_text.splitlines():
        opcode = INSTRUCTION.match(line)
        if opcode:
            name = re.search(r'op_name="([^"]*)"', line)
            scopes = SCOPE.findall(name.group(1)) if name else []
            found.setdefault(opcode.group(1), []).append(scopes[-1] if scopes else None)
    return {k: sorted(v, key=str) for k, v in found.items()}


def _lowered(program: str, p: int):
    comm = _comm(p)
    rows, rep = NamedSharding(comm.mesh, PartitionSpec(SPLIT_AXIS)), NamedSharding(comm.mesh, PartitionSpec())
    block = lambda dtype, b=B: jax.ShapeDtypeStruct((p * b,), jnp.dtype(dtype), sharding=rows)
    counts = jax.ShapeDtypeStruct((p,), jnp.int32, sharding=rep)
    if program == "frame_join":
        fn = _shuffle._join_executable((p * B,), (p * 32,), jnp.dtype("int32"), ("float32", "int32"), ("float32",), "inner", p, comm)
        return fn.lower(block("int32"), counts, block("float32"), block("int32"), block("int32", 32), counts, block("float32", 32))
    if program == "frame_plan":
        fn = _shuffle._plan_executable((p * B,), jnp.dtype("int32"), ("float32",), (("sum", 0, "float32"), ("count", 0, "int32")), p, "range", comm)
        return fn.lower(block("int32"), counts, block("float32"))
    if program == "frame_partition":
        fn = _shuffle._partition_executable((p * B,), jnp.dtype("int32"), ("float32",), p, "range", comm)
        return fn.lower(block("int32"), counts, jax.ShapeDtypeStruct((max(p - 1, 1),), jnp.int32, sharding=rep), block("float32"))
    if program == "frame_elect":
        fn = _shuffle._elect_executable(((p * B,), (p * 32,)), jnp.dtype("int32"), p, comm)
        return fn.lower(block("int32"), block("int32", 32), counts, counts)
    n, m = {"cd_fit_gram": (p * B, 5), "cd_fit_residual": (p * 2, 16)}[program]  # more columns than rows: the residual path
    assert lasso._cd_path(n, m) == program[len("cd_fit_"):]
    x = jax.ShapeDtypeStruct((n, m), jnp.float32, sharding=NamedSharding(comm.mesh, PartitionSpec(SPLIT_AXIS, None)))
    y = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rows)
    return lasso._cd_fit.lower(x, y, jax.ShapeDtypeStruct((m,), jnp.float32, sharding=rep), 0.1, 0.0, 3)


# what each program's sorts and loops must stand under, the election's own apart (on a mesh: the
# samples' small sort, their all-gather and, in the plan, the search among the run ends)
_WANTED = {
    "frame_join": {"sort": ["sort"], "while": {"scan": 1, "compact": 3}, "elects": False},  # d's loop, then 4 columns two at a time
    "frame_plan": {"sort": ["sort"], "while": {"scan": 1, "compact": 3}, "elects": True},  # the key and two totals
    "frame_partition": {"sort": ["sort"], "while": {}, "elects": False},
    "frame_elect": {"sort": ["sort", "sort"], "while": {}, "elects": True},  # a key column a side, whole
}


# one device elects nothing (no splitter stands between one destination): that program is empty there
@pytest.mark.parametrize("program,devices", [(name, p) for name in sorted(_WANTED) for p in ((4, 8) if name == "frame_elect" else (1, 4))])
def test_a_frame_programs_sorts_scan_and_compaction_stand_under_their_phases(program, devices):
    found = _phases_by_opcode(_lowered(program, devices).compile().as_text())
    want = _WANTED[program]
    sorts, loops = found.get("sort", []), [name for name in found.get("while", []) if name != "elect"]
    assert [name for name in sorts if name != "elect"] == want["sort"], found
    assert {name: loops.count(name) for name in set(loops)} == want["while"], found
    elects = devices > 1 and want["elects"]
    assert ("elect" in sorts) == elects and ("elect" in found.get("all-gather", [])) == elects, found


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("path", ["gram", "residual"])
def test_the_fits_reads_of_x_and_its_loops_stand_under_their_phases(path, devices):
    text = _lowered("cd_fit_" + path, devices).compile().as_text()
    found = _phases_by_opcode(text)
    assert found["while"] == ["sweep", "sweep"], found  # the sweeps' loop and the columns'
    scoped = {(op, phase) for op, phases in found.items() for phase in phases}
    assert ("reduce", "moments") in scoped, found
    matmuls = [phase for op in ("dot", "convolution") for phase in found.get(op, [])]
    if path == "gram":
        assert "gram" in matmuls and set(matmuls) <= {"gram"}, found  # the one matmul over x
    else:
        assert "gram" not in {phase for _, phase in scoped}, found  # no Gram matrix on this path
        assert set(matmuls) <= {"sweep"}, found  # ``X @ theta`` and the columns' dots: a sweep's own


def test_nested_phases_the_innermost_names_the_operation_and_a_call_pays_nothing():
    @jax.jit
    def f(x):
        with _hooks.phase("outer"):
            y = jnp.sin(x)
            with ht.utils.profiling.phase("inner"):
                return y + jnp.cos(x)

    text = f.lower(jnp.ones(8)).as_text(debug_info=True)
    assert "ht.phase:outer/sin" in text and "ht.phase:outer/ht.phase:inner/cos" in text
    assert SCOPE.findall("jit(f)/ht.phase:outer/while/body/ht.phase:inner/cos:")[-1] == "inner"
    f(jnp.ones(8))
    with sanitizer("a warm call of a scoped program") as region:
        f(jnp.ones(8))
    assert (region.traces, region.compiles) == (0, 0), region.stats()


# ---- the public calls that run them: a second call traces and compiles nothing, results as the references'
def _join(comm, rng):
    left = {"k": rng.integers(0, 90, 300).astype(np.int32), "v": rng.normal(size=300).astype(np.float32)}
    right = {"k": rng.permutation(120)[:48].astype(np.int32), "w": rng.normal(size=48).astype(np.float32)}
    frames = [Frame({c: ht.array(a, split=0, comm=comm) for c, a in t.items()}) for t in (left, right)]
    want = join_m1(left, right, on="k")
    return lambda: frames[0].join(frames[1], on="k").to_dict(), want


def _groupby(comm, rng):
    k, v = rng.integers(0, 23, 300).astype(np.int32), rng.integers(-5, 6, 300).astype(np.float32)
    frame = Frame({"k": ht.array(k, split=0, comm=comm), "v": ht.array(v, split=0, comm=comm)})
    present = np.flatnonzero(np.bincount(k, minlength=23))
    want = {"k": present.astype(np.int32), "v": np.bincount(k, weights=v, minlength=23)[present].astype(np.float32)}
    return lambda: frame.groupby("k").agg({"v": "sum"}).to_dict(), want  # small integers: the float32 sums are exact


def _fit(shape):
    def make(comm, rng):
        n, m = shape
        x = rng.normal(size=(n, m)).astype(np.float32)
        x[:, 0] = 1.0
        y = (x[:, :3].sum(axis=1) + 0.1 * rng.normal(size=n)).astype(np.float32)
        hx, hy = ht.array(x, split=0, comm=comm), ht.array(y, split=0, comm=comm)
        want = {"theta": lasso_cd(x, y, 0.1, 2).astype(np.float32)}
        return lambda: {"theta": ht.regression.Lasso(lam=0.1, max_iter=2, tol=0.0).fit(hx, hy).theta.numpy().ravel()}, want

    return make


_CALLS = {"Frame.join": _join, "groupby.agg": _groupby, "Lasso.fit-gram": _fit((203, 6)), "Lasso.fit-residual": _fit((9, 12))}


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("call", sorted(_CALLS))
def test_a_second_call_traces_and_compiles_nothing_and_equals_the_reference(call, devices):
    run, want = _CALLS[call](_comm(devices), np.random.default_rng([35, devices]))
    first = run()  # cold: the scopes are entered here
    with sanitizer("a warm call of scoped programs") as region:
        again = run()
    assert (region.traces, region.compiles) == (0, 0), region.stats()
    assert set(again) >= set(want)
    for name, column in want.items():
        np.testing.assert_array_equal(again[name], first[name], err_msg=name)
        if call.startswith("Lasso"):
            np.testing.assert_allclose(again[name], column, rtol=0, atol=5e-4, err_msg=name)  # test_lasso_reference.py's
        else:
            assert again[name].dtype == column.dtype, name
            np.testing.assert_array_equal(again[name], column, err_msg=name)
