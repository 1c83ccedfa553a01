"""``ht.regression.Lasso.fit`` against the plain reference (``heat_tpu/regression/reference.py``'s
``lasso_cd``: the published coordinate descent in NumPy float64), on meshes of 1, 4 and 8 devices.

The data are the chip benchmark's (``benchmarks/chip/configs/lasso-eurad-1e7.json``) cut to 203
rows, a count that no mesh here divides: a column of ones, standard-normal regressors, a true
coefficient on every fourth column, a little noise.

The tolerance. The estimator computes in float32, the reference in float64 on the same float32
data. A coefficient is ``rho / ||x_j||^2`` with ``rho`` a sum of ``n`` float32 products of size
about ``|x| |r|`` <= 4 x 10: at ``n`` = 203 its rounding is at most ``n u`` = 203 x 2^-24 = 1.2e-5 of
``sum |x_j r| / ||x_j||^2`` <= 10, and a sweep passes each coefficient's error on to the next
through the residual with a factor ``|x_j . x_k| / ||x_j||^2`` < 0.3, so five sweeps stay under
5e-4. The CPU's float32 dot is a full float32 product (no bf16 pass). ``ATOL`` = 5e-4 is a
hundred times under what any of the faults the cases name would move (>= 0.05).
"""
import contextlib

import jax
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.regression.reference import lasso_cd

ATOL = 5e-4
ROWS, COLUMNS, LAM = 203, 12, 0.1


def _table(seed: int, scale=None):
    """(X, y) float32: column 0 ones, the rest N(0, 1) (times ``scale`` a column, if given)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(ROWS, COLUMNS)).astype(np.float32)
    X[:, 0] = 1.0
    if scale is not None:
        X[:, 1:] *= np.asarray(scale, np.float32)
    theta = np.where(np.arange(COLUMNS) % 4 == 0, rng.normal(size=COLUMNS) * 2.0, 0.0)
    y = X.astype(np.float64) @ theta + 0.1 * rng.normal(size=ROWS)
    return X, y.astype(np.float32)


def _fit(X, y, devices: int, lam: float, sweeps: int):
    comm = ht.MeshCommunication(devices=jax.devices()[:devices])
    est = ht.regression.Lasso(lam=lam, max_iter=sweeps, tol=0.0)
    est.fit(ht.array(X, split=0, comm=comm), ht.array(y, split=0, comm=comm))
    return est


@pytest.mark.parametrize("sweeps", [1, 5])
@pytest.mark.parametrize("devices", [1, 4, 8])
def test_fit_equals_the_reference(devices, sweeps):
    assert ROWS % devices or devices == 1
    X, y = _table(33)
    est = _fit(X, y, devices, LAM, sweeps)
    want = lasso_cd(X, y, LAM, sweeps)
    got = est.theta.numpy()
    assert got.shape == (COLUMNS, 1) and got.dtype == np.float32
    np.testing.assert_allclose(got.ravel(), want, rtol=0, atol=ATOL)
    assert est.n_iter == sweeps  # tol=0.0: ``diff >= tol`` always holds, so max_iter sweeps run
    assert (want[1:] == 0).any() and (want[1:] != 0).any()  # the threshold bites, and not everywhere


@pytest.mark.parametrize("devices", [1, 4, 8])
def test_an_unstandardised_column_is_divided_by_its_norm(devices):
    """Columns of scale 0.05 to 30: without the division by ``||x_j||^2`` (upstream's step, which
    presumes standardised columns) their coefficients are off by the square of the scale."""
    scale = np.geomspace(0.05, 30.0, COLUMNS - 1)
    X, y = _table(34, scale=scale)
    want = lasso_cd(X, y, LAM, 5)
    got = _fit(X, y, devices, LAM, 5).theta.numpy().ravel()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=ATOL)
    undivided = want[1:] * (X[:, 1:].astype(np.float64) ** 2).sum(0) / ROWS
    assert np.abs(undivided - want[1:]).max() > 0.05


@pytest.mark.parametrize("devices", [1, 4, 8])
def test_a_large_lam_zeroes_every_regressor_and_leaves_the_intercept(devices):
    X, y = _table(35)
    est = _fit(X, y, devices, 1e3, 5)
    got, want = est.theta.numpy().ravel(), lasso_cd(X, y, 1e3, 5)
    assert (got[1:] == 0).all() and (want[1:] == 0).all()
    assert abs(got[0] - y.astype(np.float64).mean()) < ATOL and abs(got[0] - want[0]) < ATOL
    assert abs(got[0]) > 0.05  # a regularised intercept would have been thresholded to 0
    assert est.coef_.shape == (COLUMNS - 1, 1) and float(est.intercept_.numpy().ravel()[0]) == got[0]


@pytest.mark.parametrize("max_iter", [1, 2, 7])
def test_n_iter_equals_max_iter_under_tol_zero(max_iter):
    """Also where nothing moves any more: at ``lam`` = 1e3 the second sweep changes no coefficient."""
    X, y = _table(36)
    assert _fit(X, y, 4, 1e3, max_iter).n_iter == max_iter
    assert _fit(X, y, 4, LAM, max_iter).n_iter == max_iter


def test_the_reference_knows_nothing_of_the_code_under_test():
    import ast
    import inspect

    from heat_tpu.regression import reference

    tree = ast.parse(inspect.getsource(reference))
    imported = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names}
    imported |= {(n.module or ".") for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    assert imported == {"__future__", "numpy"}
    with pytest.raises(ValueError):
        lasso_cd(np.ones((3, 2)), np.ones(4), 0.1, 1)


def test_the_reference_descends_and_reaches_least_squares_at_lam_zero():
    """Independent of the estimator: at ``lam`` = 0 coordinate descent is Gauss-Seidel on the normal
    equations, and converges to ``numpy.linalg.lstsq``'s answer."""
    X, y = _table(37)
    want = np.linalg.lstsq(X.astype(np.float64), y.astype(np.float64), rcond=None)[0]
    np.testing.assert_allclose(lasso_cd(X, y, 0.0, 200), want, rtol=0, atol=1e-9)


# --- the two sweeps (PR 34). ``_cd_sweep`` picks one by the table's shape alone: the Gram matrix's
# space for a tall table (x read twice a program, a sweep over theta and an (m, m) matrix), the
# running residual's otherwise. Both are cyclic coordinate descent, coordinate by coordinate.

WIDE_ROWS, WIDE_COLUMNS = 12, 40


def _wide_table(seed: int):
    """(X, y) float32 with more columns than rows: the table the Gram path must not take."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(WIDE_ROWS, WIDE_COLUMNS)).astype(np.float32)
    X[:, 0] = 1.0
    return X, rng.normal(size=WIDE_ROWS).astype(np.float32)


def _on_mesh(X, y, devices: int):
    """The arrays ``Lasso.fit`` hands its program: the logical x and y of arrays split over ``devices``."""
    comm = ht.MeshCommunication(devices=jax.devices()[:devices])
    return ht.array(X, split=0, comm=comm)._logical(), ht.array(y, split=0, comm=comm)._logical()


def _run_sweeps(path: str, Xa, ya, theta, lam: float, sweeps: int):
    """``sweeps`` sweeps of the named path from ``theta``, one jitted program, whatever the shape."""
    from heat_tpu.regression import lasso

    @jax.jit
    def run(X, y, th):
        sweep = lasso._SWEEPS[path](X, y, np.float32(lam))
        for _ in range(sweeps):
            th = sweep(th)
        return th

    return np.asarray(run(Xa, ya, np.asarray(theta, np.float32)))


@contextlib.contextmanager
def _paths_seen():
    """The ``lasso.path`` events of the fits made inside the block."""
    from heat_tpu.core import _hooks

    seen = []

    def record(event, ctx):
        if event == "lasso.path":
            seen.append(dict(ctx))

    _hooks.add_observer(record)
    try:
        yield seen
    finally:
        _hooks.remove_observer(record)


@pytest.mark.parametrize("path", ["gram", "residual"])
@pytest.mark.parametrize("sweeps", [1, 5])
@pytest.mark.parametrize("devices", [1, 4, 8])
def test_each_sweep_equals_the_reference(devices, sweeps, path):
    X, y = _table(33)
    got = _run_sweeps(path, *_on_mesh(X, y, devices), np.zeros(COLUMNS), LAM, sweeps)
    np.testing.assert_allclose(got, lasso_cd(X, y, LAM, sweeps), rtol=0, atol=ATOL)


def test_the_rule_is_the_shape_alone():
    from heat_tpu.regression import lasso

    assert lasso._cd_path(ROWS, COLUMNS) == "gram" and lasso._cd_path(10_000_000, 108) == "gram"
    assert lasso._cd_path(WIDE_ROWS, WIDE_COLUMNS) == "residual"
    assert lasso._cd_path(40, 40) == "gram" and lasso._cd_path(39, 40) == "residual"
    widest = lasso._GRAM_MAX_COLUMNS
    assert lasso._cd_path(10 * widest, widest) == "gram" and lasso._cd_path(10 * widest, widest + 1) == "residual"


@pytest.mark.parametrize("sweeps", [1, 5])
@pytest.mark.parametrize("devices", [1, 4])
def test_a_wide_table_takes_the_residual_path_and_equals_the_reference(devices, sweeps):
    X, y = _wide_table(38)
    with _paths_seen() as seen:
        est = _fit(X, y, devices, LAM, sweeps)
    assert seen == [{"path": "residual", "rows": WIDE_ROWS, "columns": WIDE_COLUMNS}]
    np.testing.assert_allclose(est.theta.numpy().ravel(), lasso_cd(X, y, LAM, sweeps), rtol=0, atol=ATOL)
    assert est.n_iter == sweeps


@pytest.mark.parametrize("supervised", [False, True])
def test_an_observer_sees_the_path_once_a_fit(supervised):
    X, y = _table(39)
    comm = ht.MeshCommunication(devices=jax.devices()[:4])
    xa, ya = ht.array(X, split=0, comm=comm), ht.array(y, split=0, comm=comm)
    est = ht.regression.Lasso(lam=LAM, max_iter=3, tol=0.0)
    with _paths_seen() as seen:
        if supervised:
            est.fit(xa, ya, supervisor=ht.resilience.Supervisor(), block_iters=2)
        else:
            est.fit(xa, ya)
    assert seen == [{"path": "gram", "rows": ROWS, "columns": COLUMNS}]
    assert est.n_iter == 3
    np.testing.assert_allclose(est.theta.numpy().ravel(), lasso_cd(X, y, LAM, 3), rtol=0, atol=ATOL)
    est.fit(xa, ya)  # and with no observer the event costs a falsy test
    assert len(seen) == 1


@pytest.mark.parametrize("table", ["tall", "wide"])
@pytest.mark.parametrize("devices", [1, 4])
def test_a_warm_start_continues_the_one_program_fit(devices, table):
    """theta != 0 handed to ``_cd_fit``, and two chained ``_cd_block`` chunks: no residual is carried
    from program to program, so either path must rebuild what it needs from theta alone."""
    from heat_tpu.regression import lasso

    X, y = _table(40) if table == "tall" else _wide_table(40)
    assert lasso._cd_path(*X.shape) == ("gram" if table == "tall" else "residual")
    Xa, ya = _on_mesh(X, y, devices)
    lam, tol, zero = np.float32(LAM), np.float32(0.0), np.zeros(X.shape[1], np.float32)
    whole, n5 = lasso._cd_fit(Xa, ya, zero, lam, tol, np.int32(5))
    first, n2 = lasso._cd_fit(Xa, ya, zero, lam, tol, np.int32(2))
    resumed, n3 = lasso._cd_fit(Xa, ya, first, lam, tol, np.int32(3))
    assert (int(n5), int(n2), int(n3)) == (5, 2, 3) and np.abs(np.asarray(first)).max() > 0.05
    np.testing.assert_allclose(np.asarray(resumed), np.asarray(whole), rtol=0, atol=1e-6)
    th, done, diff = lasso._cd_block(Xa, ya, zero, lam, tol, np.int32(2), np.float32(np.inf))
    th, more, diff = lasso._cd_block(Xa, ya, th, lam, tol, np.int32(3), diff)
    assert (int(done), int(more)) == (2, 3)
    np.testing.assert_allclose(np.asarray(th), np.asarray(whole), rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(whole), lasso_cd(X, y, LAM, 5), rtol=0, atol=ATOL)


@pytest.mark.parametrize("devices", [1, 4, 8])
def test_uncentred_regressors_equal_the_reference(devices):
    """Each regressor + 3.0: the Gram matrix's off-diagonals are then sums of size 9 n, where those
    of centred columns stay near sqrt(n); the sweep subtracts such sums from one another."""
    X, y = _table(41)
    X[:, 1:] += np.float32(3.0)
    est = _fit(X, y, devices, LAM, 5)
    np.testing.assert_allclose(est.theta.numpy().ravel(), lasso_cd(X, y, LAM, 5), rtol=0, atol=ATOL)
    for path in ("gram", "residual"):
        got = _run_sweeps(path, *_on_mesh(X, y, devices), np.zeros(COLUMNS), LAM, 5)
        np.testing.assert_allclose(got, lasso_cd(X, y, LAM, 5), rtol=0, atol=ATOL)


def _subjaxprs(eqn):
    """The jaxprs an equation calls (a loop's body and condition, a call's body)."""
    found = [p.jaxpr if hasattr(p, "jaxpr") else p for p in eqn.params.values()]
    return [j for j in found if hasattr(j, "eqns")]


def _loops(jaxpr):
    """Every loop equation of ``jaxpr``, nested ones included: ``while``, and ``scan``, which is
    what a ``fori_loop`` of a static trip count traces to."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("while", "scan"):
            found.append(eqn)
        for sub in _subjaxprs(eqn):
            found += _loops(sub)
    return found


def _shapes(jaxpr):
    """The shape of every value of ``jaxpr`` and of what it calls."""
    shapes = [v.aval.shape for v in [*jaxpr.invars, *jaxpr.constvars] if hasattr(v.aval, "shape")]
    for eqn in jaxpr.eqns:
        shapes += [v.aval.shape for v in eqn.outvars if hasattr(v.aval, "shape")]
        for sub in _subjaxprs(eqn):
            shapes += _shapes(sub)
    return shapes


@pytest.mark.parametrize("program", ["_cd_fit", "_cd_block"])
def test_the_gram_programs_loops_hold_no_value_of_n_rows(program):
    """The sweeps' loop and the columns' loop in it touch theta, q, the norms and the (m, m) matrix:
    nothing with a dimension of n rows. On the residual path the same walk finds x in the loops."""
    from heat_tpu.regression import lasso

    def loops_shapes(rows, columns):
        f32 = lambda *s: jax.ShapeDtypeStruct(s, np.float32)  # noqa: E731
        tail = (f32(), f32(), jax.ShapeDtypeStruct((), np.int32)) + ((f32(),) if program == "_cd_block" else ())
        jaxpr = jax.make_jaxpr(getattr(lasso, program))(f32(rows, columns), f32(rows), f32(columns), *tail).jaxpr
        loops = _loops(jaxpr)
        assert len(loops) == 2  # the sweeps' and the columns'
        return [s for eqn in loops for sub in _subjaxprs(eqn) for s in _shapes(sub)]

    assert lasso._cd_path(ROWS, COLUMNS) == "gram"
    assert not [s for s in loops_shapes(ROWS, COLUMNS) if ROWS in s]
    assert [s for s in loops_shapes(WIDE_ROWS, WIDE_COLUMNS) if WIDE_ROWS in s]


def test_on_a_mesh_the_gram_programs_loops_hold_no_collective():
    """x is split by rows: G, q and the norms are each one local pass and one all-reduce a program,
    where the residual's sweep all-reduces a scalar every coordinate."""
    from heat_tpu.regression import lasso

    from ._chip_helpers import _collectives, _reached_from_loops

    def compiled_text(X, y):
        Xa, ya = _on_mesh(X, y, 4)
        assert not Xa.sharding.is_fully_replicated
        args = (Xa, ya, np.zeros(X.shape[1], np.float32), np.float32(LAM), np.float32(0.0), np.int32(2))
        return lasso._cd_fit.lower(*args).compile().as_text()

    X, y = _table(42)
    text = compiled_text(X[:200], y[:200])  # a count the mesh divides: the logical x of 203 rows is replicated
    assert _collectives(text.splitlines())  # the Gram's terms are summed over the mesh ...
    assert not _collectives(_reached_from_loops(text))  # ... before the loops, not in them
    assert _collectives(_reached_from_loops(compiled_text(*_wide_table(42))))  # the walk sees one where there is one
