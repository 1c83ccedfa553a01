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
