"""The Lasso cell's ``check`` at the rehearsal's size, on the CPU: a plain coordinate descent rounded
to float32 passes it, as does the program's own fit; each fault a coordinate descent can hide fails
it, and so does the same descent with its products' factors rounded to bfloat16 (the precision
below the configuration's float32). Beside it
the loop metric's arithmetic on made-up events: of nested loops the innermost alone is counted."""
import os
import types

import numpy as np
import pytest
from conftest import ROOT

from harness import manifest, xplane

CELL = "lasso-fit1-eurad-1e7"
FAULTS = ["a column skipped", "a residual not updated", "a regularised intercept", "lam for lam n", "bf16 products"]


def descent(x, y, lam, fault=None):
    """One sweep of coordinate descent on float32 ``x`` and ``y``, summed in float64 (so that nothing
    but the fault moves the result, whatever the rows), with ``fault`` built in. ``"bf16 products"``
    rounds both factors of every product of a column's dot to bfloat16: one bf16 pass of a matrix unit."""
    import ml_dtypes

    n, m = x.shape
    bf16 = (lambda a: a.astype(np.float32).astype(ml_dtypes.bfloat16)) if fault == "bf16 products" else (lambda a: a)
    theta, r = np.zeros(m), y.astype(np.float64)
    for j in range(m):
        if fault == "a column skipped" and j == 4:  # one with a true coefficient
            continue
        xj = x[:, j].astype(np.float64)
        rho = np.dot(bf16(xj).astype(np.float64), bf16(r).astype(np.float64))
        soft = np.sign(rho) * max(abs(rho) - (lam if fault == "lam for lam n" else lam * n), 0.0)
        theta[j] = (rho if j == 0 and fault != "a regularised intercept" else soft) / np.dot(xj, xj)
        if fault != "a residual not updated":
            r = r - xj * theta[j]
    return theta


@pytest.fixture(scope="module")
def rehearsal():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    import heat_tpu as ht

    cell = manifest.load_cell(CELL)
    config = dict(cell.config, sizes={**cell.config["sizes"], **cell.config["rehearse_sizes"]})
    driver = manifest.load_module("drivers", config["driver"])
    comm = ht.MeshCommunication(devices=jax.devices()[:1])
    state = driver.build(config, 2**31 + 33, comm)
    return types.SimpleNamespace(driver=driver, state=state, x=state["x"].numpy(), y=state["y"].numpy(),
                                 lam=config["sizes"]["lam"], ht=ht, comm=comm)


def _verdict(rehearsal, theta, n_iter=1):
    result = {"theta": rehearsal.ht.array(theta.reshape(-1, 1), comm=rehearsal.comm), "n_iter": n_iter}
    return rehearsal.driver.check(rehearsal.state, result)


def test_a_plain_descent_rounded_to_float32_passes(rehearsal):
    theta = descent(rehearsal.x, rehearsal.y, rehearsal.lam).astype(np.float32)
    verdict = _verdict(rehearsal, theta)
    assert verdict["ok"] is True and verdict["err_over_bf16_error"] < verdict["limit"] / 3, verdict
    assert _verdict(rehearsal, theta, n_iter=2)["ok"] is False


@pytest.mark.parametrize("fault", FAULTS)
def test_the_check_fails_a_seeded_fault(rehearsal, fault):
    verdict = _verdict(rehearsal, descent(rehearsal.x, rehearsal.y, rehearsal.lam, fault).astype(np.float32))
    # a fault is gross; bf16 products sit where the limit is drawn to part them from float32 (1.9 to 3.5 here)
    assert verdict["ok"] is False and verdict["err_over_bf16_error"] > (1.5 if fault == "bf16 products" else 100) * verdict["limit"], verdict
    assert verdict["objective_after"] < verdict["objective_at_zero"]  # by the limit on theta, not by the objective


def test_the_programs_own_fit_passes(rehearsal):
    verdict = rehearsal.driver.check(rehearsal.state, rehearsal.driver.call(rehearsal.state))
    assert verdict["ok"] is True and verdict["n_iter"] == 1, verdict


def test_only_the_innermost_loops_of_the_fit_are_counted():
    reader = manifest.load_module("layer_metrics", "cd_loop_ms.call")
    ops = [("%while.23 = () while()", 1.0, 9.0), ("%fusion.5 = f32[] fusion()", 1.0, 2.0), ("%while.24 = () while()", 2.0, 8.0),
           ("%fusion.1 = f32[] fusion()", 3.0, 4.0), ("%while.23 = () while()", 11.0, 19.0), ("%while.24 = () while()", 12.0, 18.0),
           ("%while.7 = () while()", 21.0, 22.0)]  # the last a loop of another program
    modules = [("jit__cd_fit", 0.5, 9.5), ("jit__cd_fit", 10.5, 19.5), ("jit_other", 20.5, 22.5)]
    trace = xplane.Trace(calls=[(0.0, 10.0), (10.0, 23.0)], host=[],
                         devices=[{"name": "/device:TPU:0", "modules": modules, "ops": ops,
                                   "busy": xplane.union((s, e) for _, s, e in modules)}])
    assert reader.read(types.SimpleNamespace(trace=trace)) == pytest.approx(6.0e3)  # 12 s of column loops over 2 calls, in ms
    assert trace.op_s_per_call(["while"]) == pytest.approx(14.5)  # every event summed counts the inner loop twice
    fit = manifest.load_module("layer_metrics", "cd_fit_ms.call")
    assert fit.read(types.SimpleNamespace(trace=trace)) == pytest.approx(9.0e3)
    no_fit = xplane.Trace(calls=trace.calls, host=[], devices=[dict(trace.devices[0], modules=modules[2:])])
    assert reader.read(types.SimpleNamespace(trace=no_fit)) is None and fit.read(types.SimpleNamespace(trace=no_fit)) is None
    assert reader.read(types.SimpleNamespace(trace=None)) is None and fit.read(types.SimpleNamespace(trace=None)) is None
