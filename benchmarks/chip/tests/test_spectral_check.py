"""The spectral cell's ``check`` at the rehearsal's size, on the CPU: the program's own fit passes it, and
so do its labels renamed and its embedding's columns with their signs flipped; the reference run one
precision lower (a one-pass bf16 cross term in the similarity, or L and the vector in bf16 at every
matvec) fails at least one of its limits; an estimator that keeps no Ritz values fails it."""
import os
import types

import numpy as np
import pytest

from harness import manifest

CELL = "spectral-fit-susy40k-lanczos300"


@pytest.fixture(scope="module")
def rehearsal():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    import heat_tpu as ht

    cell = manifest.load_cell(CELL)
    config = dict(cell.config, sizes={**cell.config["sizes"], **cell.config["rehearse_sizes"]})
    driver = manifest.load_module("drivers", config["driver"])
    comm = ht.MeshCommunication(devices=jax.devices()[:1])
    ht.use_comm(comm)
    state = driver.build(config, 2**31 + 40, comm)
    result = driver.call(state)
    want = driver.reference(state["xa"], state["gamma"], state["n_lanczos"], state["clusters"])
    return types.SimpleNamespace(driver=driver, state=state, result=result, want=want, ht=ht, comm=comm)


def test_the_programs_fit_passes(rehearsal):
    verdict = rehearsal.driver.check(rehearsal.state, rehearsal.result)
    assert verdict["ok"], verdict
    assert verdict["gap"] >= rehearsal.driver.GAP_MIN and verdict["lanczos_steps"] == rehearsal.state["n_lanczos"]


def test_renamed_labels_and_flipped_columns_pass(rehearsal):
    ht, r = rehearsal.ht, rehearsal.result
    flipped = {"labels": ht.array(1 - r["labels"].numpy(), split=0, comm=rehearsal.comm), "ritz": r["ritz"],
               "embedding": ht.array(-r["embedding"].numpy(), split=0, comm=rehearsal.comm)}
    assert rehearsal.driver.check(rehearsal.state, flipped)["ok"]


@pytest.mark.parametrize("stage", ["similarity", "matvec"])
def test_one_precision_lower_fails_a_limit(rehearsal, stage):
    d, s = rehearsal.driver, rehearsal.state
    low = d.reference(s["xa"], s["gamma"], s["n_lanczos"], s["clusters"], bf16=stage)
    errs = d.errors(low["values"], low["embedding"], rehearsal.want, s["clusters"])
    assert errs["ritz_max_abs_err"] > d.RITZ_LIMIT or errs["embedding_max_rel_err"] > d.EMBEDDING_LIMIT, errs


def test_an_estimator_without_ritz_values_fails(rehearsal):
    verdict = rehearsal.driver.check(rehearsal.state, dict(rehearsal.result, ritz=None))
    assert not verdict["ok"]


def test_the_reference_alone_gives_the_classes(rehearsal):
    labels = rehearsal.want["labels"]
    assert rehearsal.driver.same_partition(labels, rehearsal.result["labels"].numpy())
    assert np.bincount(labels).min() > 0.3 * len(labels)
