"""What ``test_frame.py`` (the container, the groupby, the engine) and
``test_frame_join.py`` (the join) share: one file was split in two by verb
so that neither is a long job for the one xdist worker ``--dist loadfile``
gives a file."""
from __future__ import annotations

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.frame import Frame

from . import _mh_helpers as mh

ROWS = 211


@pytest.fixture(scope="module", autouse=True)
def _release_executables():
    """Drop this module's compiled programs when it finishes.

    The oracle sweep compiles one shuffle program per (agg, mode,
    cardinality, dtype) combination — an executable population no other
    module approaches. Left resident, that population pushes a LATER
    module's XLA compile (test_ml_wave2's Lanczos program) into a
    segfault inside backend_compile on the single-process CPU suite;
    releasing the caches here keeps the per-module executable footprint
    flat and the crash away. Reproducer: the alphabetical tier-1 prefix
    through test_ml_wave2.py crashes with this fixture removed and
    passes with it (the module alone, or alone + test_ml_wave2, passes
    either way)."""
    yield
    import jax

    from heat_tpu.frame import _shuffle
    from heat_tpu.stream import groupby as _sgb

    _shuffle._PROGRAMS.clear()
    _sgb._PROGRAMS.clear()
    jax.clear_caches()


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(7)


def _mesh_of(devices: int):
    """A communicator over ``devices`` devices, spanning every process; skips where there is none."""
    import jax

    if devices > len(jax.devices()) or (jax.process_count() > 1 and devices % jax.process_count()):
        pytest.skip(f"no mesh of {devices} devices here")
    return ht.MeshCommunication(devices=mh.submesh(devices))


def _sorted_dict(frame: Frame, key: str):
    """Materialize a result frame as numpy, rows sorted by the key column
    (hash mode only co-locates keys; order is a range-mode extra)."""
    d = frame.to_dict()
    order = np.argsort(d[key], kind="stable")
    return {n: v[order] for n, v in d.items()}
