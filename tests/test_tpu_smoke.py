"""Real-hardware smoke tests — skipped unless the default backend is a TPU.

The CPU-mesh suite (conftest forces ``jax_platforms=cpu``) can never
exercise the actual accelerator. The proof that the main path runs on
the chip is ``chip_smoke.py`` at the repo root (one process, real sizes,
every kernel dispatched as ``.pallas``); this file is the small pytest
counterpart for the numerics that differ on TPU silicon: bf16 MXU matmul
error bounds, the f32 'highest' escape hatch, kmeans fit correctness,
sort/percentile, and IO round-trip on device. Run it WITHOUT the conftest
override::

    python -m pytest tests/test_tpu_smoke.py -q -p no:cacheprovider \
        --override-ini= -c /dev/null

or simply ``python tests/test_tpu_smoke.py`` which self-hosts.

The backend is probed inside a fixture, never while this module is
imported: every xdist worker imports every test file, and a module that
decides at import whether its tests exist can hand the workers different
collections.
"""
import sys

import numpy as np
import pytest


@pytest.fixture(scope="module")
def tpu_backend():
    import jax

    if jax.default_backend() != "tpu":
        pytest.skip("needs a real TPU backend")


def test_mxu_matmul_precision_bounds(tpu_backend):
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    rng = np.random.default_rng(0)
    a = rng.normal(size=(256, 128)).astype(np.float32)
    b = rng.normal(size=(128, 64)).astype(np.float32)
    want = a @ b
    # default: bf16 MXU passes — absolute error scales like
    # sqrt(k) * eps_bf16 * |a||b| (~0.1 for k=128 unit-normal operands);
    # near-zero outputs make pointwise relative error meaningless
    got = ht.matmul(ht.array(a, split=0), ht.array(b)).numpy()
    err = np.abs(got - want)
    assert err.max() < 0.3, f"bf16 matmul abs error out of band: {err.max()}"
    typical_rel = np.median(err / np.maximum(np.abs(want), 1e-2))
    assert typical_rel < 0.01, f"bf16 matmul typical rel error: {typical_rel}"
    # escape hatch: full f32 accumulate
    with jax.default_matmul_precision("highest"):
        got_hi = ht.matmul(ht.array(a, split=0), ht.array(b)).numpy()
    np.testing.assert_allclose(got_hi, want, rtol=2e-5, atol=2e-5)


def test_kmeans_fit_on_device(tpu_backend):
    import heat_tpu as ht

    rng = np.random.default_rng(1)
    centers = np.array([[0.0, 0.0], [10.0, 10.0], [0.0, 10.0]], np.float32)
    pts = np.concatenate(
        [c + rng.normal(0, 0.5, size=(200, 2)).astype(np.float32) for c in centers]
    )
    km = ht.cluster.KMeans(n_clusters=3, random_state=0).fit(ht.array(pts, split=0))
    found = km.cluster_centers_.numpy()
    for c in centers:
        assert np.linalg.norm(found - c, axis=1).min() < 0.2


def test_sort_and_percentile_on_device(tpu_backend):
    import heat_tpu as ht

    x = np.random.default_rng(2).normal(size=10_001).astype(np.float32)
    v, i = ht.sort(ht.array(x, split=0))
    np.testing.assert_array_equal(v.numpy(), np.sort(x))
    np.testing.assert_allclose(
        ht.percentile(ht.array(x, split=0), [25.0, 75.0]).numpy(),
        np.percentile(x, [25.0, 75.0]),
        rtol=1e-5,
    )


def test_io_roundtrip_on_device(tpu_backend, tmp_path):
    import heat_tpu as ht

    x = ht.random.randn(1000, 8, split=0)
    path = str(tmp_path / "tpu_smoke.h5")
    ht.save(x, path, "data")
    back = ht.load(path, dataset="data", split=0)
    np.testing.assert_allclose(back.numpy(), x.numpy(), rtol=1e-6)


def test_reductions_match_host(tpu_backend):
    import heat_tpu as ht

    x = np.random.default_rng(3).normal(size=(513, 9)).astype(np.float32)
    a = ht.array(x, split=0)
    np.testing.assert_allclose(float(a.sum().item()), x.sum(), rtol=1e-4)
    np.testing.assert_allclose(a.mean(axis=0).numpy(), x.mean(axis=0), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(a.std(axis=0).numpy(), x.std(axis=0), rtol=1e-3)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q", "-p", "no:cacheprovider"]))
