"""``Spectral.fit`` held to the plain reference (``cluster/reference.py``) at a small size.

Two Gaussian classes in 18 features, unequal shares (46 / 54 %), spread 1/6 a feature and their
means 1.9 apart, so that the Laplacian's second eigenvalue (about 0.07) lies far below the third
(about 0.93); gamma 1, 64 Lanczos steps, on the suite's mesh (rows split over its devices). Compared:
L elementwise, the k smallest Ritz values, the embedding's columns up to sign, the labels up to a
permutation, the ``lanczos_steps`` gauge; the three programs' phases in their compiled text; and
the Laplacian program's temporaries, under one (n, n) buffer.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.cluster import reference
from heat_tpu.cluster.spectral import _ritz
from heat_tpu.core.linalg import solver
from heat_tpu.graph.laplacian import PairwiseSimilarity, _laplacian_program

N, F, M, K = 512, 18, 64, 2
SCOPE = re.compile(r"(?:^|/)ht\.phase:([^/:]+)")


def blobs(n: int, seed: int, share: float = 0.46, spread: float = 1.0 / 6.0, apart: float = 1.9):
    rng = np.random.default_rng(seed)
    u = rng.normal(size=F)
    u /= np.linalg.norm(u)
    y = (rng.random(n) < share).astype(np.int64)
    x = rng.normal(size=(n, F)) * spread + np.outer(np.where(y == 1, 0.5, -0.5) * apart, u)
    return x.astype(np.float32), y


@pytest.fixture(scope="module", params=[0, 1])
def fitted(request):
    x, y = blobs(N, request.param)
    est = ht.cluster.Spectral(n_clusters=K, gamma=1.0, n_lanczos=M, random_state=request.param)
    est.fit(ht.array(x, split=0))
    return x, y, est, reference.spectral(x, 1.0, M, K)


@pytest.mark.parametrize("definition", ["norm_sym", "simple"])
def test_laplacian_elementwise(definition):
    x, _ = blobs(N, 3)
    got = ht.graph.Laplacian(PairwiseSimilarity("rbf", 1.0), definition=definition).construct(ht.array(x, split=0))
    assert got.split == 0 and got.shape == (N, N)
    want = np.asarray(reference.laplacian(reference.similarity(x, 1.0), definition))
    # float32 sums of 18 squares and of 512 weights: a few units of float32's rounding of the row sums
    np.testing.assert_allclose(got.numpy(), want, rtol=0, atol=1e-6 * np.abs(want).max())


@pytest.mark.parametrize("key", ["upper", "lower"])
@pytest.mark.parametrize("weighted", [True, False])
def test_eneighbour_laplacian_elementwise(key, weighted):
    x, _ = blobs(37, 4)  # 37 rows: the buffer is padded on an 8-device mesh
    lap = ht.graph.Laplacian(PairwiseSimilarity("rbf", 1.0), weighted=weighted, definition="simple",
                             mode="eNeighbour", threshold_key=key, threshold_value=0.3)
    L = lap.construct(ht.array(x, split=0))
    assert not np.asarray(L.larray)[37:].any()  # the buffer's padded rows stay zero
    got = L.numpy()
    S = np.asarray(reference.similarity(x, 1.0), np.float64)
    keep = S < 0.3 if key == "upper" else S > 0.3
    A = np.where(keep, S if weighted else 1.0, 0.0)
    np.fill_diagonal(A, 0.0)
    np.testing.assert_allclose(got, np.diag(A.sum(1)) - A, rtol=0, atol=1e-5)


def test_similarity_callable_gives_the_same_laplacian():
    x, _ = blobs(N, 5)
    xd = ht.array(x, split=0)
    inside = ht.graph.Laplacian(PairwiseSimilarity("rbf", 0.5)).construct(xd).numpy()
    given = ht.graph.Laplacian(lambda z: ht.spatial.rbf(z, sigma=1.0)).construct(xd).numpy()
    np.testing.assert_allclose(inside, given, rtol=0, atol=1e-6)


def test_ritz_values(fitted):
    _, _, est, want = fitted
    got = est.ritz_values_.numpy()
    assert got.shape == (M,) and np.all(np.diff(got) >= 0)
    assert want["values"][2] - want["values"][1] > 0.5  # the Fiedler pair is well apart from the rest
    np.testing.assert_allclose(got[:K], want["values"][:K], rtol=0, atol=1e-6)


def test_embedding_up_to_sign(fitted):
    _, _, est, want = fitted
    got = est.embedding_.numpy()
    assert got.shape == (N, K) and est.embedding_.split == 0
    for c in range(K):
        ref = want["embedding"][:, c]
        err = min(np.abs(got[:, c] - s * ref).max() for s in (1.0, -1.0))
        assert err <= 2e-5 * np.abs(ref).max(), (c, err)


def test_labels_up_to_a_permutation(fitted):
    _, y, est, want = fitted
    labels = est.labels_.numpy()
    assert reference.same_partition(labels, want["labels"])
    assert reference.same_partition(labels, y)


def test_lanczos_steps_gauge(fitted):
    x, _, _, _ = fitted
    ht.cluster.Spectral(n_clusters=K, n_lanczos=M, random_state=0).fit(ht.array(x, split=0))
    assert ht.LAYOUT_STATS["lanczos_steps"] == M
    ht.linalg.lanczos(ht.array(np.eye(12, dtype=np.float32) * 2.0, split=0), 5)
    assert ht.LAYOUT_STATS["lanczos_steps"] == 5


def _phases(compiled_text: str) -> set:
    """The innermost phase (None: no scope) of every instruction traced from the program's code:
    its ``op_name`` is a name stack, ``jit(<program>)/...``. Parameters, a reduction's adder and
    what the partitioner adds carry a bare name."""
    found = set()
    for name in re.findall(r'op_name="(jit\([^"]*)"', compiled_text):
        scopes = SCOPE.findall(name)
        found.add(scopes[-1] if scopes else None)
    return found


def test_every_operation_of_the_three_programs_is_in_its_phase():
    x = jnp.asarray(blobs(N, 6)[0])
    rows = ht.array(np.zeros((N, N), np.float32), split=0).larray.sharding
    lap = _laplacian_program.lower(x, PairwiseSimilarity("rbf", 1.0), "norm_sym", None, N, rows).compile()
    L = jax.ShapeDtypeStruct((N, N), jnp.float32, sharding=rows)
    v = jax.ShapeDtypeStruct((N,), jnp.float32)
    with jax.default_matmul_precision("highest"):
        lan = solver._lanczos_program.lower(L, v, m=M).compile()
        rz = _ritz.lower(jax.ShapeDtypeStruct((M, N), jnp.float32), jax.ShapeDtypeStruct((M, M), jnp.float32),
                         k=K, n=N).compile()
    assert _phases(lap.as_text()) == {"laplacian"}
    assert _phases(lan.as_text()) == {"lanczos"}
    assert _phases(rz.as_text()) == {"ritz"}


@pytest.mark.parametrize("threshold", [None, ("upper", 0.5, True)])
@pytest.mark.parametrize("definition", ["norm_sym", "simple"])
def test_laplacian_program_writes_no_nxn_temporary(definition, threshold):
    """The eager chain this replaced held S, an identity and ``1 - I`` at once: three (n, n)
    arrays. The program's temporaries stay under one."""
    x = jax.ShapeDtypeStruct((N, F), jnp.float32)
    rows = ht.array(np.zeros((N, N), np.float32), split=0).larray.sharding
    compiled = _laplacian_program.lower(x, PairwiseSimilarity("rbf", 1.0), definition, threshold, N, rows).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < N * N * 4
