"""The chip's compiler, asked without the chip: the four registered Mosaic kernels.

Each is lowered at the old bench's shapes and at ``chip_smoke.py``'s for
device 0 of a described ``v5e:2x2`` topology, and the two ``shard_map``
wrappers over a mesh of its four devices. ``tests/_chip_helpers.py`` says
what a compile here shows and what it does not, and why nothing in this
file describes a topology at import. The frame's programs are compiled in
``test_chip_compile_groupby.py`` and ``test_chip_compile_join.py``.
"""
from __future__ import annotations

import pytest

import chip_smoke

from ._chip_helpers import (  # noqa: F401 - the fixtures are found by name
    BENCH_CDIST_FEATURES, BENCH_KMEANS, BENCH_MOMENTS, _compiled_kernel, _spec, four_chips, one_chip, topo,
)


# Each kernel is lowered through its own entry point, so the tiles under
# test are the ones dispatch gets. Beside the bench and smoke shapes: the
# corners of what dispatch admits (``kernel_fits``, the classifier's
# n_neighbors <= 64), where a tile sized by the feature count alone ran
# out of VMEM.
KMEANS_SHAPES = {
    "bench": BENCH_KMEANS,
    "smoke": chip_smoke.SIZES["kmeans"][:3],
    "two_features": (1 << 20, 2, 3),  # the tile pads to 8 sublanes
    "many_centers": (1 << 20, 32, 256),
    "widest_most_centers": (1 << 20, 64, 1024),
    "narrow_most_centers": (1 << 20, 8, 1024),
}
MOMENTS_SHAPES = {
    "bench": BENCH_MOMENTS,
    "smoke": chip_smoke.SIZES["moments"],
    "one_column": (1 << 22, 1),  # what a 1-D array is given as
    "widest": (1 << 22, 64),
}
_NQ, _NT, _F, _K = chip_smoke.SIZES["knn"]
KNN_SHAPES = {
    "smoke": (_NQ, _NT, _F, _K),  # bench-protocol sizes too: SUSY's 18 features, k = 5
    "most_neighbors": (_NQ, _NT, _F, 64),
    "wide": (_NQ, _NT, 128, _K),
    "wide_most_neighbors": (_NQ, _NT, 128, 64),
}


def test_shapes_are_what_dispatch_admits():
    from heat_tpu.core.kernels import lloyd, moments

    assert _F == BENCH_CDIST_FEATURES
    assert all(lloyd.kernel_fits(f, k) for _, f, k in KMEANS_SHAPES.values())
    assert all(moments.kernel_fits(f) for _, f in MOMENTS_SHAPES.values())
    # one past each bound is declined
    assert not lloyd.kernel_fits(lloyd.MAX_FEATURES + 1, 8)
    assert not lloyd.kernel_fits(32, lloyd.MAX_CLUSTERS + 1)
    assert not moments.kernel_fits(moments.MAX_FEATURES + 1)


@pytest.mark.parametrize("which", sorted(KMEANS_SHAPES))
def test_lloyd_fused_compiles(one_chip, which):
    import jax
    import jax.numpy as jnp

    from heat_tpu.core.kernels import lloyd_local

    n, f, k = KMEANS_SHAPES[which]
    lowered = jax.jit(lloyd_local).lower(
        _spec((n, f), jnp.float32, one_chip), _spec((k, f), jnp.float32, one_chip),
        _spec((), jnp.int32, one_chip),
    )
    # the (n, f) operand is consumed in place: no padded or transposed copy
    _compiled_kernel(lowered, max_temp_bytes=1 << 20)


@pytest.mark.parametrize("which", sorted(MOMENTS_SHAPES))
def test_moments_onepass_compiles(one_chip, which):
    import jax
    import jax.numpy as jnp

    from heat_tpu.core.kernels import moments_local

    n, f = MOMENTS_SHAPES[which]
    lowered = jax.jit(moments_local).lower(
        _spec((n, f), jnp.float32, one_chip), _spec((), jnp.int32, one_chip)
    )
    _compiled_kernel(lowered, max_temp_bytes=1 << 20)


@pytest.mark.parametrize("n", [chip_smoke.SIZES["chol"], 1000, 100])
def test_chol_panel_fused_compiles(one_chip, n):
    """The public path's shapes: 128-wide panels once the matrix has more
    than one, a single whole-matrix panel below that; n = 1000 pads."""
    import jax
    import jax.numpy as jnp

    from heat_tpu.core.kernels import panel_update

    assert chip_smoke.SIZES["chol"] == panel_update.MAX_FUSED_N
    lowered = jax.jit(panel_update.cholesky_blocked).lower(_spec((n, n), jnp.float32, one_chip))
    _compiled_kernel(lowered, max_temp_bytes=16 << 20)


@pytest.mark.parametrize("which", sorted(KNN_SHAPES))
def test_topk_distance_compiles(one_chip, which):
    import jax
    import jax.numpy as jnp

    from heat_tpu.core.kernels import nearest_neighbors

    nq, nt, f, k = KNN_SHAPES[which]
    lowered = jax.jit(lambda x, y: nearest_neighbors(x, y, k)).lower(
        _spec((nq, f), jnp.float32, one_chip), _spec((nt, f), jnp.float32, one_chip)
    )
    # row-tiled still: XLA may re-lay both (n, f) operands out to 128 lanes
    _compiled_kernel(lowered, max_temp_bytes=(nq + nt) * 128 * 4 * 2)


def test_lloyd_sharded_compiles_over_four_chips(four_chips):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from heat_tpu.core.communication import SPLIT_AXIS
    from heat_tpu.core.kernels import lloyd_sharded

    n, f, k = KMEANS_SHAPES["smoke"]
    rows, rep = NamedSharding(four_chips, P(SPLIT_AXIS, None)), NamedSharding(four_chips, P())
    lowered = jax.jit(lambda x, c, nv: lloyd_sharded(x, c, nv, four_chips)).lower(
        _spec((n, f), jnp.float32, rows), _spec((k, f), jnp.float32, rep), _spec((), jnp.int32, rep)
    )
    compiled = _compiled_kernel(lowered, max_temp_bytes=1 << 20)
    assert "all-reduce" in compiled.as_text()  # the psum of sums, counts, inertia
    # each chip holds its quarter of the rows, not a replica
    assert compiled.memory_analysis().argument_size_in_bytes < n * f * 4 // 4 + (1 << 20)


def test_moments_sharded_compiles_over_four_chips(four_chips):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from heat_tpu.core.communication import SPLIT_AXIS
    from heat_tpu.core.kernels import moments_sharded

    n, f = MOMENTS_SHAPES["smoke"]
    rows, rep = NamedSharding(four_chips, P(SPLIT_AXIS, None)), NamedSharding(four_chips, P())
    lowered = jax.jit(lambda x, nv: moments_sharded(x, nv, four_chips)).lower(
        _spec((n, f), jnp.float32, rows), _spec((), jnp.int32, rep)
    )
    compiled = _compiled_kernel(lowered, max_temp_bytes=1 << 20)
    assert "all-reduce" in compiled.as_text()  # the Chan combine's psums
    assert compiled.memory_analysis().argument_size_in_bytes < n * f * 4 // 4 + (1 << 20)
