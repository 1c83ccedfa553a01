# graftlint-fixture: G004=6
# graftflow-fixture: F001=0
# graftlint: hot-path
"""True positives for G004: implicit host syncs on a hot path.

The pragma above opts this file into the hot-path set (in the real tree
that set is parallel/** plus the core dispatch modules).
"""
from functools import partial

import jax
import numpy as np


def asarray_sync(x):
    return np.asarray(x)  # device value -> host copy, blocks dispatch


def item_sync(x):
    return x.item()  # scalar fetch: full pipeline flush


def device_get_sync(x):
    return jax.device_get(x)


def block_sync(x):
    x.block_until_ready()
    return x


@partial(jax.jit, static_argnames=("k",))
def _fit(x, k):
    return x * k, x.sum()


def scalar_of_a_jit_call(x):
    return float(_fit(x, 2)[1].sum()), float(_fit(x, 2))  # the second is the fetch nobody counts


def scalar_of_a_name_bound_to_a_jit_result(x):
    centers, n_iter = _fit(x, 2)
    return centers, int(n_iter)
