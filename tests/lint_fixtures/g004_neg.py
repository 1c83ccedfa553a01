# graftlint-fixture: G004=0
# graftflow-fixture: F001=0
# graftlint: hot-path
"""Near-miss negatives for G004 (same hot-path pragma as the positive)."""
import jax
import numpy as np

from heat_tpu.core import _hooks


def asarray_literal():
    # literal argument: host data to host array, no device involved
    return np.asarray([1.0, 2.0, 3.0])


def waived_sync(x):
    # an intentional, documented sync is waived
    return np.asarray(x)  # graftlint: host-sync - O(world) metadata fetch


def dict_items(d):
    # .items() on a dict is not .item() on an array
    return sorted(d.items())


def asarray_in_cold_helper(x):
    # waiver in the comment block directly above also applies
    # graftlint: host-sync - result assembly is this op's contract
    return np.asarray(x)


@jax.jit
def _fit(x):
    return x * 2, x.sum()


def fetched_scalar(x):
    # the counted way: _hooks.fetch raises host.fetch and opens ht.fetch:<site>
    centers, n_iter = _fit(x)
    return centers, int(_hooks.fetch(n_iter, "fixture.n_iter")), float(_hooks.fetch(_fit(x)[1], "fixture.sum"))


def host_constants(dt, n):
    # computed by numpy, so already on the host; and a plain Python number
    return np.asarray(np.inf, dt), np.asarray(np.iinfo(dt).max, dt), float(n)
