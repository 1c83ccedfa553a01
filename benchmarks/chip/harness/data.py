"""What every driver needs to make its operands on the device from the seed."""
from __future__ import annotations


def prng_key(seed: int):
    """A key from any non-negative whole ``--seed``, also one past 2**31 (the driver's are)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def on_mesh(comm, shape, fn, *args, axis=0):
    """Run ``fn`` jitted with its ``shape`` output born split along ``axis`` of ``comm``:
    nothing is made on the host or on one chip and moved."""
    import jax

    return jax.jit(fn, out_shardings=comm.array_sharding(shape, axis))(*args)
