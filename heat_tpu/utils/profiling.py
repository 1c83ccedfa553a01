"""Profiling hooks.

The reference has none (SURVEY §5: benchmarks use bare
``time.perf_counter``). On TPU the XLA profiler is nearly free to wire in:
``trace`` captures a TensorBoard-viewable device trace, ``annotate`` names
regions inside it, and ``Timer`` reproduces the reference's benchmark
timing pattern with proper device synchronization.
"""
from __future__ import annotations

import contextlib
import os
import time
from typing import Optional

import jax

from ..core import _hooks

__all__ = ["trace", "annotate", "phase", "force_sync", "Timer", "configure_compile_cache"]


def configure_compile_cache() -> str:
    """Give JAX's persistent compilation cache a home, for the scripts
    that run on the chip (``chip_smoke.py``, ``bench.py``); returns the
    directory. The library itself sets no cache on import.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    no directory is set here. Otherwise the cache is
    ``<checkout>/.jax_cache``: a fixed, git-ignored path, because the path
    is part of the cache's key — a temp name, pid or timestamp would never
    hit. Call it before the first compile.
    """
    # small programs too: a cold run of the main path is hundreds of
    # sub-second compiles, which the default 1 s threshold would all skip
    # (on the chip three of five phases started no faster from a warm
    # cache until this was lowered)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def force_sync(*arrays) -> None:
    """Block until the computations producing ``arrays`` have run
    (``jax.block_until_ready`` on their device buffers; DNDarrays and
    pytrees of them are unwrapped). Used by the benchmark harnesses."""
    is_dnd = lambda x: hasattr(x, "larray")
    leaves = jax.tree_util.tree_leaves(arrays, is_leaf=is_dnd)
    jax.block_until_ready([getattr(leaf, "larray", leaf) for leaf in leaves])


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture an XLA device trace viewable in TensorBoard/Perfetto."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# a named region inside a :func:`trace` capture: ``with annotate("load"):``.
# The library's own spans (``ht.call:*``, ``ht.fetch:*``, ``ht.exchange:*``)
# are made by the same function.
annotate = _hooks.span

# a named part of a jitted function, on the device's plane of the capture:
# ``with phase("solve"):`` around the code as it is traced. The library's
# own (``ht.phase:sort``, ``ht.phase:scan``, ...) are made by the same function.
phase = _hooks.phase


class Timer:
    """Wall-clock timer that blocks on device completion.

    The reference timed with bare ``perf_counter`` around eager torch+MPI
    (``benchmarks/kmeans/heat-cpu.py:23-26``); under async JAX dispatch a
    correct timer must synchronize, so ``stop(x)`` blocks on ``x`` (or on
    every device's queue when given nothing).
    """

    def __init__(self):
        self._t0: Optional[float] = None
        self.elapsed: Optional[float] = None

    def start(self) -> "Timer":
        self._t0 = time.perf_counter()
        return self

    def stop(self, *block_on) -> float:
        if block_on:
            force_sync(*block_on)
        else:
            # a device runs its programs in order: a trivial one enqueued
            # now is ready only after everything dispatched before it
            jax.block_until_ready([jax.device_put(0.0, d) + 0 for d in jax.devices()])
        self.elapsed = time.perf_counter() - self._t0
        return self.elapsed

    def __enter__(self) -> "Timer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
