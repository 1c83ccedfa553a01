"""Per-kernel dispatch registry + ``KERNEL_STATS`` counters.

Every fused kernel in this package registers itself here with a *probe*
(can the compiled pallas path run on this backend?) and a declared
fallback mode. Public APIs then ask :func:`dispatch_mode` which
implementation to run and report the decision through
:func:`record_dispatch`, so kernel-vs-fallback dispatch is observable
exactly like LAYOUT/MOVE/COMPILE_STATS:

- ``"pallas"``    — compiled Mosaic kernel (TPU backend);
- ``"interpret"`` — pallas interpreter (CPU test meshes; opt-in only —
  the interpreter is orders of magnitude slower than XLA, so it is for
  parity tests, never the default dispatch);
- ``"xla"``       — a fused raw-jnp twin of the kernel (same one-pass
  dataflow, compiled by XLA; the default fast path off-TPU);
- ``"fallback"``  — the pre-kernel legacy path (two-pass reduce,
  unfused update matmul, separate XLA factorization ops).

One module-level observer folds ``kernel.dispatch`` events into
:data:`KERNEL_STATS` (exported as ``ht.KERNEL_STATS``); events from
other families pass through untouched. Dispatch is recorded at the
Python call boundary — once per eager call / fit / chunk — never inside
traced code, so warm cached programs still count.

x64 and kernel bodies: the library turns ``jax_enable_x64`` on at import,
so inside a kernel every untyped Python number is 64 bits wide, and
Mosaic has no 64-bit vectors. A literal ``0`` in a BlockSpec index map
fails to legalize the map; Python-int ``fori_loop`` bounds make the loop
index an i64 whose comparison against an int32 iota recurses in the
lowering; ``jnp.where(mask, 1.0, 0.0)`` builds an f64 vector and aborts
the compiler. Kernels therefore type them by hand: ``jnp.int32(0)`` in
index maps, ``jnp.int32`` loop bounds, ``mask.astype(x.dtype)``.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, Iterator, Optional

import jax

from .. import _hooks

__all__ = [
    "KERNEL_STATS",
    "KERNELS",
    "dispatch_mode",
    "forced_mode",
    "kernel_spec",
    "pallas_supported",
    "record_dispatch",
    "register_kernel",
    "reset_kernel_stats",
]


def _default_probe() -> bool:
    return jax.default_backend() == "tpu"


# name -> spec dict: {"probe", "fallback", "comparator", "roofline"}
KERNELS: Dict[str, Dict] = {}


def register_kernel(
    name: str,
    *,
    probe: Optional[Callable[[], bool]] = None,
    fallback: str = "fallback",
    comparator: str = "",
    roofline: str = "",
) -> str:
    """Register a fused kernel with the dispatch layer.

    ``probe`` answers "can the *compiled* pallas path run right now?"
    (default: a TPU backend). ``fallback`` names the
    mode :func:`dispatch_mode` reports when it cannot. ``comparator``
    and ``roofline`` are documentation carried into bench notes and
    docs/PERFORMANCE.md — every kernel lands with a raw-jnp comparator
    row and a roofline statement, so wins stay measured, not asserted.
    """
    KERNELS[name] = {
        "probe": probe or _default_probe,
        "fallback": fallback,
        "comparator": comparator,
        "roofline": roofline,
    }
    return name


def kernel_spec(name: str) -> Dict:
    return KERNELS[name]


def pallas_supported(kernel: Optional[str] = None) -> bool:
    """True when compiled (non-interpreted) pallas kernels can run.

    With a ``kernel`` name, consults that kernel's registered probe
    (kernels may have extra requirements beyond the backend); without
    one, keeps the historical global semantics.
    """
    if kernel is not None and kernel in KERNELS:
        return bool(KERNELS[kernel]["probe"]())
    return _default_probe()


# test-only overrides: kernel name -> forced mode (see forced_mode())
_FORCED: Dict[str, str] = {}


def dispatch_mode(kernel: str) -> str:
    """The mode the public API should dispatch for ``kernel`` right now."""
    forced = _FORCED.get(kernel)
    if forced is not None:
        return forced
    return "pallas" if pallas_supported(kernel) else KERNELS[kernel]["fallback"]


@contextlib.contextmanager
def forced_mode(kernel: str, mode: str) -> Iterator[None]:
    """Force :func:`dispatch_mode` for one kernel inside the block.

    Parity tests use this to drive the *public* APIs through the
    interpret-mode kernels on CPU meshes — dispatch never picks the
    interpreter on its own (it is orders of magnitude slower than XLA).
    """
    prev = _FORCED.get(kernel)
    _FORCED[kernel] = mode
    try:
        yield
    finally:
        if prev is None:
            _FORCED.pop(kernel, None)
        else:
            _FORCED[kernel] = prev


def record_dispatch(kernel: str, mode: str) -> None:
    """Report one public-API dispatch decision (call boundary only)."""
    _hooks.observe("kernel.dispatch", kernel=kernel, mode=mode)


KERNEL_STATS: Dict[str, int] = {"dispatches": 0}


def reset_kernel_stats() -> None:
    """Zero :data:`KERNEL_STATS` (counter-asserting tests bracket with
    this)."""
    KERNEL_STATS.clear()
    KERNEL_STATS["dispatches"] = 0


def _observer(event: str, ctx: dict) -> None:
    if event == "kernel.dispatch":
        KERNEL_STATS["dispatches"] += 1
        key = f"{ctx.get('kernel', '?')}.{ctx.get('mode', '?')}"
        KERNEL_STATS[key] = KERNEL_STATS.get(key, 0) + 1


_hooks.add_observer(_observer)
