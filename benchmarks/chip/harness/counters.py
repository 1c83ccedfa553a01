"""The program's counters, read as they stand and subtracted.

``COMPILE_STATS`` (``backend_compiles``, ``traces``, ``host_syncs``: listeners on
``jax.monitoring`` and the library's hooks; a program served from the
persistent cache counts as a backend compile too) and ``KERNEL_STATS`` (one
``<kernel>.<mode>`` entry a dispatch decision at a public call's boundary).
"""
from __future__ import annotations


def snapshot() -> dict:
    import heat_tpu as ht

    return {"compile": dict(ht.COMPILE_STATS), "kernel": dict(ht.KERNEL_STATS)}


def delta(before: dict, after: dict) -> dict:
    """Per counter, ``after - before``; a counter that first appears in ``after`` counts from 0."""
    return {
        group: {k: v - before[group].get(k, 0) for k, v in after[group].items()}
        for group in after
    }
