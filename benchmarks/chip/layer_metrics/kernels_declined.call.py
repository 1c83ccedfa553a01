"""Dispatch decisions a call that did not go to a Pallas kernel: ``KERNEL_STATS`` entries
``<kernel>.<mode>`` whose mode is not ``pallas`` (a fallback, an XLA twin, interpreted)."""
NAME, UNIT = "kernels_declined.call", "count/call"
LAYER, MOVES = "kernel dispatch registry", "call_ms.p50"


def read(run):
    if not run.calls:
        return None
    stats = run.counters["traced"]["kernel"]
    return sum(n for k, n in stats.items() if k != "dispatches" and not k.endswith(".pallas")) / run.calls
