"""The ``lloyd_fused`` kernel's own share of the HBM roofline: the least time of one of its calls
(one pass over x, from the driver's ``work``) over the mean duration of its events in the trace."""
NAME, UNIT = "lloyd_fused_roofline", "%"
LAYER, MOVES = "Pallas kernels", "call_ms.p50"
EVENT = "_lloyd_call"


def read(run):
    one = run.work["kernels"].get(EVENT)
    if run.trace is None or run.peaks is None or one is None:
        return None
    mean_s = run.trace.op_s_per_call([EVENT]) / run.work["kernel_events_per_call"][EVENT]
    least = max(one["flops"] / run.peaks["flops_per_s"], one["bytes"] / run.peaks["hbm_bytes_per_s"]) / run.chips
    return 100.0 * least / mean_s if mean_s > 0 else None
