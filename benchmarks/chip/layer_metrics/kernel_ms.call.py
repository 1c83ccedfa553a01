"""Device time a call spends in the configuration's Pallas kernels: the durations of the trace's
operations named in its ``kernels`` list. A configuration that lists none spends 0 there."""
NAME, UNIT = "kernel_ms.call", "ms"
LAYER, MOVES = "Pallas kernels", "call_ms.p50"


def read(run):
    if run.trace is None:
        return None
    return run.trace.op_s_per_call(run.config["kernels"]) * 1e3
