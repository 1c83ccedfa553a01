"""Host time of a call: its annotated span in the trace less the device-busy time inside it."""
NAME, UNIT = "host_ms.call", "ms"
LAYER, MOVES = "public call and DNDarray dispatch", "call_ms.p50"


def read(run):
    if run.trace is None:
        return None
    return (run.trace.span_s_per_call() - run.trace.device_s_per_call()) * 1e3
