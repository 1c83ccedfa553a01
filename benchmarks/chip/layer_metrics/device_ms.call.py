"""Device time of a call: the union of the device's "XLA Modules" events inside its span."""
NAME, UNIT = "device_ms.call", "ms"
LAYER, MOVES = "compiled program (XLA)", "call_ms.p50"


def read(run):
    if run.trace is None:
        return None
    return run.trace.device_s_per_call() * 1e3
