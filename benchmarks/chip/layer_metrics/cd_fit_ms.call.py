"""Device time a call of the whole fit: the program ``jit__cd_fit`` (the sweeps' ``while`` around the
columns' one, with the two whole-array passes a sweep starts with: the column norms and ``y - x theta``).
None where the trace holds no such program."""
NAME, UNIT = "cd_fit_ms.call", "ms"
LAYER, MOVES = "compiled program (XLA)", "call_ms.p50"
PROGRAM = "jit__cd_fit"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.module_s_per_call().get(PROGRAM)
    return None if seconds is None else seconds * 1e3
