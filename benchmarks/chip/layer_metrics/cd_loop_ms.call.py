"""Device time a call inside the columns' loop of the program ``jit__cd_fit``: the dependent steps
of coordinate descent, one a column, so that ``cd_fit_ms.call`` less this is the whole-array passes
a sweep starts with (the column norms and ``y - x theta``). The program's ``while`` operations nest,
the sweeps' around the columns', and the outer one holds a whole-array pass too, so only the
innermost are measured (those with no other ``while`` inside them), each moment once, averaged over
the chips. None where the trace holds no such program or no loop in it."""
import re

from harness import xplane

NAME, UNIT = "cd_loop_ms.call", "ms"
LAYER, MOVES = "compiled program (XLA)", "call_ms.p50"
PROGRAM = "jit__cd_fit"
LOOP = re.compile(r"^while(\.\d+)?$")


def read(run):
    trace = run.trace
    if trace is None:
        return None
    lo, hi = trace.window
    seconds = 0.0
    for device in trace.devices:
        fits = [(s, e) for name, s, e in device["modules"] if name == PROGRAM and lo <= s < hi]
        loops = [(s, e) for text, s, e in device["ops"] if LOOP.match(xplane.op_name(text))
                 and any(fs <= s and e <= fe for fs, fe in fits)]
        seconds += sum(e - s for s, e in loops
                       if not any((s, e) != (s2, e2) and s <= s2 and e2 <= e for s2, e2 in loops))
    return seconds * 1e3 / (len(trace.devices) * len(trace.calls)) if seconds else None
