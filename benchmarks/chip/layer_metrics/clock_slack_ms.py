"""How far the device's clock may still be off the host's in this trace: the width of the interval of
shifts under which every program starts after its launch starts, ends before its completion notice and lies
inside the window (``harness/spans.py``). The uncertainty of ``idle_in_call_ms.call`` and
``idle_outside_call_ms.call``; None where launches and programs cannot be paired by count."""
from harness import spans

NAME, UNIT = "clock_slack_ms", "ms"
LAYER, MOVES = "device", "call_ms.p50"


def read(run):
    placed = None if run.trace is None else spans.placement(run.trace)
    return None if placed is None else placed[1] * 1e3
