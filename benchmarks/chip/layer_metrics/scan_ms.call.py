"""Device time a call inside the scope ``ht.phase:scan`` (``frame/_shuffle.py::_scan_runs``): the loop that
carries values along each run of equal keys, a step a doubling of the distance, with the copies the compiler puts
into the loop's body; self times, averaged over the chips. None where the trace's operations carry no scope."""
from harness import phases

NAME, UNIT = "scan_ms.call", "ms"
LAYER, MOVES = "compiled program (XLA)", "call_ms.p50"
PHASE = "scan"


def read(run):
    found = phases.of(run)
    return None if found is None else found.ms(PHASE)
