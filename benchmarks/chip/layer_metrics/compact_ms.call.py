"""Device time a call inside the scope ``ht.phase:compact`` (``frame/_shuffle.py::_compact_front``): the
cumulative sum that says how far each kept row moves, the loop over the displacements and the loops that move the
columns, ``SHUFFLE_STATS["compact_steps"]`` passes each; self times, averaged over the chips. None where the trace's
operations carry no scope."""
from harness import phases

NAME, UNIT = "compact_ms.call", "ms"
LAYER, MOVES = "compiled program (XLA)", "call_ms.p50"
PHASE = "compact"


def read(run):
    found = phases.of(run)
    return None if found is None else found.ms(PHASE)
