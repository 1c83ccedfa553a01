"""Device time a call inside the scope ``ht.phase:lanczos`` (``core/linalg/solver.py::_lanczos_program``): every read
of L, the first matvec and the loop of the others, with each step's re-orthogonalisation. Self times, averaged over
the chips. None where the trace's operations carry no scope."""
from harness import phases

NAME, UNIT = "lanczos_ms.call", "ms"
LAYER, MOVES = "compiled program (XLA)", "call_ms.p50"
PHASE = "lanczos"


def read(run):
    found = phases.of(run)
    return None if found is None else found.ms(PHASE)
