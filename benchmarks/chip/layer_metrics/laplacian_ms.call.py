"""Device time a call inside the scope ``ht.phase:laplacian`` (``graph/laplacian.py::_laplacian_program``): the
similarity computed from the rows twice, once inside the degrees' row sums and once where it is read, and L written
once. Self times, averaged over the chips. None where the trace's operations carry no scope."""
from harness import phases

NAME, UNIT = "laplacian_ms.call", "ms"
LAYER, MOVES = "compiled program (XLA)", "call_ms.p50"
PHASE = "laplacian"


def read(run):
    found = phases.of(run)
    return None if found is None else found.ms(PHASE)
