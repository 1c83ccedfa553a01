"""Device time a call inside the scope ``ht.phase:gram`` (``regression/lasso.py::_gram_sweep``): the Gram matrix's one
matmul with the centring, the rank-one term and the zeroed diagonal fused into it, the second of the fit's two reads
of x; self times. None where the trace's operations carry no scope or the fit took the residual path."""
from harness import phases

NAME, UNIT = "gram_ms.call", "ms"
LAYER, MOVES = "compiled program (XLA)", "call_ms.p50"
PHASE = "gram"


def read(run):
    found = phases.of(run)
    return None if found is None else found.ms(PHASE)
