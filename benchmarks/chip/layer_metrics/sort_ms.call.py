"""Device time a call inside the scope ``ht.phase:sort``, in whatever program: every ``lax.sort`` that carries
its columns as operands (``frame/_shuffle.py::_carry_sort``: the join's merged sort, the plan's sort by key, a
partition's sort by destination, the election's two whole-column sorts), self times, averaged over the chips. None
where the trace's operations carry no scope (a program without them, or one from a compile cache older than they are)."""
from harness import phases

NAME, UNIT = "sort_ms.call", "ms"
LAYER, MOVES = "compiled program (XLA)", "call_ms.p50"
PHASE = "sort"


def read(run):
    found = phases.of(run)
    return None if found is None else found.ms(PHASE)
