"""``ht.exchange:*`` spans a call: bucket moves, ragged moves and tree merges the call dispatched
(opened exactly where ``MOVE_STATS`` is advanced)."""
from harness import spans

NAME, UNIT = "exchanges.call", "count/call"
LAYER, MOVES = "data movement; host", "call_ms.p50"


def read(run):
    found = spans.of(run.trace)
    return None if found is None else found.exchanges / found.calls
