"""Host time inside ``ht.exchange:*`` spans, a call: building the schedule and launching the exchange's
program (the collectives' device time is the trace's own)."""
from harness import spans

NAME, UNIT = "exchange_ms.call", "ms"
LAYER, MOVES = "data movement; host", "call_ms.p50"


def read(run):
    found = spans.of(run.trace)
    return None if found is None else found.per_call_ms(found.exchange_s)
