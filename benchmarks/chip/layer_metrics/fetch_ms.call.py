"""Host time inside ``ht.fetch:*`` spans, a call: how long the host stood blocked on device values
(the wait for the device, then the transfer)."""
from harness import spans

NAME, UNIT = "fetch_ms.call", "ms"
LAYER, MOVES = "public call and DNDarray dispatch", "call_ms.p50"


def read(run):
    found = spans.of(run.trace)
    return None if found is None else found.per_call_ms(found.fetch_s)
