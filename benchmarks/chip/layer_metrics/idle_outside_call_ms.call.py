"""Device idle time under no ``ht.call:*`` span, a call: the client's turn-around between the end of
one public call and the start of the next (the fence's return, dropping the result, the loop), which no
change under ``heat_tpu/`` moves."""
from harness import spans

NAME, UNIT = "idle_outside_call_ms.call", "ms"
LAYER, MOVES = "public call and DNDarray dispatch", "call_ms.p50"


def read(run):
    found = spans.of(run.trace)
    return None if found is None else found.per_call_ms(found.idle_outside_call_s)
