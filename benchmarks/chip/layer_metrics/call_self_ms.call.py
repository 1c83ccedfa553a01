"""heat_tpu's own Python on the host, a call: the outermost ``ht.call:*`` spans' duration less what
the ``ht.fetch:*`` and ``ht.exchange:*`` spans inside them cover. Host clock alone: no device clock enters."""
from harness import spans

NAME, UNIT = "call_self_ms.call", "ms"
LAYER, MOVES = "public call and DNDarray dispatch", "call_ms.p50"


def read(run):
    found = spans.of(run.trace)
    return None if found is None else found.per_call_ms(found.self_s)
