"""Device idle time under an outermost ``ht.call:*`` span, a call: idle the program causes. With
``idle_outside_call_ms.call`` it sums to the window's idle time, ``(window_s - busy_s) / calls``; both
are good to ``clock_slack_ms`` (``harness/spans.py`` says where the device's clock is placed)."""
from harness import spans

NAME, UNIT = "idle_in_call_ms.call", "ms"
LAYER, MOVES = "public call and DNDarray dispatch", "call_ms.p50"


def read(run):
    found = spans.of(run.trace)
    return None if found is None else found.per_call_ms(found.idle_in_call_s)
