"""Device time a call of the operations under no ``ht.phase:`` scope, in whatever program: what a program does
between its phases (staging ``concatenate``s and ``where``s, counts, the compiler's own copies outside every loop)
and every program that has none. With the phases it adds up to what the device's operations cover, so it says how
much of ``device_ms.call`` the phase metrics leave unexplained; ``harness/phases.py <trace>`` names its largest
operations by source line. Self times, averaged over the chips. None where no operation carries a scope at all."""
from harness import phases

NAME, UNIT = "unphased_ms.call", "ms"
LAYER, MOVES = "compiled program (XLA)", "call_ms.p50"


def read(run):
    found = phases.of(run)
    return None if found is None else found.unphased_s_per_call * 1e3
