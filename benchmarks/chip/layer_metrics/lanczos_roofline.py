"""The Lanczos phase's share of the HBM roofline: its least time, ``lanczos_steps`` reads of an n x n float32 L (n the
configuration's ``rows``; 4 B an entry, as the configuration stores L) at the chip's peak HBM bytes/s, over
``lanczos_ms.call``. The count is of a float32 L: storing L in another form is a question for the benchmark, not a
gain. None where the program keeps no step gauge or the trace no ``ht.phase:lanczos``."""
from harness import phases

NAME, UNIT = "lanczos_roofline", "%"
LAYER, MOVES = "compiled program (XLA)", "call_ms.p50"
PHASE = "lanczos"


def read(run):
    import heat_tpu as ht

    steps = getattr(ht, "LAYOUT_STATS", {}).get("lanczos_steps")
    found = phases.of(run)
    ms = None if found is None else found.ms(PHASE)
    if not steps or not ms or run.peaks is None:
        return None
    n = run.config["sizes"]["rows"]
    least_s = steps * n * n * 4 / (run.peaks["hbm_bytes_per_s"] * run.chips)
    return 100.0 * least_s / (ms * 1e-3)
