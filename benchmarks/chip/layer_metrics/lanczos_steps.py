"""The matvecs of L the last Lanczos solve ran: the gauge ``LAYOUT_STATS["lanczos_steps"]``, set by
``linalg/solver.py`` at each solve. None on a program that keeps no such gauge."""
NAME, UNIT = "lanczos_steps", "count"
LAYER, MOVES = "compiled program (XLA)", "call_ms.p50"


def read(run):
    import heat_tpu as ht

    return getattr(ht, "LAYOUT_STATS", {}).get("lanczos_steps")
