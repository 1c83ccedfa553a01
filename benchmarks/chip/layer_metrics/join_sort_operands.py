"""The operands the last join's one sort carried, as the records count a sort's cost (0.14 s an operand at 1e8 rows):
the ones ``lax.sort`` was handed plus one where it was asked to be stable, the index the compiler adds; ``sort_ms.call``
grows with it. The program keeps it as a gauge under ``SHUFFLE_STATS``, known when the join's program is built; None on
a program that keeps none."""
NAME, UNIT = "join_sort_operands", "count"
LAYER, MOVES = "compiled program (XLA)", "call_ms.p50"


def read(run):
    import heat_tpu as ht

    return getattr(ht, "SHUFFLE_STATS", {}).get("join_sort_operands")
