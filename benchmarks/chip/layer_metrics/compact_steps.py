"""The elementwise passes ``_compact_front`` ran in the last join, filter or groupby, on the shard that ran most:
the bit length of the most rows dropped ahead of a kept one, which ``compact_ms.call`` grows with. The program keeps
it as a gauge under ``SHUFFLE_STATS``, from the counts it fetches anyway; None on a program that keeps none."""
NAME, UNIT = "compact_steps", "count"
LAYER, MOVES = "compiled program (XLA)", "call_ms.p50"


def read(run):
    import heat_tpu as ht

    return getattr(ht, "SHUFFLE_STATS", {}).get("compact_steps")
