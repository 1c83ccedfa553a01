"""What the splitter election achieved: the fullest destination's rows over the mean, of the last
whole-row shuffle the program made (1.0 = even; every receive block, and with it the join's, is
as long as the fullest bucket, rounded up by at most a 64th). The program keeps it as a gauge under ``SHUFFLE_STATS``, computed
from the bucket matrix it fetches anyway; None on a program that keeps none."""
NAME, UNIT = "bucket_skew", "x"
LAYER, MOVES = "data movement; host", "call_ms.p50"


def read(run):
    import heat_tpu as ht

    return getattr(ht, "SHUFFLE_STATS", {}).get("bucket_skew")
