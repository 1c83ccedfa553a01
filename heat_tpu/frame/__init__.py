"""heat_tpu.frame — columnar groupby / join / filter on the shuffle engine.

A :class:`Frame` is a thin dict of named, equal-length, co-sharded
split-0 DNDarray columns. Its verbs — ``groupby(key).agg(...)``,
``value_counts``, ``join``, ``filter`` — all follow one shape: *local
sort and scan of equal keys per shard → ONE bounded bucketed exchange per operand →
local merge*, built on the sample-sort splitter election and the
``bucket_move`` collective (see :mod:`heat_tpu.frame._shuffle` for the
engine and :mod:`heat_tpu.parallel.flatmove` for the exchange). There is
no per-key traffic at any cardinality, partition decisions are
replicated (lockstep-clean at ws>1), and warm repeats dispatch cached
executables: 0 traces, 0 compiles.

Streaming: :class:`heat_tpu.stream.StreamingGroupBy` folds chunks with
the same associative statistics, so bounded-memory groupby over a
``ChunkIterator`` shares this module's aggregation contract.
"""
from ._shuffle import SHUFFLE_STATS
from .frame import Frame
from .groupby import AGGS, FrameGroupBy

__all__ = ["Frame", "FrameGroupBy", "AGGS", "SHUFFLE_STATS"]
