"""Device time a call spends deciding where each row goes and grouping rows by destination: the
splitter election (``jit_frame_elect``) and each side's partition program (``jit_frame_partition``:
every row's destination, one stable sort by it carrying the row), averaged over the chips. The
moves themselves and a caller's rebalancing copies are not in it. None where the trace holds
neither program (a mesh of one device elects and partitions nothing)."""
NAME, UNIT = "partition_ms.call", "ms"
LAYER, MOVES = "data movement; host", "call_ms.p50"
PROGRAMS = ("jit_frame_elect", "jit_frame_partition")


def read(run):
    if run.trace is None:
        return None
    per_call = run.trace.module_s_per_call()
    found = [per_call[name] for name in PROGRAMS if name in per_call]
    return sum(found) * 1e3 if found else None
