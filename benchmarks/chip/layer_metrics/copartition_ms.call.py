"""Device time a call spends bringing equal keys of two tables onto one chip: the splitter election,
each side's partition program and the bucket moves of every column. On one chip the tables are
together already, and this is what the call pays all the same. None where the trace holds none."""
NAME, UNIT = "copartition_ms.call", "ms"
LAYER, MOVES = "data movement; host", "call_ms.p50"
PROGRAMS = ("jit_frame_elect", "jit_frame_partition", "jit__ragged_kernel")


def read(run):
    if run.trace is None:
        return None
    per_call = run.trace.module_s_per_call()
    found = [per_call[name] for name in PROGRAMS if name in per_call]
    return sum(found) * 1e3 if found else None
