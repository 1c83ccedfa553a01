"""Device time a call spends matching: the program ``jit_frame_join`` (both sides sorted by key, each
left row's match looked up, the matched rows compacted). None where the trace holds no such program."""
NAME, UNIT = "join_match_ms.call", "ms"
LAYER, MOVES = "compiled program (XLA)", "call_ms.p50"
PROGRAM = "jit_frame_join"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.module_s_per_call().get(PROGRAM)
    return None if seconds is None else seconds * 1e3
