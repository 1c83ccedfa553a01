"""Device-to-host fetches a call makes (``COMPILE_STATS["host_syncs"]``), over the traced calls."""
NAME, UNIT = "host_syncs.call", "count/call"
LAYER, MOVES = "public call and DNDarray dispatch", "call_ms.p50"


def read(run):
    if not run.calls:
        return None
    return run.counters["traced"]["compile"]["host_syncs"] / run.calls
