"""Programs compiled or loaded from the cache inside the measured window
(``COMPILE_STATS["backend_compiles"]``); the harness calls the run incorrect unless it is 0."""
NAME, UNIT = "compiles.window", "count"
LAYER, MOVES = "compiled program (XLA)", "call_ms.p50"


def read(run):
    return run.counters["window"]["compile"]["backend_compiles"]
