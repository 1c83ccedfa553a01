"""Device time a call spends inside collective operations of any program, averaged over the chips:
the bucket moves' and the rebalancing copies' ``collective-permute``s, the election's and the bucket
matrices' ``all-gather``s, the duplicate flag's ``all-reduce``, in their plain and their
``-start`` / ``-done`` forms (a ``-done`` is the wait for the transfer: time the chip spends in
the exchange, whether or not another operation overlaps it). None where the trace holds no such
operation (one chip: the compiler drops a collective over a mesh of one)."""
NAME, UNIT = "collective_ms.call", "ms"
LAYER, MOVES = "data movement; host", "call_ms.p50"
KINDS = ("collective-permute", "all-gather", "all-reduce", "all-to-all", "reduce-scatter", "collective-broadcast")
OPERATIONS = tuple(kind + form for kind in KINDS for form in ("", "-start", "-done"))


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.op_s_per_call(OPERATIONS)
    return seconds * 1e3 if seconds else None
