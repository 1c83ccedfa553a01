"""The set-up clock, the fence and the measured window.

One client in a closed loop: the next call starts when the last one's result
is ready on the device and has been dropped. A traffic file says which of the
driver's functions make up the sequence of calls and how long a slice of the
window a traced run records.
"""
from __future__ import annotations

import math
import os
import time


def seconds_since_process_start() -> float:
    """Wall seconds since the kernel started this process, interpreter start-up included."""
    try:
        with open("/proc/self/stat", encoding="ascii") as fh:
            start_ticks = int(fh.read().rsplit(")", 1)[1].split()[19])  # field 22, starttime
        started = start_ticks / os.sysconf("SC_CLK_TCK")
        return time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError, AttributeError):
        raise SystemExit("cannot read this process's start time from /proc/self/stat: setup_s would leave out the interpreter's start")


def fence(result) -> None:
    """Block until every device array in ``result`` (DNDarrays unwrapped) is ready."""
    import jax

    leaves = jax.tree_util.tree_leaves(result, is_leaf=lambda x: hasattr(x, "larray"))
    jax.block_until_ready([getattr(leaf, "larray", leaf) for leaf in leaves])


def fenced_call(fn, state):
    """``fn(state)`` to the fence: (result, seconds on the host clock)."""
    t0 = time.perf_counter()
    result = fn(state)
    fence(result)
    return result, time.perf_counter() - t0


def traced_calls(traffic: dict, call_s: float) -> int:
    """How many calls a traced run records: the slice's length over one call's time, within its limits."""
    rule = traffic["trace_slice"]
    return max(rule["min_calls"], min(rule["max_calls"], math.ceil(rule["seconds"] / max(call_s, 1e-9))))


class Window:
    """What the measured window saw: each call's fenced seconds, and the first failure if any."""

    def __init__(self):
        self.call_s = []
        self.traced = 0  # calls inside the traced slice
        self.failed = 0
        self.error = None
        self.seconds = 0.0
        self.last = None  # the result of the window's last call, kept for the reference check

    @property
    def attempted(self) -> int:
        return len(self.call_s) + self.failed


def run_window(traffic: dict, driver, state, seconds: float, tracer=None) -> Window:
    """Repeat the traffic's sequence of calls for ``seconds``. With a
    ``tracer`` (a context manager that yields a factory of per-call
    annotations) the window opens with one plain call, whose time sizes the
    slice, then the traced slice, then goes on untraced. A call that raises
    ends the window: it is counted as failed and nothing is retried. Every
    result is dropped before the next call but the last one's, which is kept
    for the check: no call is made for the check alone."""
    if traffic["loop"] != "closed" or traffic["clients"] != 1:
        raise SystemExit(f"traffic loop {traffic['loop']!r} with {traffic['clients']} clients: "
                         "only a closed loop of one client is built")
    fns = [getattr(driver, name) for name in traffic["calls"]]
    win = Window()
    t0 = time.perf_counter()

    def one() -> bool:
        win.last = None  # no result outlives the next call's start
        try:
            result, dt = fenced_call(fns[win.attempted % len(fns)], state)
        except Exception as e:  # the boundary that must report: the failure goes on the result line
            win.failed += 1
            win.error = f"{type(e).__name__}: {e}"
            return False
        win.call_s.append(dt)
        if time.perf_counter() - t0 >= seconds:  # nothing follows it in the window: it is the one to check
            win.last = result
        return True

    ok = True
    if tracer is not None and (ok := one()):
        slice_calls = traced_calls(traffic, win.call_s[0])
        with tracer() as annotate:
            while ok and win.traced < slice_calls:
                with annotate():
                    ok = one()
                win.traced += ok
    while ok and (time.perf_counter() - t0 < seconds or win.last is None):
        ok = one()
    win.seconds = time.perf_counter() - t0
    return win
