"""The program's own spans in a trace, and the device's idle time split by them.

The program under test writes three families of spans on the host plane,
each a profiler annotation on the clock of the device's events:
``ht.call:<entry point>`` around a public call, ``ht.fetch:<site>`` around a
device -> host read, ``ht.exchange:<kind>`` around the host's part of a data
movement. ``of(trace)`` reads them from ``harness.xplane.Trace`` (``host``
holds every host event by name, start and end), nests them by time, and
splits the idle time of the window (what the devices' busy unions leave of
it) into the part an outermost ``ht.call:*`` span covers and the rest: idle
the program causes, and the client's turn-around between calls.

That split needs the device's clock on the host's to better than the gaps
being split, and the profiler gives it to a millisecond or so. The host plane
bounds it: the k-th program of a device starts after the k-th launch
(``LAUNCH``) starts and ends before the k-th completion notice (the first of
``COMPLETIONS`` the trace has one of for each program) starts, and no program
lies outside the window, every call being fenced. The shifts of the device's
clock that break none of this form an interval; its width is ``slack_s``, the
uncertainty of both idle numbers, and the device's events are placed at its
middle before they are intersected (where no shift satisfies all, at the
middle of the least violation, whose size ``slack_s`` then is). Where the
launches or the notices cannot be paired with the programs by count, there is
no placement: ``slack_s`` and both idle numbers are None.

A trace of a program that writes no ``ht.call:*`` span gives ``of() is
None``: every metric read from here is then left out. Seconds throughout.
"""
from __future__ import annotations

from dataclasses import dataclass

from . import xplane

CALL, FETCH, EXCHANGE = "ht.call:", "ht.fetch:", "ht.exchange:"
LAUNCH = "PJRT_LoadedExecutable_Execute"
COMPLETIONS = ("CompleteCallbacks", "tpu::System::Execute=>Done")


def complement(merged, lo: float, hi: float) -> list:
    """What sorted, merged ``merged`` leaves of ``[lo, hi]``."""
    edges = [lo] + [t for s, e in merged if e > lo and s < hi for t in (max(s, lo), min(e, hi))] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def overlap(a, b) -> float:
    """Seconds that two merged lists of intervals share."""
    return sum(xplane.clipped_length(b, s, e) for s, e in a)


def placement(trace) -> tuple | None:
    """``(shift, slack)``: what to add to the device's timestamps as ``trace`` holds them to
    stand at the middle of the shifts the host plane allows, and that interval's width."""
    starts = {wanted: sorted(s for name, s, _ in trace.host if name == wanted) for wanted in (LAUNCH, *COMPLETIONS)}
    launches = starts[LAUNCH]
    lo_w, hi_w = trace.window
    lo, hi = [], []
    for d in trace.devices:
        programs = sorted((s, e) for _, s, e in d["modules"])
        notices = next((starts[c] for c in COMPLETIONS if len(starts[c]) == len(programs)), None)
        if not programs or len(launches) != len(programs) or notices is None:
            return None
        lo += [launch - s for launch, (s, _) in zip(launches, programs)] + [lo_w - programs[0][0]]
        hi += [notice - e for notice, (_, e) in zip(notices, programs)] + [hi_w - programs[-1][1]]
    return 0.5 * (max(lo) + min(hi)), abs(min(hi) - max(lo))


@dataclass
class Spans:
    calls: int  # the harness's annotated calls: what "a call" divides by
    call_s: float  # inside the outermost ht.call:* spans
    fetch_s: float  # inside ht.fetch:* spans
    exchange_s: float  # inside ht.exchange:* spans
    exchanges: int
    self_s: float  # call_s less what the fetch and exchange spans inside those calls cover
    slack_s: float | None
    idle_in_call_s: float | None  # a device's idle seconds under an outermost ht.call:* span, averaged over devices
    idle_outside_call_s: float | None

    def per_call_ms(self, seconds) -> float | None:
        return None if seconds is None else seconds / self.calls * 1e3


def of(trace) -> Spans | None:
    """The window's spans and idle split, computed once a trace; None where the trace is None
    (a rehearsal) or holds no ``ht.call:*`` span (a program that writes none)."""
    if trace is None:
        return None
    if "_ht_spans" not in trace.__dict__:
        trace.__dict__["_ht_spans"] = _read(trace)
    return trace.__dict__["_ht_spans"]


def _read(trace) -> Spans | None:
    lo, hi = trace.window
    mine = [(name, s, e) for name, s, e in trace.host if name.startswith("ht.") and lo <= s < hi]
    outer = xplane.union((s, e) for name, s, e in mine if name.startswith(CALL))  # nested calls merge into their parent
    if not outer:
        return None
    fetch = xplane.union((s, e) for name, s, e in mine if name.startswith(FETCH))
    exchange = xplane.union((s, e) for name, s, e in mine if name.startswith(EXCHANGE))
    call_s = sum(e - s for s, e in outer)
    placed = placement(trace)
    idle_in = idle_out = None
    if placed is not None:
        between = complement(outer, lo, hi)
        idle_in = idle_out = 0.0
        for d in trace.devices:
            idle = complement([(s + placed[0], e + placed[0]) for s, e in d["busy"]], lo, hi)
            idle_in += overlap(idle, outer) / len(trace.devices)
            idle_out += overlap(idle, between) / len(trace.devices)
    return Spans(
        calls=len(trace.calls), call_s=call_s,
        fetch_s=sum(e - s for s, e in fetch), exchange_s=sum(e - s for s, e in exchange),
        exchanges=sum(name.startswith(EXCHANGE) for name, _, _ in mine),
        self_s=call_s - overlap(outer, xplane.union([*fetch, *exchange])),
        slack_s=None if placed is None else placed[1],
        idle_in_call_s=idle_in, idle_outside_call_s=idle_out)
