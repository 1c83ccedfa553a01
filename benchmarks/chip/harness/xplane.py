"""From the profiler's ``.xplane.pb`` to busy, idle and per-name device times.

The traced slice is a run of calls, each inside one host annotation
(``ANNOTATION``), which the profiler writes on the host plane on the same
clock as the device's events. Read with ``jax.profiler.ProfileData`` alone.

* A device plane is one named ``/device:<kind>:<n>`` that has an
  ``XLA Modules`` line: one event an executed program. The device is *busy*
  in the union of those events; a program's event also covers the few
  nanoseconds between its own operations.
* ``XLA Ops`` holds the operations inside the programs, nested where one
  contains others (a ``while`` and its body), so per-name times are *self*
  times: an operation's duration less that of the operations inside it.
* The window is first annotation's start to the last one's end; idle is what
  of it the busy union leaves, and each idle gap is named by the shortest host
  event that covers it whole (failing that, that covers its middle).
* The profiler sets the device's clock against the host's only to a
  millisecond or so (a chip trace of this benchmark had every program start
  1.1 ms before the call that launched it). Every call is fenced, so no program
  can lie outside the annotated calls: where the first one starts before the
  window, or the last one ends after it, the device's events are shifted by
  just that much (``clock_shift_s``). Per-call times are totals over the window
  divided by the calls, never cut at a single call's edges, so a shift that is
  left moves no metric, only the naming of sub-millisecond gaps.

Everything is in seconds; nothing here knows what was run.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass

ANNOTATION = "bench.call"
MODULES, OPS = "XLA Modules", "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:(?!CUSTOM)[^:]+:\d+$")
_HASH = re.compile(r"\(\d+\)$")  # "jit_name(123456)" -> "jit_name"
_LAYOUT = re.compile(r"\{[^{}]*\}")
_RESULT_TYPE = re.compile(r"(.*?)\s[\w-]+\(")  # what stands before the opcode


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def op_name(text: str) -> str:
    """The trace prints an operation as its HLO line; its name is what stands before `` = ``."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_label(text: str, width: int = 96) -> str:
    """Name and result type, layouts dropped: enough to tell one ``fusion.N`` from another."""
    name, _, rest = text.partition(" = ")
    kind = _RESULT_TYPE.match(_LAYOUT.sub("", rest))
    return (name.lstrip("%") + (" " + kind.group(1) if kind else ""))[:width]


def union(intervals) -> list:
    """Sorted, merged ``(start, end)`` pairs."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def clipped_length(merged, lo: float, hi: float) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def self_times(events) -> list:
    """``(text, self seconds, start)`` of each ``(text, start, end)``, for properly nested events."""
    out, stack = [], []  # stack of [text, start, end, seconds covered by children]

    def close():
        text, s, e, inner = stack.pop()
        out.append((text, (e - s) - inner, s))
        if stack:
            stack[-1][3] += e - s

    for text, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and s >= stack[-1][2]:
            close()
        stack.append([text, s, min(e, stack[-1][2]) if stack else e, 0.0])
    while stack:
        close()
    return out


@dataclass
class Trace:
    calls: list  # (start, end) of each annotated call, seconds on the trace's clock
    devices: list  # per device plane: {"name", "modules": [(name, s, e)], "ops": [(text, s, e)], "busy": merged}
    host: list  # (name, start, end) of every host event but the annotation
    clock_shift_s: float = 0.0  # what was added to the device's timestamps (see the module's notes)

    @property
    def window(self) -> tuple:
        return (self.calls[0][0], self.calls[-1][1])

    # ---- the contract's device object
    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Busy seconds inside the window, averaged over the device planes."""
        return sum(clipped_length(d["busy"], *self.window) for d in self.devices) / len(self.devices)

    # ---- per call: totals over the window, averaged over the calls (and over the devices)
    def device_s_per_call(self) -> float:
        return self.busy_s / len(self.calls)

    def span_s_per_call(self) -> float:
        return sum(e - s for s, e in self.calls) / len(self.calls)

    def op_s_per_call(self, names) -> float:
        """Device seconds of the operations whose name is one of ``names`` or ``<name>.<n>``."""
        if not names:
            return 0.0
        match = re.compile(r"^(?:%s)(?:\.\d+)?$" % "|".join(re.escape(n) for n in names))
        lo, hi = self.window
        total = sum(e - s for d in self.devices for text, s, e in d["ops"]
                    if lo <= s < hi and match.match(op_name(text)))
        return total / (len(self.devices) * len(self.calls))

    def module_s_per_call(self) -> dict:
        lo, hi = self.window
        out = {}
        for d in self.devices:
            for name, s, e in d["modules"]:
                if lo <= s < hi:
                    out[name] = out.get(name, 0.0) + (e - s) / (len(self.devices) * len(self.calls))
        return out

    # ---- the breakdown
    def top_ops(self, n: int = 10) -> list:
        """``[label, self seconds a call]`` of the first device's operations, largest first."""
        lo, hi = self.window
        acc = {}
        for text, secs, start in self_times(self.devices[0]["ops"]):
            if lo <= start < hi:
                acc[op_label(text)] = acc.get(op_label(text), 0.0) + secs / len(self.calls)
        return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10, least_s: float = 1e-6) -> list:
        """``[host event, idle seconds a call]`` of the first device, by the host event over each gap."""
        lo, hi = self.window
        edges = [lo] + [t for s, e in self.devices[0]["busy"] for t in (max(s, lo), min(e, hi)) if e > lo and s < hi] + [hi]
        acc = {}
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 - g0 < least_s:
                continue
            mid = 0.5 * (g0 + g1)
            whole = [(e - s, name) for name, s, e in self.host if s <= g0 and e >= g1]
            over = whole or [(e - s, name) for name, s, e in self.host if s <= mid <= e]
            name = min(over)[1] if over else "(no host event)"
            acc[name] = acc.get(name, 0.0) + (g1 - g0) / len(self.calls)
        return [[k[:96], v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def reduce(path: str, annotation: str = ANNOTATION) -> Trace | None:
    """The trace at ``path``, or None where it holds no annotated call or no device plane."""
    from jax.profiler import ProfileData

    ns = 1e-9
    calls, host, devices = [], [], []
    for plane in ProfileData.from_file(path).planes:
        lines = {line.name: line for line in plane.lines}
        if _DEVICE_PLANE.match(plane.name) and MODULES in lines:
            modules = [(_HASH.sub("", ev.name), ev.start_ns * ns, (ev.start_ns + ev.duration_ns) * ns)
                       for ev in lines[MODULES].events]
            ops = [(ev.name, ev.start_ns * ns, (ev.start_ns + ev.duration_ns) * ns)
                   for ev in lines[OPS].events] if OPS in lines else []
            devices.append({"name": plane.name, "modules": modules, "ops": ops,
                            "busy": union((s, e) for _, s, e in modules)})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    span = (ev.start_ns * ns, (ev.start_ns + ev.duration_ns) * ns)
                    if ev.name == annotation:
                        calls.append(span)
                    else:
                        host.append((ev.name, *span))
    if not calls or not devices:
        return None
    calls.sort()
    shift = clock_shift(calls, devices)
    if shift:
        for d in devices:
            d["modules"] = [(n, s + shift, e + shift) for n, s, e in d["modules"]]
            d["ops"] = [(n, s + shift, e + shift) for n, s, e in d["ops"]]
            d["busy"] = [[s + shift, e + shift] for s, e in d["busy"]]
    return Trace(calls=calls, devices=devices, host=host, clock_shift_s=shift)


def clock_shift(calls, devices) -> float:
    """Seconds to add to the device's timestamps so that no program lies outside the annotated calls."""
    first = min(s for d in devices for s, _ in d["busy"])
    last = max(e for d in devices for _, e in d["busy"])
    early, late = calls[0][0] - first, last - calls[-1][1]
    return early if early > 0 else -late if late > 0 else 0.0
