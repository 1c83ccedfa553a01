"""The program's phases in a trace: ``ht.phase:<name>`` scopes, read from the device plane itself.

Inside a compiled program the program under test names its parts with
``jax.named_scope("ht.phase:<name>")``. A scope is entered while the program is
traced and costs nothing afterwards; the compiler hands it on in every
operation's metadata, and the profiler writes that metadata beside the device
plane's events: ``tf_op`` (the name stack and the primitive,
``jit(frame_join)/ht.phase:sort/sort:``), ``source`` (file and line),
``bytes_accessed``. ``jax.profiler.ProfileData`` shows an event's name, start
and duration and none of that, so this module walks the ``XSpace`` protocol
buffer itself, with the standard library alone: neither TensorFlow nor xprof
may be assumed where the benchmark runs.

* ``walk(path)`` gives every device plane's "XLA Ops" events as ``Op``: program,
  operation, start, end, ``tf_op``, ``source``, ``bytes_accessed``.
* The phase of an operation is the last ``ht.phase:`` component of its
  ``tf_op`` (nested scopes: the innermost names it). What the compiler put in
  itself has no ``tf_op``: a copy takes the phase of the operation it is nested
  in (a loop's body), and a ``while`` that stands in nothing, which a TPU trace
  gives a ``source`` and no ``tf_op``, takes the phase that the operations in it
  agree on. Everything else is *unphased*.
* Times are self times under ``xplane.self_times``' rule (a ``while`` less its
  body), so the phases and the unphased rest add up to what the operations
  cover, and no moment is counted twice.
* ``of(run)`` gives seconds a call by phase for a traced run of the benchmark:
  the same window and ``clock_shift_s`` as ``run.trace``, averaged over the
  device planes, divided by the annotated calls. None where no operation in the
  window carries a scope: a program written before the scopes were, or one
  served from a compile cache that was (a scope is no part of the cache's key).

By hand, on a trace of any program: ``python benchmarks/chip/harness/phases.py <trace.xplane.pb>``.
"""
from __future__ import annotations

import glob
import os
import re
import sys
import time
from dataclasses import dataclass, field

if __package__:
    from . import manifest, xplane
else:  # run by its path: the package is this file's directory
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from harness import manifest, xplane

SCOPE = re.compile(r"(?:^|/)ht\.phase:([^/:]+)")
TRACES = os.path.join(manifest.ROOT, ".bench_trace")  # where run.py writes a traced run's profile
_PROGRAM = re.compile(r"^(.*)\((\d+)\)$")  # "jit_name(123456)"


# ---- the wire format (https://protobuf.dev/programming-guides/encoding/): what XSpace needs of it
def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(number, value)`` of each field of one message: a varint as an int, a length-delimited
    field as a slice of ``buf``, a fixed one as its 8 or 4 bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind} at byte {i}: not an XSpace")
        if i > n:
            raise ValueError("a field runs past its message's end: the file is cut short")
        yield key >> 3, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >> 63 else value


def _text(raw) -> str:
    return bytes(raw).decode("utf-8", "replace")


def _map_entry(buf) -> tuple:
    """A ``map<int64, Message>`` entry: its key, and its value still encoded."""
    key, value = 0, b""
    for number, v in _fields(buf):
        if number == 1:
            key = _signed(v)
        elif number == 2:
            value = v
    return key, value


def _stat(buf, stat_names: dict) -> tuple:
    """An ``XStat`` as ``(name, value)``: str_value (5), uint64 (3), int64 (4), a ref_value (7)
    looked up; a double or bytes value as None."""
    name, value = None, None
    for number, v in _fields(buf):
        if number == 1:
            name = stat_names.get(_signed(v))
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number == 5:
            value = _text(v)
        elif number == 7:
            value = stat_names.get(v)
    return name, value


@dataclass
class Op:
    """One event of a device plane's "XLA Ops" line, with what its metadata says of it."""
    program: str  # the jit name of the program it ran in, "" where the plane does not say
    name: str  # the operation as the trace prints it: its HLO line
    start: float  # seconds on the trace's clock, as ``xplane.reduce`` gives them before its shift
    end: float
    tf_op: str | None
    source: str | None
    bytes_accessed: int | None


def _plane(buf) -> tuple:
    """``(name, lines, event_metadata, stat_metadata)`` of an ``XPlane``, the three still encoded."""
    name, lines, events, stats = "", [], [], []
    for number, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            lines.append(v)
        elif number == 4:
            events.append(v)
        elif number == 5:
            stats.append(v)
    return name, lines, events, stats


def _line(buf) -> tuple:
    """``(name, timestamp_ns, [(metadata_id, offset_ps, duration_ps)])`` of an ``XLine``."""
    name, timestamp_ns, events = "", 0, []
    for number, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            timestamp_ns = _signed(v)
        elif number == 4:
            meta = offset = duration = 0
            for n2, v2 in _fields(v):
                if n2 == 1:
                    meta = _signed(v2)
                elif n2 == 2:
                    offset = _signed(v2)
                elif n2 == 3:
                    duration = _signed(v2)
            events.append((meta, offset, duration))
    return name, timestamp_ns, events


def walk(path: str) -> list:
    """``[(plane name, [Op, ...])]`` of every device plane in the trace at ``path`` that has an
    "XLA Ops" line, the operations in the line's order."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    planes = []
    for number, raw in _fields(space):
        if number != 1:
            continue
        name, lines, event_entries, stat_entries = _plane(raw)
        if not xplane._DEVICE_PLANE.match(name):
            continue
        lines = {ln[0]: ln for ln in map(_line, lines)}
        if xplane.OPS not in lines:
            continue
        stat_names = {}
        for entry in stat_entries:
            key, value = _map_entry(entry)
            stat_names[key] = next((_text(v) for n, v in _fields(value) if n == 2), "")
        metadata = {}  # id -> (name, {stat name: value})
        for entry in event_entries:
            key, value = _map_entry(entry)
            text, stats = "", {}
            for n, v in _fields(value):
                if n == 2:
                    text = _text(v)
                elif n == 5:
                    stat, found = _stat(v, stat_names)
                    stats[stat] = found
            metadata[key] = (text, stats)
        programs = {}  # program_id -> jit name, from the plane's "XLA Modules" events
        for meta, _, _ in lines.get(xplane.MODULES, ("", 0, []))[2]:
            named = _PROGRAM.match(metadata.get(meta, ("", {}))[0])
            if named:
                programs[int(named.group(2))] = named.group(1)
        _, timestamp_ns, events = lines[xplane.OPS]
        ops = []
        for meta, offset_ps, duration_ps in events:
            text, stats = metadata.get(meta, ("", {}))
            # whole nanoseconds, as jax.profiler.ProfileData cuts them: a time read here is the
            # time xplane.reduce reads for the same operation, to the last digit
            start_ns = float(timestamp_ns + offset_ps // 1000)
            ops.append(Op(program=programs.get(stats.get("program_id"), ""), name=text,
                          start=start_ns * 1e-9, end=(start_ns + float(duration_ps // 1000)) * 1e-9,
                          tf_op=stats.get("tf_op"), source=stats.get("source"),
                          bytes_accessed=stats.get("bytes_accessed")))
        planes.append((name, ops))
    return planes


# ---- from operations to phases
def phase_of(tf_op: str | None) -> str | None:
    """The innermost ``ht.phase:<name>`` of a name stack, None where it has none."""
    found = SCOPE.findall(tf_op or "")
    return found[-1] if found else None


def attribute(ops) -> list:
    """``(op, self seconds, phase or None)`` of each of one plane's operations, properly nested."""
    order = sorted(range(len(ops)), key=lambda k: (ops[k].start, -ops[k].end))
    parent, inside, end = {}, {k: 0.0 for k in order}, {}
    stack = []
    for k in order:
        while stack and ops[k].start >= end[stack[-1]]:
            stack.pop()
        end[k] = min(ops[k].end, end[stack[-1]]) if stack else ops[k].end
        if stack:
            parent[k] = stack[-1]
            inside[stack[-1]] += end[k] - ops[k].start
        stack.append(k)
    agreed = {}  # an operation with no tf_op and nothing around it: the phases of what it holds
    for k in order:
        if ops[k].tf_op:
            top = k
            while top in parent:
                top = parent[top]
            if top != k and not ops[top].tf_op:
                agreed.setdefault(top, set()).add(phase_of(ops[k].tf_op))
    phase = {}
    for k in order:  # a parent stands before what it holds
        if ops[k].tf_op:
            phase[k] = phase_of(ops[k].tf_op)
        elif k in parent:
            phase[k] = phase[parent[k]]
        else:
            held = agreed.get(k, set())
            phase[k] = next(iter(held)) if len(held) == 1 else None
    return [(ops[k], (end[k] - ops[k].start) - inside[k], phase[k]) for k in order]


@dataclass
class Phases:
    calls: int  # what "a call" divides by
    s_per_call: dict  # phase -> device seconds a call, averaged over the device planes
    unphased_s_per_call: float
    by_program: dict = field(default_factory=dict)  # program -> {phase or None: seconds a call}
    unphased_ops: list = field(default_factory=list)  # [label, source, seconds a call], largest first

    def ms(self, phase: str) -> float | None:
        """Milliseconds a call in ``phase``; None where no operation of the window was in it."""
        seconds = self.s_per_call.get(phase)
        return None if seconds is None else seconds * 1e3


def reduce(planes, window: tuple, shift: float, calls: int) -> Phases | None:
    """The phases of what ``walk`` gave, for the operations that start inside ``window`` once
    ``shift`` is added to the device's clock; None where none of them carries a scope."""
    lo, hi = window
    share = 1.0 / (len(planes) * calls) if planes and calls else 0.0
    phased, unphased, by_program, loose = {}, 0.0, {}, {}
    for _, ops in planes:
        for op, seconds, phase in attribute(ops):
            if not lo <= op.start + shift < hi:
                continue
            seconds *= share
            table = by_program.setdefault(op.program, {})
            table[phase] = table.get(phase, 0.0) + seconds
            if phase is None:
                unphased += seconds
                key = (xplane.op_label(op.name), op.source or "")
                loose[key] = loose.get(key, 0.0) + seconds
            else:
                phased[phase] = phased.get(phase, 0.0) + seconds
    if not phased:
        return None
    largest = sorted(loose.items(), key=lambda kv: -kv[1])
    return Phases(calls=calls, s_per_call=phased, unphased_s_per_call=unphased, by_program=by_program,
                  unphased_ops=[[label, source, seconds] for (label, source), seconds in largest])


def newest_trace(directory: str = TRACES) -> str | None:
    """The newest ``*.xplane.pb`` under the checkout's ``.bench_trace/``: the traced run's own."""
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def of(run, path: str | None = None) -> Phases | None:
    """The phases of a traced run, computed once a trace (``run.trace`` is a
    ``harness.xplane.Trace``, which carries no path: the file is the newest under
    ``.bench_trace/`` unless ``path`` names it); None where there is no trace (a rehearsal)
    or no operation of the window carries an ``ht.phase:`` scope."""
    trace = run.trace
    if trace is None:
        return None
    if "_ht_phases" not in trace.__dict__:
        path = path or newest_trace()
        trace.__dict__["_ht_phases"] = None if path is None else reduce(
            walk(path), trace.window, trace.clock_shift_s, len(trace.calls))
    return trace.__dict__["_ht_phases"]


# ---- for people
def table(found: Phases | None, programs: dict | None = None, out=None) -> None:
    """Milliseconds a call by program and phase; with ``programs`` (program -> device seconds a call, as
    ``Trace.module_s_per_call`` gives them) also what of a program no operation covers."""
    if found is None:
        print("no operation carries an ht.phase: scope (a program without scopes, or an executable from a compile "
              "cache older than they are)", file=out)
        return

    def rows(phases: dict, whole: float | None) -> None:
        for phase, seconds in sorted(phases.items(), key=lambda kv: (kv[0] is None, -kv[1])):
            print(f"    {'ht.phase:' + phase if phase else '(unphased)':<22}{seconds * 1e3:12.3f}", file=out)
        if whole is not None:
            print(f"    {'(between operations)':<22}{(whole - sum(phases.values())) * 1e3:12.3f}", file=out)

    print(f"milliseconds a call ({found.calls}), self times, averaged over the device planes", file=out)
    for program, phases in sorted(found.by_program.items(), key=lambda kv: -sum(kv[1].values())):
        print(f"{program or '(no program named)'}  {sum(phases.values()) * 1e3:.3f}", file=out)
        rows(phases, (programs or {}).get(program))
    everything = {**found.s_per_call, None: found.unphased_s_per_call}
    print(f"all programs  {sum(everything.values()) * 1e3:.3f}", file=out)
    rows(everything, sum(programs.values()) if programs else None)
    print("the largest unphased operations", file=out)
    for label, source, seconds in found.unphased_ops[:10]:
        print(f"    {seconds * 1e3:10.3f}  {label}  {source or '(the compiler put it in: no source)'}", file=out)


def main(argv) -> int:
    if len(argv) != 2:
        print(__doc__.strip().splitlines()[-1].strip(), file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    planes = walk(argv[1])
    walked = time.perf_counter() - t0
    trace = xplane.reduce(argv[1])
    t0 = time.perf_counter()
    if trace is None:  # no ``bench.call`` annotation: a trace of one's own; the whole of it is one call
        print(f"no {xplane.ANNOTATION} annotation: the whole trace counts as one call")
        found, programs = reduce(planes, (float("-inf"), float("inf")), 0.0, 1), None
    else:
        found, programs = reduce(planes, trace.window, trace.clock_shift_s, len(trace.calls)), trace.module_s_per_call()
    print(f"{sum(len(ops) for _, ops in planes)} operations on {len(planes)} device planes, walked in {walked:.2f} s, "
          f"reduced in {time.perf_counter() - t0:.2f} s")
    table(found, programs)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
