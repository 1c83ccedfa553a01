"""Fault-injection hook points (consumed by :mod:`heat_tpu.resilience.chaos`).

Production code calls :func:`fault_point` at the places where real
deployments fail — file opens/writes/commits in :mod:`heat_tpu.core.io`,
shard assembly and host allgathers in :mod:`heat_tpu.core.communication`,
checkpoint shard serialization — and the call is a no-op unless an
injector has been installed. ``resilience.chaos(...)`` installs a seeded
injector for the duration of a ``with`` block, which lets every recovery
path (retry, atomic rename, checksum verification) be exercised
deterministically on CPU.

This module imports nothing of ``heat_tpu`` on purpose: ``core`` must not
import ``resilience`` at module scope (resilience sits above core), so the
registry lives down here and chaos reaches down to install itself.

The same hook carries the program's spans (:func:`span`,
:func:`public_call`, :func:`fetch`): a span is a
``jax.profiler.TraceAnnotation``, which the profiler writes on the host
plane on the clock of the device's events while a trace is being taken
(``ht.utils.profiling.trace``) and which is one object and one flag test
otherwise. Inside a compiled program a span is a :func:`phase`: a name
scope, entered while the program is traced and carried by every operation
of it from then on. Nothing else stores, exports or switches them.
"""
from __future__ import annotations

import functools as _functools
import itertools as _itertools
import threading as _threading
from typing import Callable, Dict, Optional

import jax as _jax

# the active injector: fn(name, ctx) -> None, may raise to simulate a
# fault and may mutate ``ctx`` values in place (e.g. corrupt a byte
# buffer). None means fault injection is off (the production state).
_INJECTOR: Optional[Callable[[str, Dict], None]] = None


def set_injector(injector: Optional[Callable[[str, Dict], None]]):
    """Install (or with ``None`` remove) the process-wide fault injector.

    Returns the previous injector so callers can restore it (the chaos
    context manager nests correctly).
    """
    global _INJECTOR
    prev = _INJECTOR
    _INJECTOR = injector
    return prev


def get_injector() -> Optional[Callable[[str, Dict], None]]:
    return _INJECTOR


def fault_point(name: str, **ctx) -> Dict:
    """Declare a fault-injection site.

    ``name`` is a dotted site id (``"io.open"``, ``"io.commit"``,
    ``"collective.assemble"``, ``"checkpoint.shard_bytes"``,
    ``"supervisor.step"`` — the last fires before every supervised step,
    the injection point for step-level faults including simulated device
    loss). The installed injector may raise (OSError, TimeoutError, ...)
    to simulate a failure at this site, or mutate mutable ``ctx`` entries
    (e.g. a ``bytearray`` payload) to simulate corruption. Returns ``ctx``
    so call sites can read mutated values back.
    """
    if _OBSERVERS:
        # the existing fault sites double as instrumentation points: every
        # collective/io/checkpoint site is reported to passive observers
        # (see ``observe`` below) before any injected fault can fire
        for fn in tuple(_OBSERVERS):
            fn(name, ctx)
    if _INJECTOR is not None:
        _INJECTOR(name, ctx)
    return ctx


# the active deadline runner: fn(label, callable, args, kwargs) -> result.
# None (the production default) means blocking host-side paths run inline
# with zero overhead; ``resilience.watchdog.deadlines(...)`` installs a
# runner that bounds each labeled call and raises CollectiveTimeout
# instead of hanging forever. Same layering trick as the injector: the
# slot lives down here so core never imports resilience.
_DEADLINE_RUNNER = None


def set_deadline_runner(runner):
    """Install (or with ``None`` remove) the process-wide deadline runner.

    Returns the previous runner so contexts nest correctly.
    """
    global _DEADLINE_RUNNER
    prev = _DEADLINE_RUNNER
    _DEADLINE_RUNNER = runner
    return prev


def get_deadline_runner():
    return _DEADLINE_RUNNER


def guarded_call(label: str, fn, *args, **kwargs):
    """Run a blocking host-side operation under the active deadline runner.

    ``label`` names the operation in any timeout raised
    (``"collective.assemble"``, ``"flatmove.ragged"``, ...). With no
    runner installed this is a direct call — the hot path pays one global
    read and nothing else.
    """
    if _DEADLINE_RUNNER is None:
        return fn(*args, **kwargs)
    return _DEADLINE_RUNNER(label, fn, args, kwargs)


# trace-safe mode: a PER-THREAD depth counter armed by the lazy-fusion
# subsystem (:mod:`heat_tpu.core.lazy`) while it replays DNDarray ops under
# a jax trace (``jax.eval_shape`` metadata probes and the fused-program
# ``jax.jit``). Two effects, both consulted from core with one integer
# read: placement helpers (``dndarray._place`` / ``_from_ragged``) skip
# ``jax.device_put`` — tracers cannot be placed, shardings are pinned via
# the jit's ``out_shardings`` instead — and host-side data movement
# (``balance_``, ``flatmove.ragged_move``) raises :class:`TraceBarrierError`
# so an op that would need a collective exchange under trace is declined
# at capture time rather than miscompiled. Same layering trick as the
# slots above: the flag lives down here so core never imports the lazy
# package at module scope. The depth is THREAD-LOCAL: a serving
# dispatcher thread replaying a fused program must not flip eager
# client threads into trace-safe mode (and vice versa) — each thread
# carries its own capture/replay state.
_TRACE_SAFE = _threading.local()


class TraceBarrierError(RuntimeError):
    """Raised by host-side data-movement paths entered under trace-safe
    mode — the signal that an op cannot be captured into a fused program
    and must take the eager path instead."""


def enter_trace_safe() -> None:
    _TRACE_SAFE.depth = getattr(_TRACE_SAFE, "depth", 0) + 1


def exit_trace_safe() -> None:
    _TRACE_SAFE.depth = getattr(_TRACE_SAFE, "depth", 0) - 1


def in_trace_safe() -> bool:
    """True while lazy fusion is replaying ops under a jax trace (on the
    CALLING thread; other threads' replays are invisible here)."""
    return getattr(_TRACE_SAFE, "depth", 0) > 0


def trace_barrier(label: str) -> None:
    """Declare a host-side data-movement site that cannot run under a jax
    trace (``"balance_"``, ``"ragged_move"``, ...). No-op in normal eager
    execution; under trace-safe mode raises :class:`TraceBarrierError` so
    the lazy capture layer falls back to eager for the offending op."""
    if getattr(_TRACE_SAFE, "depth", 0) > 0:
        raise TraceBarrierError(
            f"{label} moves data host-side and cannot run under a jax trace"
        )


# passive event observers: fn(event, ctx) -> None, must not raise. Unlike
# the injector (which simulates faults) and the deadline runner (which
# bounds calls), observers only *record*: ``analysis.sanitizer`` registers
# one to attribute cache insertions, host transfers, and collective
# dispatches to a code region, and ``analysis.lockstep`` registers one to
# digest the ORDER of ``collective.*`` sites (observers fire before any
# injected fault, so a chaos-dropped event was recorded first — the
# property the ``lockstep_divergence`` fault kind relies on). Same
# layering trick again — the list lives down here so core never imports
# analysis.
_OBSERVERS = []


def add_observer(fn):
    """Register a process-wide event observer; returns ``fn``."""
    _OBSERVERS.append(fn)
    return fn


def remove_observer(fn):
    """Remove a previously registered observer (no error if absent)."""
    try:
        _OBSERVERS.remove(fn)
    except ValueError:
        pass


def observe(event: str, **ctx) -> None:
    """Report an instrumentation event (``"cache.insert"``,
    ``"host.gather"``, ... — plus the ``"recovery.*"`` family emitted by
    :mod:`heat_tpu.resilience.supervisor`, which its ``RECOVERY_STATS``
    observer counts, and the ``"stream.*"`` family — ``stream.chunk``
    (``rows``, ``nbytes``), ``stream.prefetch_hit``, ``stream.stall``,
    ``stream.overlap`` (``seconds``) — emitted by the chunked pipeline
    layer and folded into ``STREAM_STATS`` by
    :mod:`heat_tpu.stream._stats`). Free when no observer is installed:
    one falsy check on the hot path."""
    if _OBSERVERS:
        for fn in tuple(_OBSERVERS):
            fn(event, ctx)


# spans: the four families a trace of the program shows, named at the
# layer boundaries (docs/PERFORMANCE.md, "Reading a trace of your own
# program"): ``ht.call:<public call>`` around an entry point,
# ``ht.fetch:<site>`` around a device -> host read, ``ht.exchange:<kind>``
# around the host's part of a data movement, on the host plane, and
# ``ht.phase:<name>`` inside a compiled program, on the device's
# (:func:`phase`). ``_CALL`` is the open public
# call of this thread (depth, and the number every span of one request
# carries); like ``_TRACE_SAFE`` it is per thread, so a serving thread's
# calls do not renumber a client's.
_CALL = _threading.local()
_CALL_NUMBERS = _itertools.count(1)


def span(name: str, **attrs):
    """A named region of the profiler's host plane: a context manager
    that records ``name`` (and ``attrs``, plus ``call=<n>`` inside a
    :func:`public_call`) while a trace is being taken and nothing
    otherwise. Public as ``ht.utils.profiling.annotate``."""
    if getattr(_CALL, "depth", 0):
        attrs.setdefault("call", _CALL.n)
    return _jax.profiler.TraceAnnotation(name, **attrs)


def public_call(name: str):
    """Decorator of a public entry point: its body runs inside the span
    ``ht.call:<name>`` with ``call=<n>``. Only the outermost public call
    of a thread draws a new ``n`` from the process-wide counter; one made
    inside it opens a child span with the same ``n``."""
    label = "ht.call:" + name

    def decorate(fn):
        @_functools.wraps(fn)
        def wrapper(*args, **kwargs):
            depth = getattr(_CALL, "depth", 0)
            if not depth:
                _CALL.n = next(_CALL_NUMBERS)
            _CALL.depth = depth + 1
            try:
                with span(label):
                    return fn(*args, **kwargs)
            finally:
                _CALL.depth = depth

        return wrapper

    return decorate


def fetch(x, site: str):
    """Read a device value (an array or a pytree of them) to the host:
    the one way the library does it on a call path. Raises the
    ``host.fetch`` event, which ``COMPILE_STATS["host_syncs"]`` counts,
    and blocks inside the span ``ht.fetch:<site>``."""
    observe("host.fetch", site=site)
    with span("ht.fetch:" + site):
        return _jax.device_get(x)


def phase(name: str):
    """A named part of a compiled program: a context manager for code that
    runs under ``jit``. It is ``jax.named_scope("ht.phase:<name>")``,
    entered when the program is traced and never again, so a cached
    program pays nothing for it; every operation traced inside carries
    the name in its metadata, and a profiler trace shows it on the
    device's plane as the operation's ``tf_op``. Nested phases: the
    innermost names the operation. The scope is no part of the compile
    cache's key: an executable served from a cache filled before a scope
    was written or moved still carries the old names. Public as
    ``ht.utils.profiling.phase``."""
    return _jax.named_scope("ht.phase:" + name)
