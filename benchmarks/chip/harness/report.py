"""The end-to-end metrics, the run handed to the per-layer readers, and the two output lines."""
from __future__ import annotations

import json
import statistics
from dataclasses import dataclass

GiB = float(1 << 30)


def percentile(values, q: float) -> float:
    """Linear interpolation between the order statistics, as ``numpy.percentile`` does it."""
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def end_to_end(setup_s: float, call_s: list, peak_bytes, tail_min_calls: int) -> dict:
    """The harness's own four, by name; a tail only from a window that holds ``tail_min_calls`` calls."""
    out = {"setup_s": setup_s}
    if call_s:
        out["call_ms.p50"] = statistics.median(call_s) * 1e3
        if len(call_s) >= tail_min_calls:
            out["call_ms.p95"] = percentile(call_s, 95) * 1e3
    if peak_bytes is not None:
        out["peak_hbm_GiB"] = peak_bytes / GiB
    return out


@dataclass
class TracedRun:
    """What a per-layer reader may read. ``trace`` is None where no device was traced."""
    config: dict
    chips: int
    work: dict  # the driver's work(config): least flops and bytes of one call, from the shapes
    peaks: dict | None  # this device kind's entry of peaks.json
    calls: int  # calls in the traced slice
    counters: dict  # {"traced": ..., "window": ...}, each {"compile": {...}, "kernel": {...}} deltas
    trace: object  # harness.xplane.Trace


def pick(values: dict, entries: list) -> tuple:
    """(metrics object for the result line, names that had no value): the manifest's entries, in its order."""
    metrics, missing = {}, []
    for m in entries:
        if values.get(m["name"]) is None:
            missing.append(m["name"])
        else:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return metrics, missing


def emit(info: dict, result: dict) -> None:
    """The ``info`` line, then the contract's line, which is the last of standard output."""
    print(json.dumps({"info": info}), flush=True)
    print(json.dumps(result), flush=True)
