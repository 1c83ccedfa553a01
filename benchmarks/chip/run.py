#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, in this process, on the chips JAX finds.

    python benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Brings up JAX and refuses anything but a TPU with the chips the cell asks for
(exit 2, no result line); makes the operands on the device from the seed;
warms up the cell's calls once; repeats them in the traffic's loop for
``--seconds``, each fenced and its result dropped; reads peak memory; checks the
last call's result against the driver's plain reference; prints an ``info`` line and,
last, the result line. ``--trace 0`` prints the cell's end-to-end metrics,
``--trace 1`` traces a slice of the window and prints its per-layer metrics.

``--rehearse`` runs the configuration's ``rehearse_sizes`` on the CPU to try
paths and control flow: the result line then says ``cpu`` and carries no metric.
"""
from __future__ import annotations

import argparse
import contextlib
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, ROOT]  # the harness package; the program, from this checkout

from harness import counters, loop, manifest, report, xplane  # noqa: E402  (none touches JAX on import)

TRACE_DIR = os.path.join(ROOT, ".bench_trace")  # fixed and git-ignored, one directory a cell


def compile_cache_dir(jax) -> str:
    """JAX's persistent cache where JAX_COMPILATION_CACHE_DIR says, else the checkout's
    fixed ``.jax_cache/`` (the path is part of the key); every program is kept, however
    small or quick to compile, so that a cell's second run in a checkout compiles nothing."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class Tracer:
    """The profiler around the traced slice, the counters at its two ends, one annotation a call."""

    def __init__(self, directory: str):
        self.directory = directory
        self.counted = None

    @contextlib.contextmanager
    def __call__(self):
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        jax.profiler.start_trace(self.directory)
        before = counters.snapshot()
        try:
            yield lambda: jax.profiler.TraceAnnotation(xplane.ANNOTATION)
        finally:
            self.counted = counters.delta(before, counters.snapshot())
            jax.profiler.stop_trace()


def per_layer_values(cell, run: report.TracedRun) -> dict:
    """Each of the cell's per-layer metrics from its own reader, ``layer_metrics/<name>.py``."""
    values = {}
    for entry in cell.per_layer:
        reader = manifest.load_module("layer_metrics", entry["name"])
        declared = {"name": reader.NAME, "unit": reader.UNIT, "layer": reader.LAYER, "moves": reader.MOVES}
        listed = {k: entry[k] for k in declared}
        if declared != listed:
            raise SystemExit(f"layer_metrics/{entry['name']}.py declares {declared}, BENCHMARK.json lists {listed}")
        values[entry["name"]] = reader.read(run)
    return values


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="a name of BENCHMARK.json's workloads")
    ap.add_argument("--seed", type=int, default=0, help="seed of the operands")
    ap.add_argument("--seconds", type=float, default=None, help="length of the measured window (default: run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true", help="the configuration's rehearse_sizes, on the CPU, no metric printed")
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed is a whole number, 0 or more")

    cell = manifest.load_cell(args.workload)
    seconds = float(cell.run_seconds if args.seconds is None else args.seconds)
    at = {"start": loop.seconds_since_process_start()}  # each set-up phase's end, seconds since the process started

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if cell.chips > 1 and "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count={cell.chips}".strip()
    import jax

    devices = jax.devices()
    at["jax_and_devices"] = loop.seconds_since_process_start()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != ("cpu" if args.rehearse else "tpu"):
        print(f"run.py: JAX reports platform {platform!r}: a measurement needs a TPU "
              "(a --rehearse run the CPU); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"run.py: {args.workload} asks for {cell.chips} chips, JAX reports {len(devices)}; nothing was run",
              file=sys.stderr)
        return 2
    cache_dir = None if args.rehearse else compile_cache_dir(jax)  # a rehearsal's CPU programs are not worth keeping

    import heat_tpu as ht

    at["import_program"] = loop.seconds_since_process_start()
    comm = ht.MeshCommunication(devices=devices[:cell.chips])
    ht.use_comm(comm)
    config = dict(cell.config)
    if args.rehearse:
        config["sizes"] = {**config["sizes"], **config["rehearse_sizes"]}
    driver = manifest.load_module("drivers", config["driver"])

    state = driver.build(config, args.seed, comm)
    loop.fence(state)
    at["operands"] = loop.seconds_since_process_start()
    first_call_s = 0.0
    for name in dict.fromkeys(cell.traffic["calls"]):  # every call of the mix once: compiles, or loads from the cache
        result, dt = loop.fenced_call(getattr(driver, name), state)
        del result
        first_call_s += dt
    at["first_call"] = setup_s = loop.seconds_since_process_start()
    after_setup = counters.snapshot()

    tracer = Tracer(os.path.join(TRACE_DIR, cell.name)) if args.trace else None
    win = loop.run_window(cell.traffic, driver, state, seconds, tracer)
    counted = {"window": counters.delta(after_setup, counters.snapshot()),
               "traced": tracer.counted if tracer else None}
    used = devices[:cell.chips]
    peaks_read = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in used]
    peak_bytes = None if None in peaks_read else max(peaks_read)

    # outside the window: its last call's result, held to the driver's plain reference
    verdict = {"ok": False, "skipped": "a call failed in the window"}
    if not win.failed:
        verdict = driver.check(state, win.last)
    win.last = None
    window_compiles = counted["window"]["compile"]["backend_compiles"]
    correct = bool(verdict["ok"]) and win.failed == 0 and window_compiles == 0

    device = {"platform": platform, "kind": kind, "count": len(devices), "memory_peak_bytes": peak_bytes}
    work = driver.work(config)
    breakdown, modules = None, None
    if args.trace:
        path = xplane.find_xplane(tracer.directory)
        trace = xplane.reduce(path) if path else None
        if trace is None and not args.rehearse:
            raise SystemExit(f"the trace under {tracer.directory} holds no annotated call or no device plane")
        if trace is not None:
            device.update(busy_s=trace.busy_s, window_s=trace.window_s)
            breakdown = {"device_ops": trace.top_ops(), "idle_gaps": trace.idle_gaps()}
            modules = {"clock_shift_s": trace.clock_shift_s, **trace.module_s_per_call()}
        run = report.TracedRun(
            config=config, chips=cell.chips, work=work,
            peaks=None if args.rehearse else manifest.peaks_for(kind),
            calls=win.traced, counters=counted, trace=trace)
        values, entries = per_layer_values(cell, run), cell.per_layer
    else:
        values = report.end_to_end(setup_s, win.call_s, peak_bytes, cell.traffic["tail_min_calls"])
        entries = cell.end_to_end
    metrics, missing = report.pick(values, entries)

    info = {
        "workload": cell.name, "config": config["name"], "seed": args.seed, "seconds": seconds,
        "trace": args.trace, "rehearse": args.rehearse, "sizes": config["sizes"],
        "window_s": win.seconds, "calls": len(win.call_s), "traced_calls": win.traced,
        "setup_phases_at_s": at, "first_call_s": first_call_s,
        "setup_compiles": after_setup["compile"]["backend_compiles"], "window_compiles": window_compiles,
        "compile_cache_dir": cache_dir, "error": win.error, "reference": verdict,
        "work": work, "device_programs_s_per_call": modules, "metrics_without_value": missing,
    }
    if args.rehearse:  # a CPU's numbers under no device metric's name: the names alone say what was computed
        info["rehearsed_metrics"] = sorted(metrics)
        metrics = {}
    result = {"correct": correct, "attempted": win.attempted, "failed": win.failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    report.emit(info, result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
