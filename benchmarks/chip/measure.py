#!/usr/bin/env python3
"""Make a benchmark's proof runs and read their spread, as the contract sets the bounds.

    python benchmarks/chip/measure.py run --out chiprun_out/proof [--workloads a,b] \
        [--sets 2] [--runs 6] [--seconds S] [--traced 1] [--seed0 2500000000]
    python benchmarks/chip/measure.py spread chiprun_out/proof

``run`` starts ``run.py`` once a run, each a new process (this one never touches
JAX, so the chip is the child's), the runs of one cell together, every run of a
set with another seed and both sets with the same seeds; then ``--traced`` runs
with ``--trace 1``. It keeps each run's two output lines in
``<out>/<cell>.jsonl`` and what it wrote to stderr in ``<out>/<cell>.err``.
``spread`` prints, for each cell and end-to-end metric, each set's median and
its spread (the distance between the first and third quartile as Python's
``statistics.quantiles(values, n=4)`` gives them, over the median), the wider
of the two, and the second median against the first.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_sets(args) -> int:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        manifest = json.load(fh)
    cells = args.workloads.split(",") if args.workloads else [w["name"] for w in manifest["workloads"]]
    seconds = args.seconds if args.seconds is not None else manifest["run_seconds"]
    os.makedirs(args.out, exist_ok=True)
    bad = 0
    for cell in cells:
        plan = [(s, r, 0) for s in range(args.sets) for r in range(args.runs)] + [(args.sets, r, 1) for r in range(args.traced)]
        for set_no, run_no, trace in plan:
            cmd = [*manifest["command"], "--workload", cell, "--seed", str(args.seed0 + run_no),
                   "--seconds", str(seconds), "--trace", str(trace)] + (["--rehearse"] if args.rehearse else [])
            t0 = time.time()
            with open(os.path.join(args.out, cell + ".err"), "a", encoding="utf-8") as err:
                done = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE, stderr=err, text=True)
            lines = [ln for ln in done.stdout.splitlines() if ln.startswith("{")]
            record = {"cell": cell, "set": set_no, "run": run_no, "trace": trace, "rc": done.returncode,
                      "wall_s": time.time() - t0, "lines": [json.loads(ln) for ln in lines[-2:]]}
            with open(os.path.join(args.out, cell + ".jsonl"), "a", encoding="utf-8") as out:
                out.write(json.dumps(record) + "\n")
            last = record["lines"][-1] if record["lines"] else {}
            print(cell, "set", set_no, "run", run_no, "trace", trace, "rc", done.returncode,
                  f"{record['wall_s']:.1f}s", json.dumps(last.get("metrics")), "correct", last.get("correct"), flush=True)
            bad += done.returncode != 0 or not last.get("correct")
    return 1 if bad else 0


def spread_of(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def spread(args) -> int:
    for path in sorted(glob.glob(os.path.join(args.dir, "*.jsonl"))):
        with open(path, encoding="utf-8") as fh:
            records = [json.loads(ln) for ln in fh]
        runs = [r for r in records if not r["trace"] and r["rc"] == 0 and r["lines"]]
        sets = sorted({r["set"] for r in runs})
        names = sorted({m for r in runs for m in r["lines"][-1]["metrics"]})
        print(f"== {records[0]['cell']}: {len(runs)} runs in sets {sets}, "
              f"all correct: {all(r['lines'][-1]['correct'] for r in runs)}")
        for name in names:
            per_set = []
            for s in sets:
                values = [r["lines"][-1]["metrics"][name]["value"] for r in runs if r["set"] == s]
                if name == "setup_s":  # the first run of a side compiles: recorded apart, held to no bound
                    values = values[1:] if s == sets[0] else values
                if len(values) >= 2:
                    per_set.append((statistics.median(values), spread_of(values), min(values), max(values), len(values)))
            text = "  ".join(f"set{i}: median {m:.6g} spread {100 * sp:.3f}% [{lo:.6g}..{hi:.6g}] n={n}"
                             for i, (m, sp, lo, hi, n) in enumerate(per_set))
            widest = max((sp for _, sp, *_ in per_set), default=float("nan"))
            drift = (per_set[1][0] / per_set[0][0] - 1) if len(per_set) > 1 else float("nan")
            print(f"  {name:14s} {text}  widest {100 * widest:.3f}%  second/first {100 * drift:+.3f}%")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    r = sub.add_parser("run")
    r.add_argument("--out", required=True)
    r.add_argument("--workloads", default="")
    r.add_argument("--sets", type=int, default=2)
    r.add_argument("--runs", type=int, default=6)
    r.add_argument("--traced", type=int, default=1)
    r.add_argument("--seconds", type=float, default=None)
    r.add_argument("--seed0", type=int, default=2_500_000_000)
    r.add_argument("--rehearse", action="store_true", help="pass --rehearse on: tries this tool without a chip")
    s = sub.add_parser("spread")
    s.add_argument("dir")
    args = ap.parse_args(argv)
    return run_sets(args) if args.what == "run" else spread(args)


if __name__ == "__main__":
    sys.exit(main())
