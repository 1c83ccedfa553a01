"""Each cell's ``--rehearse`` run, untraced and traced: the contract's last line, the CPU's name on it,
no metric under a device metric's name; and no rehearsal flag, no TPU: no result line."""
import json
import os
import subprocess
import sys

import pytest
from conftest import CHIP, ROOT

from harness import manifest

CELLS = [w["name"] for w in manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))["workloads"]]


def run(*args):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    return subprocess.run([sys.executable, os.path.join(CHIP, "run.py"), *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contracts_last_line(cell, trace):
    done = run("--workload", cell, "--seed", str(2**31 + 12345), "--seconds", "1", "--trace", str(trace), "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    info, last = (json.loads(ln) for ln in done.stdout.splitlines()[-2:])
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 1
    assert last["device"]["platform"] == "cpu" and last["metrics"] == {} and "breakdown" not in last
    assert info["info"]["window_compiles"] == 0 and info["info"]["reference"]["ok"] is True
    wanted = {"call_ms.p50", "setup_s"} if trace == 0 else {"compiles.window", "host_syncs.call", "kernels_declined.call"}
    assert wanted <= set(info["info"]["rehearsed_metrics"])
    assert info["info"]["traced_calls"] >= 2 if trace else info["info"]["traced_calls"] == 0


def test_without_a_tpu_nothing_runs_and_no_result_is_printed():
    done = run("--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0")
    assert done.returncode != 0 and not any(ln.startswith("{") for ln in done.stdout.splitlines())


def test_an_unknown_cell_is_refused():
    done = run("--workload", "no-such-cell", "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse")
    assert done.returncode != 0 and not done.stdout.strip()
