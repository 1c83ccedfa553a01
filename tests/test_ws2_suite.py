"""Tier-1 bounded multi-process shard: a sampled ws-2 slice of the
multihost-marked subset runs through the REAL runner
(``tools/mpirun.py`` / ``heat_tpu.testing``) on every tier-1 invocation
— real ``jax.distributed`` processes, real collectives, real quarantine
handling — and its wall clock is recorded into ``SUITE_SECONDS.json``
and gated against creep (>20% over the recorded baseline fails, the
``tools/bench_check.py`` discipline applied to suite seconds).

The whole-suite ws-2/4/8 runs are ``python tools/mpirun.py -n {2,4,8}``
(see docs/TESTING.md); this wrapper keeps a fast, always-on canary of
that path inside tier-1 without blowing the suite budget.
"""
import os
import time

import pytest

from tools import mpirun

testing = mpirun._load_testing()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# hard ceiling protecting the tier-1 wall clock; the recorded-baseline
# budget gate below polices real creep much more tightly
WS2_HARD_CEILING_SECONDS = 120.0
SAMPLE_SIZE = 6


def _run_shard(world_size: int, sample: int, tmp_path, deadline: float = 60.0):
    cfg = testing.RunnerConfig(
        world_size=world_size,
        devices_total=8,
        deadline=deadline,
        grace=15.0,
        startup_timeout=300.0,
        max_restarts=2,
        # the multihost-marked subset: every test in it is written for
        # real multi-process execution, and one module keeps collection fast
        pytest_args=["tests/test_mh_suite.py"],
        sample=sample,
        sample_seed=12,
        repo_root=REPO,
        log_dir=str(tmp_path / "logs"),
    )
    return testing.SuiteRunner(cfg).run()


def test_ws2_sampled_shard_and_budget(tmp_path):
    t0 = time.monotonic()
    result = _run_shard(2, SAMPLE_SIZE, tmp_path)
    wall = time.monotonic() - t0

    ran = {tid: r for tid, r in result.results.items()
           if r["outcome"] != "quarantined"}
    bad = {tid: (r["outcome"], r.get("exc_type"), (r.get("error") or "")[:300])
           for tid, r in ran.items()
           if r["outcome"] in ("failed", "error", "restart-failure", "uneven")}
    assert not bad, f"ws-2 shard failures: {bad}"
    assert sum(1 for r in ran.values() if r["outcome"] == "passed") >= 3
    assert result.restarts == 0, "worker group recycled during the canary shard"
    assert wall < WS2_HARD_CEILING_SECONDS

    # budget gate BEFORE recording: this run must fit the baseline, then
    # it becomes the new baseline (ratchet follows reality, creep fails)
    # the canary's wall is startup-dominated (6 tiny tests behind a full
    # 2-process jax.distributed boot), which varies ~2x with machine
    # state — so this gate runs at 100% tolerance over the high-water
    # baseline: it still fails a pathological startup regression, while
    # the tight default 20% keeps policing the suite-scale ws runs
    violations = mpirun.check_budget("ws2_shard", result.wall_seconds,
                                     mpirun.load_suite_seconds(),
                                     tolerance=1.0)
    assert not violations, violations
    # the canary's wall varies >2x with page-cache state and memory
    # pressure (6.1s..17.8s back to back on an otherwise idle machine),
    # so the recorded baseline is a HIGH-water mark: real creep still
    # fails the budget gate above, but a lucky fast run must not ratchet
    # the baseline down into the noise band and flake every later run
    prior = (mpirun.load_suite_seconds().get("ws_runs", {})
             .get("ws2_shard", {}).get("suite_seconds", 0.0))
    recorded = max(result.wall_seconds, prior)
    mpirun.record_ws_run("ws2_shard", {
        "wall_seconds": recorded,
        "world_size": result.world_size,
        "collected": result.collected,
        "counts": result.counts(),
        "restarts": result.restarts,
    })
    data = mpirun.load_suite_seconds()
    assert data["ws_runs"]["ws2_shard"]["suite_seconds"] == recorded


@pytest.mark.slow
@pytest.mark.parametrize("world_size", [4, 8])
def test_ws_matrix_sampled_shard(world_size, tmp_path):
    """The ws-4/ws-8 sampled matrix on the multihost-marked subset — the
    reference's ``mpirun -n {1,2,5,8}`` sweep, sampled. Slow-marked: run
    via ``python -m pytest tests/test_ws2_suite.py -m slow`` or the full
    matrix via ``python tools/mpirun.py -n {4,8} --sample N``."""
    result = _run_shard(world_size, 4, tmp_path, deadline=90.0)
    bad = {tid: (r["outcome"], r.get("exc_type"))
           for tid, r in result.results.items()
           if r["outcome"] in ("failed", "error", "restart-failure", "uneven")}
    assert not bad, f"ws-{world_size} shard failures: {bad}"
    assert any(r["outcome"] == "passed" for r in result.results.values())
