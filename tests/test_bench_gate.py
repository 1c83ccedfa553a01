"""The bench floor gate must not self-normalize a sustained regression.

``bench.update_history`` keeps the gate baseline as the trailing median
of runs that themselves passed the gate; violating runs stay out of the
window (else a regression drags the median to itself within a few runs
and the 0.7x floor goes silent). Three consecutive violations agreeing
within 15% re-baseline — a persistent environment change is accepted
only after failing visibly three times.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


def _gate(value, suspect=frozenset()):
    out = {
        "value": value,
        "cdist_gbps": None,
        "moments_gbps": None,
        "qr_gflops": None,
        "matmul_gflops": None,
        "lasso_sweeps_per_sec": None,
    }
    return bench.update_history(out, suspect=suspect)[2]["kmeans_iters_per_sec"]


def _with_history(tmp_path, name):
    bench.HISTORY_PATH = str(tmp_path / name)


def test_sustained_regression_keeps_failing(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "HISTORY_PATH", str(tmp_path / "h.json"))
    for v in (100, 105, 98, 102, 101):
        assert _gate(v) >= bench.FLOOR
    # a drop to half speed must violate on EVERY run until re-baselined,
    # not launder itself into the trailing median
    gates = [_gate(v) for v in (50, 52, 50)]
    assert all(g < bench.FLOOR for g in gates), gates


def test_rebaseline_after_three_agreeing_violations(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "HISTORY_PATH", str(tmp_path / "h.json"))
    for v in (100, 105, 98):
        _gate(v)
    for v in (50, 52, 50):
        _gate(v)
    # the new sustained level is now the baseline: an honest run at that
    # level passes, and a further regression below it fails again
    assert _gate(51) >= bench.FLOOR
    assert _gate(30) < bench.FLOOR


def test_single_dip_does_not_move_baseline(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "HISTORY_PATH", str(tmp_path / "h.json"))
    for v in (100, 105, 98):
        _gate(v)
    assert _gate(60) < bench.FLOOR
    # recovery compares against the healthy window, not the dip
    assert _gate(99) >= bench.FLOOR


def test_suspect_runs_cannot_rebaseline(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "HISTORY_PATH", str(tmp_path / "h.json"))
    for v in (100, 102, 98):
        _gate(v)
    # three agreeing low runs, all flagged as timer-corrupted: they must
    # not install themselves as the baseline
    for _ in range(3):
        _gate(50, suspect={"kmeans_iters_per_sec"})
    # an honest run at the old level still passes against the old baseline
    assert _gate(99) >= bench.FLOOR
    # and an honest run at the low level still violates (no rebaseline)
    assert _gate(50) < bench.FLOOR


def test_suspect_pass_does_not_reset_rebaseline_vote(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "HISTORY_PATH", str(tmp_path / "h.json"))
    for v in (100, 102, 98):
        _gate(v)
    # two honest agreeing violations start the rebaseline vote...
    assert _gate(50) < bench.FLOOR
    assert _gate(52) < bench.FLOOR
    # ...then a timer-corrupted rep that happens to pass the gate must
    # NOT clear the pending vote (corrupted timers neither vote for nor
    # against a rebaseline)
    _gate(101, suspect={"kmeans_iters_per_sec"})
    # the third agreeing honest violation completes the vote: rebaselined
    assert _gate(50) < bench.FLOOR
    assert _gate(51) >= bench.FLOOR


def test_disagreeing_violations_do_not_rebaseline(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "HISTORY_PATH", str(tmp_path / "h.json"))
    for v in (100, 102, 98):
        _gate(v)
    # three violations spanning >15% disagree — noise, not a new level
    gates = [_gate(v) for v in (50, 65, 50, 50)]
    assert all(g < bench.FLOOR for g in gates[:3])


# --- r7 OVERLAP_BAND: DMA-overlap diagnostics cannot keep a top-of-band
# spike as the bar (the BENCH_r05 kernel_matmul_gram / moments_fused
# "regressions" were healthy in-band runs compared against exactly that)


def test_band_migration_retires_stale_best():
    key = "kernel_matmul_gram_gflops"
    med = 25_000.0  # trailing clean level, under the physical cap
    spike_best, spike_med = 33_000.0, 32_000.0  # in-cap, out-of-band
    assert spike_best < bench.CAPS[key]  # the CAPS purge must NOT be what fires
    hist = {
        "_protocol": "api-r6",
        key: {
            "runs": [med] * 9,
            "clean": [med] * 9,
            "best": spike_best,
            "best_median": spike_med,
        },
    }
    out = bench._migrate_history(hist)
    rec = out[key]
    limit = bench.OVERLAP_BAND[key] * med
    assert rec["best"] <= limit and rec["best_median"] <= limit
    assert spike_best in rec["retired_band_outliers"]
    assert spike_med in rec["retired_band_outliers"]
    assert "band_note" in rec
    assert out["_protocol"] == bench.PROTOCOL
    # idempotent: the protocol stamp short-circuits a second migration
    import copy

    again = bench._migrate_history(copy.deepcopy(out))
    assert again == out


def test_band_in_band_best_survives_migration():
    key = "kernel_moments_fused_gbps"
    med = 700.0
    hist = {
        "_protocol": "api-r6",
        key: {"runs": [med] * 9, "clean": [med] * 9, "best": 1.1 * med,
              "best_median": med},
    }
    rec = bench._migrate_history(hist)[key]
    assert rec["best"] == 1.1 * med  # within band: untouched
    assert "retired_band_outliers" not in rec


# A history shaped like the record the r7 band clamp was written against:
# written at protocol api-r5, a top-of-band ``best`` (32173.5) over a
# ~26k trailing clean median, and two values already retired above the
# physical cap.
_GRAM_HISTORY = {
    "_protocol": "api-r5",
    "kernel_matmul_gram_gflops": {
        "best": 32173.5,
        "best_median": 32173.5,
        "clean": [22529.96, 25688.16, 24596.07, 25427.6, 27672.7, 31051.54,
                  32173.5, 31121.89, 26087.26],
        "runs": [17630.62, 10529.08, 26400.65, 22529.96, 25688.16, 24596.07,
                 25427.6, 27672.7, 31051.54, 32173.5, 31121.89, 26087.26],
        "retired_artifacts": [46286.73, 50457.26],
        "pending_violations": [],
    },
}


def test_history_gram_outlier_retires_on_migration():
    """A gram record carrying a top-of-band best (32173.5 against a
    ~26 TFLOP/s trailing clean median) made every healthy in-band run
    read as ~0.81x vs_best. The r8 protocol bump re-runs
    ``_migrate_history``, whose r7 band clamp must retire exactly that
    best — pinned on a whole api-r5 history, not a single synthetic
    record."""
    import copy

    hist = copy.deepcopy(_GRAM_HISTORY)
    key = "kernel_matmul_gram_gflops"
    rec = hist[key]
    limit = bench._band_limit(rec, bench.OVERLAP_BAND[key])
    migrated = bench._migrate_history(copy.deepcopy(hist))[key]
    # whatever the starting state, the migrated bar sits inside the band
    assert migrated.get("best", 0) <= limit
    assert migrated.get("best_median", 0) <= limit
    if rec.get("best", 0) > limit:  # the 0.81x artifact was still live
        assert rec["best"] in migrated["retired_band_outliers"]
    # the pre-r5 marginal-timer spikes stay visibly retired through the bump
    for v in rec.get("retired_artifacts", []):
        assert v in migrated["retired_artifacts"]


def test_band_bounds_the_ratchet(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "HISTORY_PATH", str(tmp_path / "h.json"))
    import json

    key = "kernel_matmul_gram_gflops"
    for _ in range(5):
        bench.update_history({"value": 100.0, key: 25_000.0})
    # a lucky top-of-band catch (in-cap) must not become the new best
    bench.update_history({"value": 100.0, key: 33_000.0})
    with open(bench.HISTORY_PATH) as fh:
        rec = json.load(fh)[key]
    assert rec["best"] <= bench.OVERLAP_BAND[key] * 25_000.0
    assert 33_000.0 in rec["runs"]  # the run itself still records
