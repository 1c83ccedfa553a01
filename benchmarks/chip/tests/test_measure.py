"""``measure.py spread``: the contract's spread of a set, and the first compiling run left out of ``setup_s``."""
import importlib.util
import json
import os
import statistics

import pytest
from conftest import CHIP

spec = importlib.util.spec_from_file_location("chipbench_measure", os.path.join(CHIP, "measure.py"))
measure = importlib.util.module_from_spec(spec)
spec.loader.exec_module(measure)


def test_spread_is_the_quartile_distance_over_the_median():
    values = [10.0, 10.1, 10.2, 10.3, 10.4, 13.0]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert measure.spread_of(values) == pytest.approx((q3 - q1) / 10.25)


def test_spread_reads_the_sets_and_drops_the_first_runs_setup(tmp_path, capsys):
    def record(set_no, run_no, setup, p50):
        line = {"correct": True, "metrics": {"setup_s": {"value": setup, "unit": "s"}, "call_ms.p50": {"value": p50, "unit": "ms"}}}
        return {"cell": "a-cell", "set": set_no, "run": run_no, "trace": 0, "rc": 0, "wall_s": 1.0, "lines": [{"info": {}}, line]}

    rows = [record(0, 0, 90.0, 10.0)] + [record(0, r, 20.0, 10.0) for r in (1, 2, 3)] + [record(1, r, 20.0, 10.1) for r in range(4)]
    (tmp_path / "a-cell.jsonl").write_text("".join(json.dumps(r) + "\n" for r in rows))
    assert measure.main(["spread", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    setup = next(ln for ln in out.splitlines() if ln.strip().startswith("setup_s"))
    assert "median 20 " in setup and "n=3" in setup and "90" not in setup  # the compiling run is not in it
    p50 = next(ln for ln in out.splitlines() if ln.strip().startswith("call_ms.p50"))
    assert "second/first +1.000%" in p50 and "widest 0.000%" in p50
