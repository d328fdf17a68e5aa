"""The data-driven path on the CPU: cells of each engine run through the
same code as `bench/run.py`, and a new configuration, mix or per-layer
metric is found as a new file alone."""

import json
import os
import subprocess
import sys

import pytest

from bench.tests.conftest import CELLS, ROOT

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                 "checks"}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_runs_and_is_correct(checkout, run_cell, cell):
    line, out = run_cell(checkout, cell)
    assert set(line) == CONTRACT_KEYS
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    engine = CELLS[cell][1]
    assert set(line["metrics"]) == {f"{engine}.router_cycles_per_s",
                                     "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["checks"] and all(c["value"] == 0
                                  for c in line["checks"].values())
    # each compared number is printed beside its limit, last on stderr
    tail = out.err.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and "(limit 0)" in t for t in tail)
    if "lanes" in cell:
        # the lane drawn from the call's seed is named, with its rate
        (c,) = line["checks"].values()
        assert 0 <= c["lane"] < 5
        assert c["injection_rate"] == [0.1, 0.3, 0.5, 0.7, 0.9][c["lane"]]
        assert f"lane {c['lane']} " in tail[-1]
    assert "0 compilations inside the window" in out.out


def test_new_metric_is_found_by_name(checkout, run_cell):
    with open(checkout / "bench/metrics/calls_in_window.py", "w") as f:
        f.write("def read(ctx):\n    return len(ctx['window']['calls'])\n")
    with open(checkout / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["per_layer"].append({
        "name": "calls_in_window.open", "unit": "calls", "better": "higher",
        "source": "host_clock", "layer": "device",
        "moves": "open_loop.router_cycles_per_s",
        "workloads": ["sf_q5.uniform_short"]})
    with open(checkout / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    line, _ = run_cell(checkout, "sf_q5.uniform_short", trace=1)
    assert set(line) == CONTRACT_KEYS | {"breakdown"}
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert line["metrics"]["calls_in_window.open"] == {"value": 1,
                                                       "unit": "calls"}
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps", "stages",
                                      "idle_by_span"}


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "sf_q19.uniform_ugal_l", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "metrics" not in p.stdout
