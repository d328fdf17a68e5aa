"""Shared pieces of the benchmark's CPU tests: a scratch checkout with
small cells, and a harness run with the chip checks steered off."""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

SF_Q5 = {
    "name": "sf_q5", "source": "test", "reduced": [], "assumed": {},
    "topology": {"family": "slimfly", "q": 5},
    "switch": {"vcs": 4, "q_net": 16, "q_src": 64, "lookahead": 4,
               "n_val_candidates": 4}}
MIXES = {
    "uniform_short": {"engine": "open_loop", "pattern": "uniform",
                      "injection_rate": 0.5, "mode": "ugal_l",
                      "cycles": 24, "warmup": 8, "reduced": {}},
    "uniform_lanes": {"engine": "open_loop", "pattern": "uniform",
                      "rates": [0.1, 0.3, 0.5, 0.7, 0.9], "mode": "ugal_l",
                      "cycles": 24, "warmup": 8, "reduced": {}},
    "ring_short": {"engine": "closed_loop", "collective": "ring_all_reduce",
                   "args": {"n_ranks": 16, "chunk_flits": 4}, "mode": "min",
                   "placement": "spread", "chunk": 16, "max_cycles": 32,
                   "reduced": {}},
}
CELLS = {"sf_q5.uniform_short": ("uniform_short", "open_loop"),
         "sf_q5.uniform_lanes": ("uniform_lanes", "open_loop"),
         "sf_q5.ring_short": ("ring_short", "closed_loop")}


def write_json(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture
def checkout(tmp_path):
    """A checkout holding a copy of the benchmark's code, with the q=5
    configuration, three short mixes and their cells added as new
    files."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    write_json(str(tmp_path / "bench/configs/sf_q5.json"), SF_Q5)
    for name, mix in MIXES.items():
        write_json(str(tmp_path / f"bench/traffic/{name}.json"), mix)
    bench["configs"] = [{"name": "sf_q5", "source": "test",
                         "file": "bench/configs/sf_q5.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": c, "config": "sf_q5", "traffic": m,
                           "chips": 1, "why": "test"}
                          for c, (m, _) in CELLS.items()]
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "workloads" in m:
                engine = m["name"].split(".")[0]
                tag = m["name"].rsplit(".", 1)[-1]
                m["workloads"] = [
                    c for c, (_, e) in CELLS.items()
                    if engine == e or tag == {"open_loop": "open",
                                              "closed_loop": "closed"}[e]]
    write_json(str(tmp_path / "BENCHMARK.json"), bench)
    return tmp_path


@pytest.fixture
def run_cell(monkeypatch, capsys):
    """Run one cell through `harness.main` on the CPU: the look for a
    chip and for the Pallas path are steered here, and the persistent
    compilation cache stays off."""
    import jax

    monkeypatch.setattr(harness, "require_chip", lambda n: jax.devices())
    monkeypatch.setattr(harness, "require_pallas", lambda engine, state: None)
    monkeypatch.setattr(harness, "compilation_cache", lambda: ("off", None))

    def run(root, cell, seed=11, trace=0):
        rc = harness.main(["--workload", cell, "--seed", str(seed),
                           "--seconds", "0", "--trace", str(trace)],
                          root=str(root))
        out = capsys.readouterr()
        assert rc == 0, out.err
        return json.loads(out.out.strip().splitlines()[-1]), out
    return run
