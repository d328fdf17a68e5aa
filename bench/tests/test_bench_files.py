"""Each axis of a cell arrives as files: a fabric family, a routing mode,
a collective, a lane count and an engine's rate are added to a copy of
the benchmark as new files and `BENCHMARK.json` entries alone, and run
through the harness on the CPU with `correct` true.

The new fabric is the paper's third, a 3-level fat tree (endpoints on
its edge routers only, routers of two port counts), at p=3 under ECMP;
the new collective an all-to-all.  The reference's fabrics are the same
arrays as before families moved into files of their own."""

import contextlib
import hashlib
import importlib
import json
import os
import shutil
import sys

import numpy as np
import pytest

from bench.tests.conftest import MIXES, ROOT, SF_Q5, write_json

FATTREE3 = '''"""3-level fat tree (a p-ary 3-tree): p pods of p edge and p
aggregation routers, and p^2 core routers in p groups.  Edge router
(pod, i) is pod*p + i, aggregation router (pod, j) p^2 + pod*p + j,
core router (j, c) 2p^2 + j*p + c.  Every edge router links to each
aggregation router of its pod; aggregation router j of every pod links
to each core router of group j.  p endpoints on each edge router."""

import numpy as np


def build(p):
    n = p * p
    adj = np.zeros((3 * n, 3 * n), dtype=bool)
    for pod in range(p):
        for i in range(p):
            for j in range(p):
                adj[pod * p + i, n + pod * p + j] = True
        for j in range(p):
            for c in range(p):
                adj[n + pod * p + j, 2 * n + j * p + c] = True
    adj |= adj.T
    return adj, np.repeat(np.arange(n), p)
'''

ECMP = '''"""ECMP: no intermediate; at each hop, of the ports whose neighbour
lies one hop closer to the target, the one whose downstream input queue
holds the fewest packets, the lowest port on ties."""

import numpy as np


def route(net, src_r, dst_r, occ, draws):
    return dst_r.copy(), np.ones_like(dst_r)


def hop(net, r, tgt, occ):
    f = net.fab
    nb = f.nbr[r]
    closer = (nb >= 0) & (f.dist[np.maximum(nb, 0), tgt[:, None]]
                          == f.dist[r, tgt][:, None] - 1)
    o = np.where(closer, occ[r], np.iinfo(np.int64).max)
    return np.where(r == tgt, -1, o.argmin(axis=1))
'''

ALL_TO_ALL = '''"""Personalised all-to-all: rank r's j-th message goes to rank
(r + j) mod k, j = 1..k-1, with no dependencies, in one phase."""

import numpy as np


def messages(n_ranks, flits_per_pair):
    k = n_ranks
    src = np.repeat(np.arange(k), k - 1)
    dst = (src + np.tile(np.arange(1, k), k)) % k
    m = len(src)
    return dict(n_ranks=k, src=src, dst=dst,
                size=np.full(m, flits_per_pair, np.int64),
                dep=np.full((m, 1), -1), phase=np.zeros(m, np.int64))
'''

# an engine that differs from the open loop only in the rate it declares
OPEN_SWEEP = '''from bench.engines.open_loop import *  # noqa: F401,F403

RATE = "open_loop.router_cycles_per_s"
'''

FT3 = {"name": "ft3_p3", "source": "test",
       "topology": {"family": "fattree3", "p": 3},
       "switch": SF_Q5["switch"], "reduced": [], "assumed": {}}
NEW_FILES = {
    "bench/reference/families/fattree3.py": FATTREE3,
    "bench/reference/modes/ecmp.py": ECMP,
    "bench/reference/collectives/all_to_all.py": ALL_TO_ALL,
    "bench/engines/open_sweep.py": OPEN_SWEEP,
}
NEW_MIXES = {
    "uniform_ecmp": {"engine": "open_loop", "pattern": "uniform",
                     "injection_rate": 0.6, "mode": "ecmp",
                     "tables": {"ecmp": True}, "cycles": 24, "warmup": 8},
    "uniform_ecmp.lanes3": {"engine": "open_loop", "pattern": "uniform",
                            "rates": [0.2, 0.6, 1.0], "mode": "ecmp",
                            "tables": {"ecmp": True}, "cycles": 24,
                            "warmup": 8},
    "all_to_all_ecmp": {"engine": "closed_loop", "collective": "all_to_all",
                        "args": {"n_ranks": 27, "flits_per_pair": 2},
                        "mode": "ecmp", "tables": {"ecmp": True},
                        "placement": "spread", "chunk": 16,
                        "max_cycles": 48},
    "uniform_sweep": dict(MIXES["uniform_short"], engine="open_sweep"),
}
NEW_CELLS = {  # cell: (configuration, mix, rate it reports)
    "ft3_p3.uniform_ecmp": ("ft3_p3", "uniform_ecmp",
                            "open_loop.router_cycles_per_s"),
    "ft3_p3.uniform_ecmp.lanes3": ("ft3_p3", "uniform_ecmp.lanes3",
                                   "open_loop.router_cycles_per_s"),
    "ft3_p3.all_to_all_ecmp": ("ft3_p3", "all_to_all_ecmp",
                               "closed_loop.router_cycles_per_s"),
    "sf_q5.uniform_sweep": ("sf_q5", "uniform_sweep",
                            "open_loop.router_cycles_per_s"),
}


def _digests(root):
    out = {}
    for d, dirs, files in os.walk(os.path.join(root, "bench")):
        dirs[:] = [x for x in dirs if x not in ("tests", "__pycache__")]
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@contextlib.contextmanager
def bench_package_of(root):
    """Import `bench` from the checkout at `root` for the duration, and
    give the process its own `bench` back afterwards."""
    def ours():
        return [k for k in sys.modules if k == "bench"
                or k.startswith(("bench.", "bench_file_"))]
    saved = {k: sys.modules.pop(k) for k in ours()}
    sys.path.insert(0, str(root))
    try:
        yield importlib.import_module("bench.harness")
    finally:
        sys.path.remove(str(root))
        for k in ours():
            del sys.modules[k]
        sys.modules.update(saved)


@pytest.fixture
def files_checkout(tmp_path):
    """A copy of the benchmark with the new axes added as files only."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = _digests(str(tmp_path))
    for rel, text in NEW_FILES.items():
        (tmp_path / rel).write_text(text)
    write_json(str(tmp_path / "bench/configs/ft3_p3.json"), FT3)
    write_json(str(tmp_path / "bench/configs/sf_q5.json"), SF_Q5)
    for name, mix in NEW_MIXES.items():
        write_json(str(tmp_path / f"bench/traffic/{name}.json"), mix)
    bench = {
        "configs": [{"name": c, "source": "test", "reduced": [], "why": "test",
                     "file": f"bench/configs/{c}.json"}
                    for c in ("ft3_p3", "sf_q5")],
        "workloads": [{"name": n, "config": c, "traffic": m, "chips": 1,
                       "why": "test"} for n, (c, m, _) in NEW_CELLS.items()],
        "end_to_end": [
            {"name": rate, "unit": "router-cycles/s", "better": "higher",
             "bound": 0.01, "source": "host_clock",
             "workloads": [n for n, (_, _, r) in NEW_CELLS.items()
                           if r == rate]}
            for rate in ("open_loop.router_cycles_per_s",
                         "closed_loop.router_cycles_per_s")] + [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": []}
    write_json(str(tmp_path / "BENCHMARK.json"), bench)
    return tmp_path, before


@pytest.mark.parametrize("cell", sorted(NEW_CELLS))
def test_new_axes_arrive_as_files(files_checkout, monkeypatch, capsys, cell):
    import jax

    root, before = files_checkout
    with bench_package_of(root) as harness:
        assert harness.ROOT == str(root)
        monkeypatch.setattr(harness, "require_chip", lambda n: jax.devices())
        monkeypatch.setattr(harness, "require_pallas", lambda e, s: None)
        monkeypatch.setattr(harness, "compilation_cache",
                            lambda: ("off", None))
        rc = harness.main(["--workload", cell, "--seed", str(2 ** 31 + 5),
                           "--seconds", "0", "--trace", "0"],
                          root=str(root))
    out = capsys.readouterr()
    assert rc == 0, out.err
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True, out.err
    assert set(line["metrics"]) == {NEW_CELLS[cell][2], "setup_s"}
    (check,) = line["checks"].values()
    assert check["value"] == 0
    if "lanes" in cell:
        assert check["injection_rate"] == [0.2, 0.6, 1.0][check["lane"]]
    # every file the benchmark had is as it was
    after = _digests(str(root))
    assert {k: after[k] for k in before} == before


def test_fattree_reference_matches_the_simulator_tables():
    """The test's fat-tree family builds the simulator's own tables, and
    the fabric keeps endpoints on edge routers and unused ports."""
    import types

    from repro.core.topologies import build_fattree3
    from repro.sim import SimTables

    from bench.reference import fabric

    family = types.ModuleType("fattree3")
    exec(FATTREE3, family.__dict__)
    f = fabric.fabric(*family.build(p=3))
    t = SimTables.build(build_fattree3(p=3), ecmp=True)
    assert f.p == t.p == 3 and f.n_endpoints == 27
    assert (f.ep_at[9:] == -1).all() and (f.nbr[:9, 3:] == -1).all()
    for mine, theirs in ((f.nbr, t.nbr), (f.rev, t.rev_port),
                         (f.dist, t.dist), (f.port_toward, t.port_toward),
                         (f.ep_router, t.ep_router)):
        assert np.array_equal(mine, theirs)


# sha256 (first 16 hex digits) of (shape, int64 bytes) of each array of
# the reference fabric, as the single-file fabric module built them
# before the families moved into files of their own
BEFORE = {
    "sf5": ("f46ac19752f9e5a6", "77be7a87cf557f5f", "d6013187fb750d21",
            "a1e0114f7881c6bc", "9f6c6e8597f7af45"),
    "sf7": ("f2c7eac997d05d2e", "6949e388ea69adbd", "4e6b4b4c5335852f",
            "a8e4f7d4ce3bcb3d", "cca3fb22ed6e3d12"),
    "df2": ("ff3bf02b708dd2ec", "9e4cff0f865cd5d6", "c4102709b03b559f",
            "d2095c97c7835548", "4b65e2e94e5b0c46"),
    "df3": ("792713ee803fc2b6", "12a8f88459315763", "8552ab10a61e0b1e",
            "e4e1fc39add10998", "665cf9d800439054"),
}
SPECS = {"sf5": {"family": "slimfly", "q": 5},
         "sf7": {"family": "slimfly", "q": 7},
         "df2": {"family": "dragonfly", "h": 2},
         "df3": {"family": "dragonfly", "h": 3}}


@pytest.mark.parametrize("name", sorted(SPECS))
def test_reference_fabric_unchanged_by_the_move(name):
    from bench.reference import fabric

    f = fabric.build(SPECS[name])

    def digest(a):
        assert a.dtype == np.int64
        return hashlib.sha256(repr(a.shape).encode()
                              + np.ascontiguousarray(a).tobytes()
                              ).hexdigest()[:16]
    assert tuple(digest(getattr(f, k)) for k in (
        "nbr", "rev", "dist", "port_toward", "ep_router")) == BEFORE[name]
