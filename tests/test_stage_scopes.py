"""The simulator's profile names: stage scopes inside the compiled scan
and host spans around it.

Every stage of a simulated cycle runs under a flat `jax.named_scope`
(`switch.*` in `SwitchCore`, `closed.*` in the closed loop's step); the
scope reaches a profile only as the `op_name` metadata of the compiled
runner, which `compiled_runner_hlo` returns.  The entry points wrap
their phases in `jax.profiler.TraceAnnotation` spans (`sim.*`,
`workload.*`) on the profiler's clock.  These tests keep both from
being lost in a refactor.
"""

import glob
import re

import pytest

import jax

from repro.core import build_slimfly
from repro.sim import (SimConfig, SimTables, TelemetryConfig, engine,
                       make_traffic, simulate)
from repro.sim.workloads import (WorkloadSimConfig, closed_loop,
                                 ring_all_reduce, run_workload)

SWITCH = {"switch.occupancy", "switch.route", "switch.inject",
          "switch.desires", "switch.space", "switch.alloc", "switch.fold",
          "switch.arrivals", "switch.compaction"}
CLOSED = {"closed.ready", "closed.pick", "closed.account"}
STAGE = re.compile(r'op_name="[^"]*?/((?:switch|closed)\.[a-z]+)(?=[/"])')


@pytest.fixture(scope="module")
def q5():
    tables = SimTables.build(build_slimfly(5))
    return tables, make_traffic(tables, "uniform")


def stages_of(texts):
    assert len(texts) == 1
    return set(STAGE.findall(texts[0]))


def test_open_loop_runner_names_every_stage(q5, monkeypatch):
    # counters on, so that the telemetry block compiles in as well
    monkeypatch.setattr(engine, "_OPEN_LOOP_CACHE", {})
    tables, traffic = q5
    simulate(tables, traffic, SimConfig(
        injection_rate=0.5, cycles=4, warmup=1, mode="ugal_l",
        telemetry=TelemetryConfig(counters=True)))
    assert stages_of(engine.compiled_runner_hlo()) == (
        SWITCH | {"switch.telemetry"})


@pytest.mark.parametrize("mode,drop,ranks", [
    ("ugal_l", set(), 8),
    # MIN reads no occupancy and chooses no route: both compile away
    ("min", {"switch.occupancy", "switch.route"}, 8),
    # every endpoint sends, so the pick scans all of them
    ("min", {"switch.occupancy", "switch.route"}, 150)])
def test_closed_loop_runner_names_every_stage(q5, monkeypatch, mode, drop,
                                              ranks):
    monkeypatch.setattr(closed_loop, "_RUNNER_CACHE", {})
    tables, _ = q5
    run_workload(tables, ring_all_reduce(ranks, 2), WorkloadSimConfig(
        mode=mode, placement="spread", chunk=4, max_cycles=8))
    assert stages_of(closed_loop.compiled_runner_hlo()) == (
        (SWITCH - drop) | CLOSED)


def test_compiled_runner_hlo_skips_the_lane_sweeps(q5, monkeypatch):
    from repro.sim import sweep_run_workload

    monkeypatch.setattr(closed_loop, "_RUNNER_CACHE", {})
    tables, _ = q5
    sweep_run_workload(tables, ring_all_reduce(8, 2), WorkloadSimConfig(
        mode="min", placement="spread", chunk=4, max_cycles=8),
        seeds=[1, 2])
    # the sweep caches its single-lane runner and its vmapped one
    assert len(closed_loop._RUNNER_CACHE) == 2
    assert len(closed_loop.compiled_runner_hlo()) == 1


def host_spans(log_dir):
    from jax.profiler import ProfileData

    (pb,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(pb).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)) for e in line.events
                           if e.name.startswith(("sim.", "workload.")))
    return out


def inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_entry_points_write_host_spans(q5, tmp_path):
    tables, traffic = q5
    cfg = SimConfig(injection_rate=0.5, cycles=4, warmup=1, mode="ugal_l",
                    seed=7)
    wcfg = WorkloadSimConfig(mode="min", placement="spread", chunk=4,
                             max_cycles=8, seed=5)
    wl = ring_all_reduce(16, 4)                 # runs past two chunks
    simulate(tables, traffic, cfg)
    run_workload(tables, wl, wcfg)
    with jax.profiler.trace(str(tmp_path)):
        simulate(tables, traffic, cfg)
        res = run_workload(tables, wl, wcfg)
    spans = host_spans(tmp_path)
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)

    (sim,) = by["sim.simulate"]
    assert sim[3] == {"seed": 7}
    for child in ("sim.init_carry", "sim.scan", "sim.assemble"):
        (c,) = by[child]
        assert inside(c, sim)
    assert (by["sim.init_carry"][0][2] <= by["sim.scan"][0][1]
            and by["sim.scan"][0][2] <= by["sim.assemble"][0][1])

    (run,) = by["workload.run"]
    # one rank on each of 16 routers, 2 x 15 ring steps each
    assert run[3] == {"seed": 5, "pick_rows": 16, "pick_width": 30}
    chunks = sorted(by["workload.chunk"], key=lambda s: s[1])
    assert res.cycles_run == 8
    assert [c[3] for c in chunks] == [{"start": 0}, {"start": 4}]
    for c in chunks + by["workload.init_carry"] + by["workload.result"]:
        assert inside(c, run)
    assert by["workload.init_carry"][0][2] <= chunks[0][1]
    assert chunks[-1][2] <= by["workload.result"][0][1]
