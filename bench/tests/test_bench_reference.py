"""The plain reference's fabrics agree with the simulator's tables at
sizes a test run holds, and its runs with the simulator's answers."""

import numpy as np
import pytest

from bench.reference import fabric, runs
from bench.reference.network import Switch

SW = Switch(vcs=4, q_net=16, q_src=64, lookahead=4, n_val_candidates=4)


@pytest.mark.parametrize("spec", [{"family": "slimfly", "q": 5},
                                  {"family": "slimfly", "q": 7},
                                  {"family": "dragonfly", "h": 2},
                                  {"family": "dragonfly", "h": 3}],
                         ids=["sf5", "sf7", "df2", "df3"])
def test_fabric_matches_the_simulator_tables(spec):
    from repro.core import build_slimfly
    from repro.core.topologies import build_dragonfly
    from repro.sim import SimTables

    topo = (build_slimfly(spec["q"]) if spec["family"] == "slimfly"
            else build_dragonfly(h=spec["h"]))
    t = SimTables.build(topo)
    f = fabric.build(spec)
    assert f.p == t.p
    for mine, theirs in ((f.nbr, t.nbr), (f.rev, t.rev_port),
                         (f.dist, t.dist), (f.port_toward, t.port_toward),
                         (f.ep_router, t.ep_router)):
        assert np.array_equal(mine, theirs)


def test_paper_sizes():
    """q=19 by its published numbers, without building the routes."""
    from bench.reference.families import dragonfly, slimfly

    adj, ep_router = slimfly.build(q=19)
    assert adj.shape[0] == 722 and (adj.sum(1) == 29).all()
    assert (np.bincount(ep_router) == 15).all() and len(ep_router) == 10830
    adj, ep_router = dragonfly.build(h=7)
    assert adj.shape[0] == 1386 and (adj.sum(1) == 20).all()
    assert (np.bincount(ep_router) == 7).all() and len(ep_router) == 9702


@pytest.mark.parametrize("mode,rate", [("min", 0.9), ("ugal_l", 0.4)])
def test_open_loop_matches_simulate(mode, rate):
    from repro.core.topologies import build_dragonfly
    from repro.sim import SimConfig, SimTables, make_traffic, simulate

    t = SimTables.build(build_dragonfly(h=2))
    r = simulate(t, make_traffic(t, "uniform"),
                 SimConfig(injection_rate=rate, cycles=40, warmup=10,
                           mode=mode, seed=2 ** 31 + 3))
    ref = runs.open_loop(fabric.build({"family": "dragonfly", "h": 2}), SW,
                         pattern="uniform", rate=rate, mode=mode, cycles=40,
                         warmup=10, seed=2 ** 31 + 3)
    assert np.array_equal(r.per_cycle_delivered,
                          ref["per_cycle"]["delivered"])
    assert np.array_equal(r.per_cycle_in_flight,
                          ref["per_cycle"]["in_flight"])
    assert r.avg_latency == ref["summary"]["avg_latency"]


def test_ring_to_completion_matches_run_workload():
    from repro.core import build_slimfly
    from repro.sim import SimTables
    from repro.sim.workloads import (WorkloadSimConfig, ring_all_reduce,
                                     run_workload)

    t = SimTables.build(build_slimfly(5))
    r = run_workload(t, ring_all_reduce(16, 4),
                     WorkloadSimConfig(placement="spread", chunk=32,
                                       max_cycles=512))
    ref = runs.closed_loop(fabric.build({"family": "slimfly", "q": 5}), SW,
                           kind="ring_all_reduce",
                           args={"n_ranks": 16, "chunk_flits": 4},
                           placement="spread", mode="min", chunk=32,
                           max_cycles=512)
    assert r.completed and ref["completed"]
    assert r.makespan == ref["makespan"] and r.cycles_run == ref["cycles_run"]
    assert np.array_equal(r.msg_done, ref["msg_done"])
    assert np.array_equal(r.per_cycle_delivered, ref["per_cycle_delivered"])


@pytest.mark.parametrize("config", ["sf_q19", "df_h7"])
def test_cells_switch_matches_at_q5(config):
    """The committed configurations' switch settings (the W=6 window
    among them), on a fabric a test run holds: both engines agree with
    the reference."""
    import json
    import os

    from repro.core import build_slimfly
    from repro.sim import SimConfig, SimTables, make_traffic, simulate
    from repro.sim.workloads import (WorkloadSimConfig, ring_all_reduce,
                                     run_workload)

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", config + ".json")) as f:
        sw = json.load(f)["switch"]
    t = SimTables.build(build_slimfly(5))
    f5 = fabric.build({"family": "slimfly", "q": 5})
    r = simulate(t, make_traffic(t, "uniform"),
                 SimConfig(injection_rate=0.5, cycles=40, warmup=10,
                           mode="ugal_l", seed=2 ** 31 + 9, **sw))
    ref = runs.open_loop(f5, Switch(**sw), pattern="uniform", rate=0.5,
                         mode="ugal_l", cycles=40, warmup=10,
                         seed=2 ** 31 + 9)
    assert np.array_equal(r.per_cycle_delivered,
                          ref["per_cycle"]["delivered"])
    assert r.avg_latency == ref["summary"]["avg_latency"]
    w = run_workload(t, ring_all_reduce(16, 16),
                     WorkloadSimConfig(placement="spread", chunk=32,
                                       max_cycles=96, **sw))
    ref = runs.closed_loop(f5, Switch(**sw), kind="ring_all_reduce",
                           args={"n_ranks": 16, "chunk_flits": 16},
                           placement="spread", mode="min", chunk=32,
                           max_cycles=96)
    assert (w.msg_done >= 0).any()
    assert np.array_equal(w.msg_done, ref["msg_done"])
    assert np.array_equal(w.per_cycle_delivered, ref["per_cycle_delivered"])
