"""Closed-loop engine: one call is one `repro.sim.workloads.run_workload`
of a collective, cut at the mix's `max_cycles`.  The collective is the
program's `repro.sim.workloads.<collective>(**args)` and the reference's
`bench/reference/collectives/<collective>.py`, both given the mix's
`args`.

The tables and the workload are built once; every call reuses them, so
after the warm-up every chunk hits the simulator's compiled runner.
The seed reaches the program as `WorkloadSimConfig.seed`, a traced
key; under MIN routing with `spread` placement nothing draws from it,
so every seed runs the same collective."""

from __future__ import annotations

import dataclasses

import numpy as np

from bench.engines.open_loop import sizes  # noqa: F401 (the harness calls it)
from bench.engines.open_loop import tables
from bench.reference import fabric as ref_fabric
from bench.reference import runs as ref_runs
from bench.reference.network import Switch


@dataclasses.dataclass
class State:
    config: dict
    mix: dict
    tables: object
    workload: object
    cfg: object


def build_workload(mix: dict):
    import repro.sim.workloads

    return getattr(repro.sim.workloads, mix["collective"])(**mix["args"])


def setup(config: dict, mix: dict) -> State:
    from repro.sim.workloads import WorkloadSimConfig

    sw = config["switch"]
    cfg = WorkloadSimConfig(mode=mix["mode"], placement=mix["placement"],
                            chunk=int(mix["chunk"]),
                            max_cycles=int(mix["max_cycles"]),
                            vcs=sw["vcs"], q_net=sw["q_net"],
                            q_src=sw["q_src"], lookahead=sw["lookahead"],
                            n_val_candidates=sw["n_val_candidates"])
    return State(config, mix, tables(config, mix), build_workload(mix), cfg)


def uses_pallas(state: State) -> bool:
    from repro.sim import SwitchCore

    return SwitchCore(state.tables, state.cfg.to_sim_config()).use_pallas


def call(state: State, seed: int, max_cycles=None):
    from repro.sim.workloads import run_workload

    cfg = dataclasses.replace(state.cfg, seed=seed)
    if max_cycles is not None:
        cfg = dataclasses.replace(cfg, max_cycles=max_cycles)
    return run_workload(state.tables, state.workload, cfg)


def warmup(state: State, seed: int):
    """One chunk through the same compiled chunk runner as the calls."""
    return call(state, seed, max_cycles=state.cfg.chunk)


def router_cycles(state: State, result) -> int:
    # the chunks that ran, not the makespan: a completed run stops at
    # its chunk boundary
    chunks = -(-int(result.cycles_run) // state.cfg.chunk)
    return state.tables.n_routers * chunks * state.cfg.chunk


FIELDS = ("completed", "makespan", "cycles_run", "flits_injected",
          "flits_delivered")
MSG_ARRAYS = ("msg_size", "msg_phase", "msg_sent", "msg_delivered",
              "msg_start", "msg_done")


def observe(result) -> dict:
    out = {k: getattr(result, k) for k in FIELDS + MSG_ARRAYS}
    out["per_cycle_delivered"] = result.per_cycle_delivered
    out["ep_of_rank"] = result.ep_of_rank
    return out


def reference(state: State, seed: int, control: bool = False) -> dict:
    """The plain reference's run; the control reads the dependency state
    one cycle late, which breaks the dependency-trigger guarantee."""
    mix, cfg = state.mix, state.cfg
    return ref_runs.closed_loop(
        ref_fabric.build(state.config["topology"]),
        Switch(**state.config["switch"]), kind=mix["collective"],
        args=mix["args"], placement=mix["placement"], mode=mix["mode"],
        chunk=cfg.chunk, max_cycles=cfg.max_cycles,
        stale_deps=1 if control else 0)


def parts(got: dict, want: dict) -> dict:
    """Where the answer departs from the reference, counted by part."""
    bad_msg = np.zeros(len(want["msg_sent"]), bool)
    for k in MSG_ARRAYS:
        g, w = np.asarray(got[k]), np.asarray(want[k])
        bad_msg |= (g != w) if g.shape == w.shape else True
    g, w = np.asarray(got["per_cycle_delivered"]), want["per_cycle_delivered"]
    cycles = (int((g != w).sum()) if g.shape == w.shape
              else max(len(g), len(w)))
    g, w = np.asarray(got["ep_of_rank"]), np.asarray(want["ep_of_rank"])
    fields = sum(got[k] != want[k] for k in FIELDS)
    fields += g.shape != w.shape or not np.array_equal(g, w)
    return {"messages": int(bad_msg.sum()), "per_cycle_delivered": cycles,
            "fields": int(fields)}


def compare(got: dict, want: dict) -> list:
    """[(name, value, limit)]: messages, cycles and fields that differ
    from the reference, which must be none."""
    return [("closed.mismatches", sum(parts(got, want).values()), 0)]
