"""Open-loop engine: one call is one `repro.sim.simulate` study.

The tables and the traffic object are built once and every call reuses
them, so after the warm-up every call hits the simulator's compiled
runner.  The seed reaches the program only as `SimConfig.seed`, a
traced operand, so a new seed never recompiles."""

from __future__ import annotations

import dataclasses

import numpy as np

from bench.reference import fabric as ref_fabric
from bench.reference import runs as ref_runs
from bench.reference.network import Switch


@dataclasses.dataclass
class State:
    config: dict
    mix: dict
    tables: object
    traffic: object
    cfg: object


def topology(spec: dict):
    from repro.core import build_slimfly
    from repro.core.topologies import build_dragonfly

    if spec["family"] == "slimfly":
        return build_slimfly(int(spec["q"]))
    if spec["family"] == "dragonfly":
        return build_dragonfly(h=int(spec["h"]))
    raise ValueError(f"unknown fabric family {spec['family']!r}")


def setup(config: dict, mix: dict) -> State:
    from repro.sim import SimConfig, SimTables, make_traffic

    if mix.get("lanes", 1) != 1:
        raise ValueError("the open-loop engine runs one lane per call")
    tables = SimTables.build(topology(config["topology"]))
    traffic = make_traffic(tables, mix["pattern"])
    sw = config["switch"]
    cfg = SimConfig(injection_rate=float(mix["injection_rate"]),
                    cycles=int(mix["cycles"]), warmup=int(mix["warmup"]),
                    mode=mix["mode"], vcs=sw["vcs"], q_net=sw["q_net"],
                    q_src=sw["q_src"], lookahead=sw["lookahead"],
                    n_val_candidates=sw["n_val_candidates"])
    return State(config, mix, tables, traffic, cfg)


def uses_pallas(state: State) -> bool:
    from repro.sim import SwitchCore

    return SwitchCore(state.tables, state.cfg).use_pallas


def call(state: State, seed: int):
    """One study, ending in host numpy (so the device has finished)."""
    from repro.sim import simulate

    return simulate(state.tables, state.traffic,
                    dataclasses.replace(state.cfg, seed=seed))


def router_cycles(state: State, result) -> int:
    return state.tables.n_routers * state.cfg.cycles


def sizes(state: State) -> dict:
    """Logical sizes of one cycle, for the kernels' byte counts; the
    closed-loop engine shares it (its state has the same `tables` and a
    config with the same switch fields)."""
    t, c = state.tables, state.cfg
    return dict(N=t.n_routers, P=t.P, V=c.vcs, W=c.lookahead, PE=t.p,
                E=t.n_endpoints, C=c.n_val_candidates,
                ugal=c.mode in ("ugal_l", "ugal_g"))


def observe(result) -> dict:
    """What the check compares, from the program's answer."""
    return {
        "per_cycle": {
            "injected": result.per_cycle_injected,
            "delivered": result.per_cycle_delivered,
            "dropped": result.per_cycle_dropped,
            "in_flight": result.per_cycle_in_flight},
        "summary": {k: getattr(result, k) for k in (
            "accepted_load", "avg_latency", "delivered", "injected",
            "dropped_at_source", "src_occupancy")}}


def reference(state: State, seed: int, control: bool = False) -> dict:
    """The plain reference's answer for the call made with `seed`; the
    control keeps the float32 latency sum in bfloat16."""
    import ml_dtypes

    cfg, mix = state.cfg, state.mix
    out = ref_runs.open_loop(
        ref_fabric.build(state.config["topology"]),
        Switch(**state.config["switch"]), pattern=mix["pattern"],
        rate=cfg.injection_rate, mode=cfg.mode, cycles=cfg.cycles,
        warmup=cfg.warmup, seed=seed,
        latency_dtype=ml_dtypes.bfloat16 if control else np.float32)
    out["per_cycle"] = {k: out["per_cycle"][k] for k in
                        ("injected", "delivered", "dropped", "in_flight")}
    return out


def parts(got: dict, want: dict) -> dict:
    """Where the answer departs from the reference, counted by part."""
    per_g, per_w = got["per_cycle"], want["per_cycle"]
    entries = 0
    for k, w in per_w.items():
        g = np.asarray(per_g[k])
        entries += int((g != w).sum()) if g.shape == w.shape else w.size
    fields = sum(got["summary"][k] != v for k, v in want["summary"].items())
    return {"per_cycle_counts": entries, "summary_fields": int(fields)}


def compare(got: dict, want: dict) -> list:
    """[(name, value, limit)]: per-cycle counts and summary fields that
    differ from the reference, which must be none."""
    return [("open.mismatches", sum(parts(got, want).values()), 0)]
