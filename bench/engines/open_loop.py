"""Open-loop engine: one call is one `repro.sim.simulate` study, or, for
a mix that gives `rates`, one `repro.sim.sweep.sweep_simulate` over one
lane per rate.

The tables and the traffic object are built once and every call reuses
them, so after the warm-up every call hits the simulator's compiled
runner.  The seed reaches the program only as `SimConfig.seed`, a
traced operand, so a new seed never recompiles; every lane of a call
runs with the call's seed and shares the one table set."""

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
    rates: list                   # injection rate of each lane

    @property
    def lanes(self) -> int:
        return len(self.rates)


def topology(spec: dict):
    """The program's fabric: `build_<family>` of `repro.core` or of
    `repro.core.topologies`, given the entry's other keys."""
    import repro.core
    import repro.core.topologies

    name = "build_" + spec["family"]
    build = (getattr(repro.core, name, None)
             or getattr(repro.core.topologies, name))
    return build(**{k: v for k, v in spec.items() if k != "family"})


def tables(config: dict, mix: dict):
    """`SimTables` of the configuration's fabric, built with the mix's
    `tables` arguments (such as `{"ecmp": true}`), if any."""
    from repro.sim import SimTables

    return SimTables.build(topology(config["topology"]),
                           **mix.get("tables", {}))


def setup(config: dict, mix: dict) -> State:
    from repro.sim import SimConfig, make_traffic

    rates = [float(r) for r in mix.get("rates", [mix.get("injection_rate")])]
    tab = tables(config, mix)
    traffic = make_traffic(tab, mix["pattern"])
    sw = config["switch"]
    cfg = SimConfig(injection_rate=rates[0],
                    cycles=int(mix["cycles"]), warmup=int(mix["warmup"]),
                    mode=mix["mode"], vcs=sw["vcs"], q_net=sw["q_net"],
                    q_src=sw["q_src"], lookahead=sw["lookahead"],
                    n_val_candidates=sw["n_val_candidates"])
    return State(config, mix, tab, traffic, cfg, rates)


def uses_pallas(state: State) -> bool:
    from repro.sim import SwitchCore

    return SwitchCore(state.tables, state.cfg).use_pallas


def call(state: State, seed: int):
    """One study, or one lane sweep, ending in host numpy (so the device
    has finished)."""
    from repro.sim import simulate
    from repro.sim.sweep import sweep_simulate

    cfg = dataclasses.replace(state.cfg, seed=seed)
    if "rates" not in state.mix:
        return simulate(state.tables, state.traffic, cfg)
    return sweep_simulate(state.tables, state.traffic, cfg,
                          rates=state.rates)


def router_cycles(state: State, result) -> int:
    return state.tables.n_routers * state.cfg.cycles * state.lanes


def sizes(state: State) -> dict:
    """Logical sizes of one cycle of one lane, for the kernels' byte
    counts; the closed-loop engine shares it (its state has the same
    `tables` and a config with the same switch fields)."""
    t, c = state.tables, state.cfg
    return dict(N=t.n_routers, P=t.P, V=c.vcs, W=c.lookahead, PE=t.p,
                E=t.n_endpoints, C=c.n_val_candidates,
                ugal=c.mode in ("ugal_l", "ugal_g"))


def _answer(result) -> dict:
    return {
        "per_cycle": {
            "injected": result.per_cycle_injected,
            "delivered": result.per_cycle_delivered,
            "dropped": result.per_cycle_dropped,
            "in_flight": result.per_cycle_in_flight},
        "summary": {k: getattr(result, k) for k in (
            "accepted_load", "avg_latency", "delivered", "injected",
            "dropped_at_source", "src_occupancy")}}


def observe(result) -> dict:
    """What the check compares, from the program's answer: one study's,
    or each lane's under `lanes`."""
    if isinstance(result, list):
        return {"lanes": [_answer(r) for r in result]}
    return _answer(result)


def lane_of(state: State, seed: int) -> int:
    """The lane the check compares, drawn from the call's seed."""
    return int(np.random.default_rng(seed).integers(state.lanes))


def reference(state: State, seed: int, control: bool = False,
              lane: int = None) -> dict:
    """The plain reference's answer for the call made with `seed`, at
    the rate of `lane` (by default the one `lane_of` draws); the
    control keeps the float32 latency sum in bfloat16."""
    import ml_dtypes

    cfg, mix = state.cfg, state.mix
    lane = lane_of(state, seed) if lane is None else lane
    out = ref_runs.open_loop(
        ref_fabric.build(state.config["topology"]),
        Switch(**state.config["switch"]), pattern=mix["pattern"],
        rate=state.rates[lane], mode=cfg.mode, cycles=cfg.cycles,
        warmup=cfg.warmup, seed=seed,
        latency_dtype=ml_dtypes.bfloat16 if control else np.float32)
    out["per_cycle"] = {k: out["per_cycle"][k] for k in
                        ("injected", "delivered", "dropped", "in_flight")}
    if "rates" in mix:
        out.update(lane=lane, injection_rate=state.rates[lane])
    return out


def parts(got: dict, want: dict) -> dict:
    """Where the answer departs from the reference, counted by part;
    a lane sweep's answer is taken at the reference's lane."""
    if "lanes" in got:
        got = got["lanes"][want["lane"]]
    per_g, per_w = got["per_cycle"], want["per_cycle"]
    entries = 0
    for k, w in per_w.items():
        g = np.asarray(per_g[k])
        entries += int((g != w).sum()) if g.shape == w.shape else w.size
    fields = sum(got["summary"][k] != v for k, v in want["summary"].items())
    return {"per_cycle_counts": entries, "summary_fields": int(fields)}


def compare(got: dict, want: dict) -> list:
    """[(name, value, limit[, where])]: per-cycle counts and summary
    fields that differ from the reference, which must be none; `where`
    names the lane of a lane sweep and its rate."""
    n = sum(parts(got, want).values())
    if "lane" not in want:
        return [("open.mismatches", n, 0)]
    return [("open.mismatches", n, 0,
             {"lane": want["lane"], "injection_rate": want["injection_rate"]})]
