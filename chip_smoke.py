#!/usr/bin/env python3
"""End-to-end smoke run of the flit simulator on one TPU chip.

Drives the simulator's main entry points once, at the paper's §V size
(Slim Fly q=19: 722 routers, 10,830 endpoints), on the Pallas kernel
path, and checks every result against the repo's own references:

  (a) q=5 goldens: `simulate` with the configs of
      tests/test_engine_scaling.py::test_golden_outcomes_q5 must give
      the same integers as on the CPU;
  (b) §V fabric, open loop: uniform traffic, UGAL-L, injection rate
      0.5, 600 cycles (200 warmup), on the Pallas path and on the `ref`
      path — every SimResult field and per-cycle array identical, and
      flits conserved at every cycle;
  (c) the fig6 load curve as one launch: `sweep_simulate` over five
      rates, Pallas and `ref` identical per lane, the 0.5 lane equal to
      (b);
  (d) closed loop: `run_workload` of a 128-rank ring all-reduce (4
      flits per step, MIN routing, spread placement) completes, with
      makespan and per-message latencies identical on both paths;
  (e) routing tables through the min-plus kernel: `build_routing` at
      q=19 with the Pallas APSP equals the jnp one.

Every compiled step of the Pallas path must hold a `tpu_custom_call`.
The per-phase timings it prints are smoke timings, not a benchmark: one
run on the host clock, where compile_s is the ahead-of-time lower and
compile of the step an entry point runs, and steady_s is the entry
point's call after it, which reuses that executable.  The last line of
stdout is one JSON object naming the device.

It exits non-zero, printing no result, where JAX finds no TPU, where the
simulator would not take its Pallas path, or where any check fails.

    python chip_smoke.py            # from the repo root; one chip
"""

import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# (mode, delivered, injected, avg_latency) of test_golden_outcomes_q5
Q5_GOLDENS = [("min", 10391, 10562, 3.407100285),
              ("ugal_l", 10244, 10562, 5.074401665)]
RATES = [0.1, 0.3, 0.5, 0.7, 0.9]


def log(msg):
    print(msg, flush=True)


def check(ok, what):
    if not ok:
        raise AssertionError(what)


def timed(fn):
    """(result, wall seconds) of one call."""
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def differing_field(a, b):
    """Name of the first field (or list index) where results differ."""
    if isinstance(a, list):
        for i, (x, y) in enumerate(zip(a, b)):
            bad = differing_field(x, y)
            if bad is not None:
                return f"[{i}].{bad}"
        return None if len(a) == len(b) else "len"
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if isinstance(x, np.ndarray):
            if not np.array_equal(x, y):
                return f.name
        elif x != y and not (x != x and y != y):           # nan == nan
            return f.name
    return None


def compile_step(what, path, fn, *args):
    """Compile the step `fn(*args)` ahead of the entry point that runs
    it (the call then reuses the executable) and check that it holds
    the Pallas kernel exactly on the Pallas path.  Returns the compile
    seconds."""
    t0 = time.perf_counter()
    text = fn.lower(*args).compile().as_text()
    seconds = time.perf_counter() - t0
    check(("tpu_custom_call" in text) == (path == "auto"),
          f"{what} ({path}): Pallas kernel presence is wrong")
    return seconds


def timing(phase, path, compile_s, steady_s, router_cycles):
    path = "pallas" if path == "auto" else path
    log(f"smoke-timing phase={phase} path={path} "
        f"compile_s={compile_s!r} steady_s={steady_s!r} "
        f"router_cycles_per_s={router_cycles / steady_s!r}")


def open_loop_step(tables, traffic, cfg):
    """The compiled scan `simulate` runs for cfg, and its arguments."""
    import jax
    import jax.numpy as jnp

    from repro.sim import telemetry as tel
    from repro.sim.engine import _open_loop_runner

    core, fn = _open_loop_runner(tables, traffic, cfg)
    carry = core.init_queues() + (jax.random.PRNGKey(cfg.seed),
                                  tel.init_state(cfg.telemetry, core))
    return fn, carry, jnp.float32(cfg.injection_rate)


def sweep_step(tables, traffic, cfg, lanes):
    """The compiled lane-batched scan `sweep_simulate` runs."""
    import jax
    import jax.numpy as jnp

    from repro.sim.sweep import _sweep_runner

    core, fn = _sweep_runner(tables, traffic, cfg, lanes, tables_vary=False)
    carry = tuple(jnp.zeros((lanes,) + q.shape, q.dtype)
                  for q in core.init_queues())
    carry += (jnp.stack([jax.random.PRNGKey(cfg.seed)] * lanes), ())
    return fn, carry, jnp.zeros((lanes,), jnp.float32)


def phase_a():
    from repro.core import build_slimfly
    from repro.sim import SimConfig, SimTables, make_traffic, simulate

    tables = SimTables.build(build_slimfly(5))
    uni = make_traffic(tables, "uniform")
    for mode, delivered, injected, latency in Q5_GOLDENS:
        cfg = SimConfig(injection_rate=0.35, cycles=150, warmup=40,
                        mode=mode, seed=7)
        comp = compile_step(f"q=5 {mode}", "auto",
                            *open_loop_step(tables, uni, cfg))
        r, steady = timed(lambda: simulate(tables, uni, cfg))
        got = (r.delivered, r.injected, round(r.avg_latency, 9))
        check(got == (delivered, injected, latency),
              f"q=5 {mode} golden: got {got}")
        timing(f"a_q5_golden_{mode}", "auto", comp, steady,
               tables.n_routers * cfg.cycles)
        log(f"phase a ok: q=5 {mode} delivered={r.delivered} "
            f"injected={r.injected} avg_latency={r.avg_latency!r}")


def phase_b(tables, uni):
    from repro.sim import SimConfig, simulate

    cfg = SimConfig(injection_rate=0.5, cycles=600, warmup=200,
                    mode="ugal_l", seed=0)
    res = {}
    for path in ("auto", "ref"):
        c = dataclasses.replace(cfg, kernel_path=path)
        comp = compile_step("open loop", path,
                            *open_loop_step(tables, uni, c))
        r, steady = timed(lambda: simulate(tables, uni, c))
        timing("b_open_loop_q19_ugal_l", path, comp, steady,
               tables.n_routers * cfg.cycles)
        res[path] = r
    r = res["auto"]
    bad = differing_field(r, res["ref"])
    check(bad is None, f"open loop: Pallas and ref differ in {bad}")
    cum_in = np.cumsum(r.per_cycle_injected)
    cum_out = np.cumsum(r.per_cycle_delivered)
    check(np.array_equal(cum_in, cum_out + r.per_cycle_in_flight),
          "open loop: flits not conserved")
    check(r.delivered > 0 and r.dropped_at_source
          == int(r.per_cycle_dropped.sum()), "open loop: bad counters")
    log(f"phase b ok: q=19 ugal_l rate=0.5 injected={r.injected} "
        f"delivered={r.delivered} in_flight={int(r.per_cycle_in_flight[-1])} "
        f"dropped={r.dropped_at_source} accepted={r.accepted_load!r} "
        f"avg_latency={r.avg_latency!r}; pallas == ref on every field")
    return cfg, r


def phase_c(tables, uni, cfg, single):
    from repro.sim import sweep_simulate

    res = {}
    for path in ("auto", "ref"):
        c = dataclasses.replace(cfg, kernel_path=path)
        comp = compile_step("sweep", path,
                            *sweep_step(tables, uni, c, len(RATES)))
        rs, steady = timed(lambda: sweep_simulate(tables, uni, c,
                                                  rates=RATES))
        timing("c_fig6_sweep_q19_L5", path, comp, steady,
               tables.n_routers * cfg.cycles * len(RATES))
        res[path] = rs
    bad = differing_field(res["auto"], res["ref"])
    check(bad is None, f"sweep: Pallas and ref differ in lane {bad}")
    bad = differing_field(res["auto"][RATES.index(0.5)], single)
    check(bad is None, f"sweep: the 0.5 lane differs from phase b in {bad}")
    log("phase c ok: accepted load per rate "
        + " ".join(f"{r.offered_load}:{r.accepted_load!r}"
                   for r in res["auto"])
        + "; pallas == ref per lane; lane 0.5 == phase b")


def phase_d(tables):
    import jax
    import jax.numpy as jnp

    from repro.sim.workloads import (WorkloadSimConfig, place_ranks,
                                     ring_all_reduce, run_workload)
    from repro.sim.workloads.closed_loop import _chunk_runner

    wl = ring_all_reduce(128, 4)
    cfg = WorkloadSimConfig(mode="min", placement="spread", seed=0)
    res = {}
    for path in ("auto", "ref"):
        c = dataclasses.replace(cfg, kernel_path=path)
        ep = place_ranks(tables, wl.n_ranks, c.placement, seed=c.seed)
        run_chunk, init_carry, _ = _chunk_runner(tables, wl, ep, c)
        comp = compile_step("closed loop", path, run_chunk,
                            init_carry(jax.random.PRNGKey(c.seed)),
                            jnp.int32(0))
        r, steady = timed(lambda: run_workload(tables, wl, c))
        check(r.completed, f"ring all-reduce ({path}) did not complete")
        timing("d_ring_all_reduce_q19_128r", path, comp, steady,
               tables.n_routers * r.cycles_run)
        res[path] = r
    r = res["auto"]
    bad = differing_field(r, res["ref"])
    check(bad is None, f"closed loop: Pallas and ref differ in {bad}")
    log(f"phase d ok: ring_all_reduce(128 ranks, 4 flits) completed "
        f"makespan={r.makespan!r} flits={r.flits_delivered} "
        f"avg_msg_latency={r.avg_msg_latency!r}; pallas == ref on every "
        f"field")


def phase_e(topo):
    import jax.numpy as jnp

    from repro.core.routing import build_routing
    from repro.kernels.minplus import minplus_pallas

    t0 = time.perf_counter()
    pal = build_routing(topo, use_pallas=True)
    t1 = time.perf_counter()
    ref = build_routing(topo, use_pallas=False)
    check(np.array_equal(pal.dist, ref.dist)
          and np.array_equal(pal.next_hop, ref.next_hop),
          "routing: Pallas APSP differs from the jnp one")
    check(int(pal.dist.max()) == 2, "routing: SF q=19 diameter is not 2")
    x = jnp.zeros((1, topo.n_routers, topo.n_routers), jnp.float32)
    compile_step("routing", "auto", minplus_pallas, x, x)
    log(f"phase e ok: build_routing q=19 (Pallas APSP) == jnp, diameter 2, "
        f"first call {t1 - t0!r} s")


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                 f"{dev.platform!r}); nothing was run")

    from repro.bench import enable_compilation_cache
    from repro.core import build_slimfly
    from repro.sim import SimConfig, SimTables, SwitchCore, make_traffic

    state, cache_dir = enable_compilation_cache()
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compilation cache {state} ({cache_dir})")

    topo = build_slimfly(19)
    t0 = time.perf_counter()
    tables = SimTables.build(topo)
    log(f"SF q=19 tables: {tables.n_routers} routers, "
        f"{tables.n_endpoints} endpoints, built in "
        f"{time.perf_counter() - t0!r} s")
    if not SwitchCore(tables, SimConfig()).use_pallas:
        sys.exit("chip_smoke: the simulator would not take its Pallas "
                 "path on this device")
    uni = make_traffic(tables, "uniform")

    phase_a()
    cfg, single = phase_b(tables, uni)
    phase_c(tables, uni, cfg, single)
    phase_d(tables)
    phase_e(topo)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
