"""Telemetry export benchmark + artifact writer (DESIGN.md §12).

Two things in one module:

  1. the fig6-smoke-shaped q=5 load sweep with COUNTERS ON — all rate
     lanes in one compiled launch — exported as a per-lane channel-load
     heatmap (``TELEMETRY_channel_load.json``);
  2. a small closed-loop collective with full tracing, exported as
     perfetto-compatible Chrome-trace JSON (``TELEMETRY_trace.json``,
     load it at https://ui.perfetto.dev).

Artifacts land in ``$REPRO_TELEMETRY_DIR`` when set, else next to
``$REPRO_BENCH_OUT``, else the working directory.
"""

import json
import os
import time

from repro.core import build_slimfly
from repro.sim import (SimConfig, SimTables, TelemetryConfig, make_traffic,
                       sweep_simulate)
from repro.sim.telemetry import export
from repro.sim.workloads import WorkloadSimConfig, run_workload
from repro.sim.workloads.ir import ring_all_reduce


def _artifact_dir() -> str:
    d = os.environ.get("REPRO_TELEMETRY_DIR")
    if d:
        os.makedirs(d, exist_ok=True)
        return d
    bench_out = os.environ.get("REPRO_BENCH_OUT")
    if bench_out and os.path.dirname(bench_out):
        return os.path.dirname(bench_out)
    return "."


def run(fast: bool = True):
    full = os.environ.get("REPRO_FULL", "0") == "1" or not fast
    smoke = os.environ.get("REPRO_SMOKE", "0") == "1" and not full
    out_dir = _artifact_dir()

    q = 19 if full else 5
    cycles, warmup = (3000, 1000) if full else ((250, 80) if smoke
                                                else (700, 250))
    loads = ([0.1, 0.3, 0.5, 0.7, 0.9] if full
             else ([0.5, 0.8] if smoke else [0.1, 0.5, 0.8]))

    tables = SimTables.build(build_slimfly(q))
    traffic = make_traffic(tables, "uniform")
    rows = []

    # ---- 1. counters-on fig6-shaped sweep -> per-lane heatmap --------------
    tc = TelemetryConfig(counters=True)
    cfg = SimConfig(cycles=cycles, warmup=warmup, mode="ugal_l",
                    lookahead=6 if full else 4, telemetry=tc)
    t0 = time.time()
    res = sweep_simulate(tables, traffic, cfg, rates=loads)
    sweep_s = time.time() - t0
    heat_path = os.path.join(out_dir, "TELEMETRY_channel_load.json")
    doc = export.write_channel_heatmap(
        heat_path, [r.telemetry for r in res],
        lane_labels=[f"rate={r.offered_load}" for r in res])
    # conservation across every lane: grants == channel forwards +
    # ejections (the drained-run hop identity is asserted in tests)
    for r in res:
        cs = r.telemetry.counters
        assert cs.alloc_grant.sum() == (cs.chan_flits.sum()
                                        + cs.ej_count.sum())
    peak = max(row["load"] for lane in doc["lanes"]
               for row in lane["hottest_channels"])
    rows.append(dict(name=f"telemetry/heatmap_q{q}",
                     lanes=doc["n_lanes"], sweep_s=round(sweep_s, 2),
                     derived=round(peak, 4)))       # hottest channel load

    # ---- 2. traced closed-loop run -> perfetto Chrome trace ----------------
    k, chunk_flits = (16, 128) if not smoke else (8, 64)
    wl = ring_all_reduce(k, chunk_flits // 16)
    wcfg = WorkloadSimConfig(
        mode="ugal_l", placement="linear", chunk=128,
        telemetry=TelemetryConfig(counters=True, trace=True,
                                  trace_sample_shift=0,
                                  trace_capacity=1 << 15))
    wres = run_workload(tables, wl, wcfg)
    trace_path = os.path.join(out_dir, "TELEMETRY_trace.json")
    tdoc = export.write_chrome_trace(
        trace_path, wres.telemetry,
        per_cycle_counter=wres.per_cycle_delivered)
    with open(trace_path) as f:                      # exporter sanity
        loaded = json.load(f)
    assert loaded["traceEvents"], "empty trace"
    rows.append(dict(name="telemetry/trace_ring",
                     events=len(wres.telemetry.events),
                     spans=tdoc["otherData"]["n_spans"],
                     dropped=wres.telemetry.events_dropped,
                     derived=float(tdoc["otherData"]["n_spans"])))
    return rows
