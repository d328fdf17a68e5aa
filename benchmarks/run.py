"""Benchmark harness — one module per paper table/figure + the framework
roofline.  Prints ``name,us_per_call,derived`` CSV (module wall time is
amortised over its rows), then one machine-parseable ``# SUMMARY``
JSON line with per-module wall time and status, so CI logs show where
smoke time goes.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only fig6]
"""

import argparse
import json
import sys
import time

MODULES = [
    "fig1_hops",
    "fig5_moore",
    "fig5c_bisection",
    "table3_resiliency",
    "faults_sweep",
    "fig6_perf",
    "workloads_jct",
    "multitenant",
    "fig8_buffers",
    "engine_scaling",
    "table4_cost",
    "topology_collectives",
    "collective_search",
    "roofline_bench",
    "telemetry_export",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale runs (q=19 sims etc.)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    # persistent compilation cache: must be configured before the first
    # jit of the process; a warm directory turns every unchanged
    # simulator compile into a deserialize
    from repro.bench import enable_compilation_cache
    cache_state, cache_dir = enable_compilation_cache()
    print(f"# compilation cache: {cache_state} ({cache_dir})",
          file=sys.stderr)

    print("name,us_per_call,derived")
    failures = 0
    summary = {}
    for modname in MODULES:
        if args.only and args.only not in modname:
            continue
        mod = __import__(f"benchmarks.{modname}", fromlist=["run"])
        t0 = time.time()
        try:
            rows = mod.run(fast=not args.full)
        except Exception as e:  # keep the harness going
            print(f"{modname}/ERROR,0,{type(e).__name__}:{e}",
                  file=sys.stdout)
            failures += 1
            summary[modname] = {"wall_s": round(time.time() - t0, 3),
                                "rows": 0, "status": "error",
                                "error": f"{type(e).__name__}: {e}"}
            continue
        wall = time.time() - t0
        summary[modname] = {"wall_s": round(wall, 3), "rows": len(rows),
                            "status": "ok"}
        dt_us = wall * 1e6 / max(len(rows), 1)
        for row in rows:
            extras = {k: v for k, v in row.items()
                      if k not in ("name", "derived")}
            suffix = ";".join(f"{k}={v}" for k, v in extras.items())
            derived = row.get("derived", "")
            if suffix:
                print(f"{row['name']},{dt_us:.0f},{derived} [{suffix}]")
            else:
                print(f"{row['name']},{dt_us:.0f},{derived}")
    # structured per-module wall-time/status trailer, greppable in CI
    # logs: `grep '^# SUMMARY' | sed 's/^# SUMMARY //' | jq .`
    print("# SUMMARY " + json.dumps(
        {"total_wall_s": round(sum(m["wall_s"] for m in summary.values()),
                               3),
         "failures": failures, "modules": summary}, sort_keys=True))
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
