#!/usr/bin/env python3
"""Record the small stage traces that `test_bench_stages.py` reads.

    python3 bench/tests/record_q5_stages.py bench/tests/data

Run on a TPU.  For a q=5 Slim Fly with the benchmark's switch (4 VCs,
16-flit queues, W=6, 4 Valiant candidates) it makes two calls, each
once to compile and once traced, in a `bench.call` span and with the
profiler options of the benchmark's `--trace 1`:

- `open`: `simulate`, 6 cycles of uniform traffic at 0.5 under UGAL-L;
- `ring`: `run_workload` of a 16-rank ring all-reduce of 4 flits a
  step, MIN, spread placement, two chunks of 4 cycles.

For each it writes the profile as `q5_stages_<call>.xplane.pb.gz` and
the optimised HLO text of the runner that ran (whose `op_name`
metadata names the stages) as `q5_stages_<call>.hlo.gz`.
"""

import glob
import gzip
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

SWITCH = dict(vcs=4, q_net=16, q_src=64, lookahead=6, n_val_candidates=4)


def calls():
    from repro.core import build_slimfly
    from repro.sim import SimConfig, SimTables, engine, make_traffic
    from repro.sim import simulate
    from repro.sim.workloads import (WorkloadSimConfig, closed_loop,
                                     ring_all_reduce, run_workload)

    tables = SimTables.build(build_slimfly(5))
    traffic = make_traffic(tables, "uniform")
    cfg = SimConfig(injection_rate=0.5, cycles=6, warmup=2, mode="ugal_l",
                    seed=3, **SWITCH)
    wl = ring_all_reduce(16, 4)
    wcfg = WorkloadSimConfig(mode="min", placement="spread", chunk=4,
                             max_cycles=8, **SWITCH)
    return {"open": (lambda: simulate(tables, traffic, cfg),
                     engine.compiled_runner_hlo),
            "ring": (lambda: run_workload(tables, wl, wcfg),
                     closed_loop.compiled_runner_hlo)}


def main(out_dir: str) -> int:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    os.makedirs(out_dir, exist_ok=True)
    for name, (call, hlo) in calls().items():
        call()
        with tempfile.TemporaryDirectory() as d:
            with jax.profiler.trace(d, profiler_options=opts):
                with jax.profiler.TraceAnnotation("bench.call"):
                    call()
            (pb,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                              recursive=True)
            dst = os.path.join(out_dir, f"q5_stages_{name}.xplane.pb.gz")
            with open(pb, "rb") as f, gzip.open(dst, "wb") as g:
                shutil.copyfileobj(f, g)
        (text,) = hlo()
        with gzip.open(os.path.join(out_dir, f"q5_stages_{name}.hlo.gz"),
                       "wt") as g:
            g.write(text)
        print(f"{name}: {dst}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
