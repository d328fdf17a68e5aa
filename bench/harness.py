"""Run one benchmark cell once: set-up, the measured window, the check
against the plain reference, and one result line.

Everything that belongs to one configuration, traffic mix, engine,
fabric family, routing mode, collective or per-layer metric sits in a
file of its own, found by name, so that a new one arrives as a new file
and an entry in `BENCHMARK.json`:

- `BENCHMARK.json` names the cells (configuration x traffic mix) and
  the metrics;
- a configuration is the JSON file its entry names; its
  `topology.family` is built by the program's `build_<family>` (in
  `repro.core` or `repro.core.topologies`) and by the reference's
  `bench/reference/families/<family>.py`, both given the entry's other
  keys;
- a traffic mix is `bench/traffic/<mix>.json`; its `engine` key names
  `bench/engines/<engine>.py`, which builds the system under test once
  (`setup`), makes one call of its public entry point (`call`), counts
  the work of a call (`router_cycles`) and checks an answer against the
  plain reference (`observe`, `reference`, `compare`).  An engine
  reports the rate `RATE` where it declares one, else
  `<engine>.router_cycles_per_s`;
- a mix's `mode` is the program's routing mode and the reference's
  `bench/reference/modes/<mode>.py`; a closed-loop mix's `collective`
  is `repro.sim.workloads.<collective>` and the reference's
  `bench/reference/collectives/<collective>.py`, both given the mix's
  `args`; an open-loop mix with `rates` runs one lane per rate in one
  call, and the check compares one lane, drawn from the call's seed;
- a per-layer metric `<name>` is read by `bench/metrics/<name>.py`, or,
  for a name `<base>.<engine tag>`, by `bench/metrics/<base>.py`; its
  `read(ctx)` returns a number, or None when the run has nothing it
  can read.

The window is made of whole calls: calls start until `--seconds` have
passed, and a rate is all the work of all the calls over all their
time.  With `--trace 1` the window is one call, traced by the profiler,
and the line carries the per-layer metrics instead.
"""

from __future__ import annotations

import argparse
import collections
import importlib.util
import json
import os
import sys
import tempfile
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
BENCH_NAME = os.path.basename(BENCH_DIR)

# the JAX monitoring events that mean a function was traced or compiled
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


def log(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file of the benchmark by its path."""
    name = "bench_file_" + os.path.relpath(path, ROOT).replace(
        os.sep, "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def call_seed(seed: int, i: int) -> int:
    """Seed of call `i` of a run (the warm-up is call -1)."""
    return (seed * 1000003 + i + 1) % (1 << 31)


class Cell:
    """One cell of BENCHMARK.json with its configuration, mix and engine."""

    def __init__(self, root: str, name: str):
        self.root = root
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
        self.cell = cells[name]
        self.name = name
        cfg_entry = {c["name"]: c for c in self.bench["configs"]}[
            self.cell["config"]]
        self.config = load_json(os.path.join(root, cfg_entry["file"]))
        self.mix = load_json(os.path.join(
            root, BENCH_NAME, "traffic", self.cell["traffic"] + ".json"))
        self.engine = load_module(os.path.join(
            root, BENCH_NAME, "engines", self.mix["engine"] + ".py"))

    def applies(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"] if self.applies(m)]

    def per_layer(self) -> list:
        return [m for m in self.bench["per_layer"] if self.applies(m)]

    def metric_reader(self, name: str):
        d = os.path.join(self.root, BENCH_NAME, "metrics")
        for stem in (name, name.rsplit(".", 1)[0]):
            path = os.path.join(d, stem + ".py")
            if os.path.isfile(path):
                return load_module(path)
        raise SystemExit(f"bench: no reader for per-layer metric {name!r}")


def require_chip(n_chips: int) -> list:
    """The devices of the cell; exits, printing no result, without a TPU
    or with fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX platform is "
                         f"{devs[0].platform!r}); nothing was measured")
    if len(devs) < n_chips:
        raise SystemExit(f"bench: the cell needs {n_chips} chips, JAX "
                         f"finds {len(devs)}")
    return devs


def require_pallas(engine, state) -> None:
    if not engine.uses_pallas(state):
        raise SystemExit("bench: the switch would not take its Pallas path "
                         "on this device; nothing was measured")


def compilation_cache() -> tuple:
    """Turn on JAX's persistent compilation cache: at
    `$JAX_COMPILATION_CACHE_DIR` where that is set, else at the fixed
    `<checkout>/.jax_cache`, so that only a cell's first run compiles."""
    from repro.bench import enable_compilation_cache

    return enable_compilation_cache()


def device_record(devs: list) -> dict:
    import jax

    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts tracings and compilations from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.events = collections.Counter()
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, name, *args, **kwargs):
        if self.on and name in COMPILE_EVENTS:
            self.events[name] += 1

    def count(self) -> int:
        return sum(self.events.values())


def measure(engine, state, seed: int, seconds: float) -> dict:
    """Whole calls until `seconds` have passed; the last ends past it."""
    calls, t0 = [], time.perf_counter()
    while not calls or time.perf_counter() - t0 < seconds:
        s = call_seed(seed, len(calls))
        t = time.perf_counter()
        result = engine.call(state, s)
        calls.append(dict(seed=s, seconds=time.perf_counter() - t,
                          work=engine.router_cycles(state, result),
                          answer=engine.observe(result)))
    return dict(calls=calls, seconds=time.perf_counter() - t0)


def traced_call(engine, state, seed: int, trace_dir: str) -> dict:
    """One call under the profiler, wrapped in a `bench.call` span."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    s = call_seed(seed, 0)
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench.call"):
            t = time.perf_counter()
            result = engine.call(state, s)
            seconds = time.perf_counter() - t
    return dict(calls=[dict(seed=s, seconds=seconds,
                            work=engine.router_cycles(state, result),
                            answer=engine.observe(result))],
                seconds=seconds)


def check(engine, state, window: dict, seed: int) -> list:
    """Compare one call of the window, drawn from the seed, with the plain
    reference.  Returns [(name, value, limit[, where])], `where` a dict
    that says which part of the answer was compared."""
    import numpy as np

    calls = window["calls"]
    pick = calls[int(np.random.default_rng(seed).integers(len(calls)))]
    want = engine.reference(state, pick["seed"])
    log(f"bench: checked call seed {pick['seed']}: "
        f"{engine.parts(pick['answer'], want)}")
    return engine.compare(pick["answer"], want)


def rate_metric(cell: Cell) -> str:
    """The end-to-end rate the cell's engine reports."""
    return getattr(cell.engine, "RATE",
                   f"{cell.mix['engine']}.router_cycles_per_s")


def memory_line(devs: list) -> str:
    """Bytes the allocator holds now and at its peak so far, per chip."""
    stats = [d.memory_stats() or {} for d in devs]
    return ", ".join(f"{s.get('bytes_in_use', 0)} in use, peak "
                     f"{s.get('peak_bytes_in_use', 0)}" for s in stats)


def prepare(cell: Cell, seed: int) -> tuple:
    """The set-up of a run: the chip, the compilation cache, the system
    under test built once, and one warm-up call.  Returns (devices,
    state)."""
    devs = require_chip(int(cell.cell["chips"]))
    state_cache, cache_dir = compilation_cache()
    import jax

    log(f"bench: {cell.name} on {devs[0].platform} {devs[0].device_kind} "
        f"x{len(jax.devices())}; jax {jax.__version__}; compilation cache "
        f"{state_cache} ({cache_dir})")
    engine = cell.engine
    t = time.perf_counter()
    state = engine.setup(cell.config, cell.mix)
    log(f"bench: tables and system {time.perf_counter() - t!r} s; "
        f"memory {memory_line(devs)}")
    require_pallas(engine, state)
    t = time.perf_counter()
    getattr(engine, "warmup", engine.call)(state, call_seed(seed, -1))
    log(f"bench: compile and warm-up call {time.perf_counter() - t!r} s; "
        f"memory {memory_line(devs)}")
    return devs, state


def main(argv=None, root: str = ROOT, t_start: float = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell(root, args.workload)
    devs, state = prepare(cell, args.seed)
    counter = CompileCounter()
    engine = cell.engine
    setup_s = time.perf_counter() - t_start
    log(f"bench: set-up {setup_s!r} s")

    counter.on = True
    trace_summary = None
    if args.trace:
        from bench import stages
        from bench import trace as trace_mod

        with tempfile.TemporaryDirectory() as d:
            window = traced_call(engine, state, args.seed, d)
            profile = trace_mod.load_profile(trace_mod.find_profile(d))
            trace_summary = trace_mod.reduce_planes(profile.planes)
            idle_spans = stages.idle_by_span(profile.planes)
    else:
        window = measure(engine, state, args.seed, args.seconds)
    counter.on = False
    n_calls = len(window["calls"])
    work = sum(c["work"] for c in window["calls"])
    log(f"bench: window {window['seconds']!r} s, {n_calls} calls "
        f"({[c['seconds'] for c in window['calls']]}), {work} router-cycles, "
        f"{counter.count()} compilations inside the window; memory "
        f"{memory_line(devs)}")
    device = device_record(devs[:int(cell.cell["chips"])])

    metrics, breakdown = {}, None
    if args.trace:
        ctx = dict(trace=trace_summary, window=window, device=device,
                   sizes=engine.sizes(state))
        for m in cell.per_layer():
            value = cell.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = trace_summary.busy_s
        device["window_s"] = trace_summary.window_s
        stage_s = collections.Counter(ctx.get("stage_seconds", {}))
        breakdown = {"device_ops": trace_summary.top_ops(10),
                     "idle_gaps": trace_summary.top_gaps(10),
                     "stages": [list(kv) for kv in stage_s.most_common(10)],
                     "idle_by_span": idle_spans[:10]}
    else:
        taken = {"setup_s": setup_s,
                 rate_metric(cell): work / window["seconds"]}
        for m in cell.end_to_end():
            if m["name"] not in taken:
                raise SystemExit(f"bench: the harness takes no {m['name']!r}")
            metrics[m["name"]] = {"value": taken[m["name"]],
                                  "unit": m["unit"]}

    t = time.perf_counter()
    numbers = [(name, v, limit, where[0] if where else {}) for
               name, v, limit, *where in check(engine, state, window,
                                               args.seed)]
    log(f"bench: reference check {time.perf_counter() - t!r} s")
    correct = all(v <= limit for _, v, limit, _ in numbers)
    out = {"correct": correct, "attempted": n_calls,
           "failed": 0 if correct else 1, "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {name: {"value": v, "limit": limit, **where}
                     for name, v, limit, where in numbers}
    for name, v, limit, where in numbers:
        at = "".join(f" {k} {x}" for k, x in where.items())
        print(f"check {name} = {v} (limit {limit}){at}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
