"""Timing, memory, and JSON persistence for ``BENCH_*.json`` files.

Methodology:

- `bench_callable` separates the first call (trace + compile + device
  warmup, with the memory probe bracketing it) from the steady-state
  measurement: it times `repeats` further calls and reports min/mean
  wall seconds.  The min is the regression-gate number — it is the
  least noisy estimator on shared CI machines; the compile time is
  reported separately because a tracing regression is a real
  regression too.
- `peak_memory_bytes` prefers the JAX device allocator's
  ``peak_bytes_in_use`` (TPU/GPU); on CPU hosts, where the allocator
  exposes no stats, it falls back to `tracemalloc` around one call.
  tracemalloc only sees host-side Python allocations (device buffers
  are invisible to it), so that number is a coarse host-traffic proxy
  — which probe produced an entry is recorded in its ``mem_probe``
  field so trajectories never silently mix the two.  Paper-scale
  entries use the near-free RSS high-water probe (``cheap=True`` /
  ``measure_memory="rss"``) instead of tracemalloc, whose hooks would
  dominate a q=17 run; ``peak_mem_bytes`` is therefore never null.
- `enable_compilation_cache` turns on JAX's persistent compilation
  cache (at ``$JAX_COMPILATION_CACHE_DIR`` where that is set, else at
  ``<checkout>/.jax_cache``) and reports whether the directory was
  cold or warm, so benchmark wall times can distinguish a real XLA
  compile from a cache deserialize.  CI persists the directory across
  runs.

Schema (``BENCH_*.json``)::

    {"schema": 1, "suite": "engine_scaling", "backend": "cpu",
     "entries": {"<name>": {"wall_s": .., "compile_s": ..,
                            "cycles": .., "cycles_per_sec": ..,
                            "peak_mem_bytes": .., "mem_probe": "..",
                            "meta": {...}}}}

`check_regression` compares one metric of one entry between a baseline
file and fresh numbers with a multiplicative tolerance, for the CI
gate (``benchmarks/engine_scaling.py --check-regression``).  Machine
speeds differ between the laptop that wrote the baseline and the CI
runner, so gate factors must stay coarse (the default CI gate is 2x).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
import tracemalloc
from typing import Callable, Optional

__all__ = ["BenchEntry", "bench_callable", "peak_memory_bytes",
           "rss_hwm_bytes", "enable_compilation_cache",
           "write_bench", "load_bench", "check_regression",
           "repo_stamp", "lowering_breakdown"]

SCHEMA_VERSION = 1

_GIT_SHA_CACHE: list = []


def repo_stamp(telemetry: bool = False) -> dict:
    """Provenance stamp for a BENCH entry's meta: the git SHA of the
    working tree, the jax version, and whether the benched path had
    telemetry enabled — so BENCH_*.json trajectories stay attributable
    across PRs and across telemetry-on/off configurations."""
    import jax

    if not _GIT_SHA_CACHE:
        sha = "unknown"
        try:
            import subprocess
            out = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                cwd=os.path.dirname(os.path.abspath(__file__)),
                capture_output=True, text=True, timeout=10)
            if out.returncode == 0:
                sha = out.stdout.strip()
        except Exception:
            pass
        _GIT_SHA_CACHE.append(sha)
    return {"git_sha": _GIT_SHA_CACHE[0], "jax_version": jax.__version__,
            "telemetry": bool(telemetry)}


def lowering_breakdown(fn, *args) -> dict:
    """Split a jitted callable's pre-execution cost into tracing/
    lowering vs XLA compilation, in seconds: ``{"trace_lower_s": ..,
    "xla_compile_s": ..}``.  Telemetry changes the traced graph (extra
    carry arrays, counter updates), so benchmarks report both phases
    separately to show where a config's compile tax actually goes.
    `fn` must be a jax.jit-wrapped callable (it needs `.lower`)."""
    t0 = time.perf_counter()
    lowered = fn.lower(*args)
    t1 = time.perf_counter()
    lowered.compile()
    t2 = time.perf_counter()
    return {"trace_lower_s": t1 - t0, "xla_compile_s": t2 - t1}


# <checkout>/.jax_cache: a fixed path, because the cache directory is
# part of what a later process must find again (gitignored)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compilation_cache() -> tuple:
    """Turn on JAX's persistent compilation cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it itself
    and this sets no directory; otherwise the cache goes to the fixed
    path `DEFAULT_CACHE_DIR` (``<checkout>/.jax_cache``).

    Returns ``(state, cache_dir)`` where state is:
      - ``"cold"``  — the directory holds no entry yet (compiles will
        populate it);
      - ``"warm"``  — it already holds entries (compiles with unchanged
        HLO deserialize instead of re-running XLA).

    Call this BEFORE the first jit of the process (the entry points do
    it at main() entry).  The min-compile-time gate is lowered to 1s so
    the big simulator scans always persist, and entries are written on
    every backend including CPU.  The sweep engine's tables-as-operands
    design is what makes the cache useful for fault studies at all:
    masks live in operands, not in the HLO, so every failure sample of
    a topology hits one cache entry (DESIGN.md §10).
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    state = "warm" if os.path.isdir(cache_dir) and any(
        name.endswith("-cache") for name in os.listdir(cache_dir)) else "cold"
    return state, cache_dir


@dataclasses.dataclass
class BenchEntry:
    name: str
    wall_s: float                       # steady-state min wall seconds/call
    wall_mean_s: float                  # steady-state mean
    compile_s: float                    # first call (trace+compile+run)
    repeats: int
    cycles: Optional[int] = None        # simulated cycles per call
    peak_mem_bytes: Optional[int] = None
    # device | tracemalloc | tracemalloc-nested | rss | rss-total |
    # none (rss-total = absolute VmHWM when an earlier, larger workload
    # hides this call behind the monotone high-water mark)
    mem_probe: str = "none"
    meta: dict = dataclasses.field(default_factory=dict)
    # additional top-level gate metrics (e.g. sweep_points_per_sec) —
    # serialized beside cycles_per_sec so check_regression can address
    # them by name
    extra_metrics: dict = dataclasses.field(default_factory=dict)

    @property
    def cycles_per_sec(self) -> Optional[float]:
        if self.cycles is None or self.wall_s <= 0:
            return None
        return self.cycles / self.wall_s

    def to_json(self) -> dict:
        d = {
            "wall_s": self.wall_s,
            "wall_mean_s": self.wall_mean_s,
            "compile_s": self.compile_s,
            "repeats": self.repeats,
            "peak_mem_bytes": self.peak_mem_bytes,
            "mem_probe": self.mem_probe,
            "meta": self.meta,
        }
        if self.cycles is not None:
            d["cycles"] = self.cycles
            d["cycles_per_sec"] = self.cycles_per_sec
        d.update(self.extra_metrics)
        return d


def rss_hwm_bytes() -> Optional[int]:
    """Process peak resident-set size (VmHWM) in bytes, or None when
    the platform exposes neither /proc nor getrusage."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    try:
        import resource
        import sys
        ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # ru_maxrss is bytes on macOS, KiB everywhere else
        return int(ru) * (1 if sys.platform == "darwin" else 1024)
    except Exception:
        return None


def peak_memory_bytes(fn: Callable[[], object],
                      cheap: bool = False) -> tuple:
    """(peak_bytes, probe_kind) for one invocation of `fn`.

    Uses the device allocator's peak counter when the backend exposes
    one (delta vs the pre-call peak), else tracemalloc.  With
    ``cheap=True`` (or as the last-resort fallback) the probe reads the
    process RSS high-water mark instead: near-zero overhead — the
    tracemalloc hooks dominate paper-scale runs — at the cost of
    coarser attribution.  A call that does not move the monotone HWM
    reports the absolute mark with probe ``"rss-total"`` so
    ``peak_mem_bytes`` is never null.
    """
    import jax

    if cheap:
        before = rss_hwm_bytes()
        fn()
        after = rss_hwm_bytes()
        if after is None:
            return None, "none"
        if before is not None and after > before:
            return int(after - before), "rss"
        # an earlier larger workload hides this call behind the HWM:
        # report the absolute mark, clearly labelled
        return int(after), "rss-total"

    dev = jax.devices()[0]
    stats = getattr(dev, "memory_stats", lambda: None)()
    if stats and "peak_bytes_in_use" in stats:
        before = dev.memory_stats()["peak_bytes_in_use"]
        fn()
        after = dev.memory_stats()["peak_bytes_in_use"]
        if after > before:
            return int(after - before), "device"
        # the allocator peak is a monotone high-water mark: an earlier,
        # larger workload in this process hides this call entirely —
        # fall back to the absolute RSS mark rather than reporting
        # nothing (mem_probe records which probe produced the number)
        rss = rss_hwm_bytes()
        return (int(rss), "rss-total") if rss is not None else (None, "none")
    if tracemalloc.is_tracing():
        # don't clobber an enclosing session's peak with reset_peak();
        # approximate from the running counters and label the probe so
        # trajectories never silently mix it with clean readings (a
        # stale historical peak can dominate peak1 here)
        cur0, _ = tracemalloc.get_traced_memory()
        fn()
        _, peak1 = tracemalloc.get_traced_memory()
        return int(max(peak1 - cur0, 0)), "tracemalloc-nested"
    tracemalloc.start()
    try:
        fn()
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    return int(peak), "tracemalloc"


def bench_callable(name: str, fn: Callable[[], object], *,
                   repeats: int = 3, cycles: Optional[int] = None,
                   measure_memory=True,
                   meta: Optional[dict] = None,
                   telemetry: bool = False) -> BenchEntry:
    """Compile-vs-steady-state timing of `fn` (which must block until
    the result is materialised — call block_until_ready/np.asarray
    inside).

    The memory probe brackets the FIRST call: on allocator-stats
    backends the peak counter is a monotone high-water mark, so only
    the first execution moves it — probing a later call would read a
    zero delta.  ``measure_memory`` may be True (full probe: device
    stats or tracemalloc), ``"rss"`` (cheap RSS high-water probe — the
    right choice for paper-scale entries where tracemalloc's hooks
    would dominate the measurement), or False (no probe).  When the
    probe is tracemalloc, `compile_s` includes its tracing overhead
    (both are coarse diagnostics, not gate metrics)."""
    t0 = time.perf_counter()
    peak, probe = (None, "none")
    if measure_memory:
        peak, probe = peak_memory_bytes(
            fn, cheap=(measure_memory == "rss"))  # trace+compile+warmup
    else:
        fn()
    compile_s = time.perf_counter() - t0

    walls = []
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        fn()
        walls.append(time.perf_counter() - t0)

    # provenance stamp defaults under explicit meta (an explicit
    # git_sha/jax_version/telemetry key in `meta` wins)
    stamped = repo_stamp(telemetry=telemetry)
    stamped.update(meta or {})
    return BenchEntry(name=name, wall_s=min(walls),
                      wall_mean_s=sum(walls) / len(walls),
                      compile_s=compile_s, repeats=len(walls),
                      cycles=cycles, peak_mem_bytes=peak, mem_probe=probe,
                      meta=stamped)


def write_bench(path: str, suite: str, entries: list, *,
                extra_meta: Optional[dict] = None) -> dict:
    """Serialise BenchEntry list to the BENCH_*.json schema."""
    import jax

    doc = {
        "schema": SCHEMA_VERSION,
        "suite": suite,
        "backend": jax.default_backend(),
        "meta": dict(extra_meta or {}),
        "entries": {e.name: e.to_json() for e in entries},
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return doc


def load_bench(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    assert doc.get("schema") == SCHEMA_VERSION, \
        f"unknown bench schema in {path}: {doc.get('schema')}"
    return doc


def check_regression(baseline: dict, entry_name: str, metric: str,
                     current: float, *, factor: float = 2.0,
                     higher_is_better: bool = True) -> tuple:
    """(ok, message) comparing `current` against the baseline metric.

    higher_is_better=True (e.g. cycles_per_sec): fail when current <
    baseline / factor.  Otherwise (e.g. wall_s): fail when current >
    baseline * factor.  A missing baseline entry passes with a notice —
    new benchmarks must not brick CI.
    """
    ent = baseline.get("entries", {}).get(entry_name)
    if ent is None or ent.get(metric) is None:
        return True, f"no baseline for {entry_name}.{metric}; skipping"
    base = float(ent[metric])
    if higher_is_better:
        ok = current >= base / factor
        rel = current / base if base else float("inf")
    else:
        ok = current <= base * factor
        rel = base / current if current else float("inf")
    msg = (f"{entry_name}.{metric}: current={current:.4g} "
           f"baseline={base:.4g} ({rel:.2f}x, gate {factor}x) "
           f"{'OK' if ok else 'REGRESSION'}")
    return ok, msg
