"""Persistent performance-regression harness (DESIGN.md §9).

`harness` provides steady-state timing (explicit warmup/compile
separation), peak-memory probes, and a stable JSON schema
(``BENCH_*.json``) so benchmark trajectories survive across PRs and a
CI gate can fail on hot-path regressions.
"""

from .harness import (BenchEntry, bench_callable, check_regression,
                      enable_compilation_cache, load_bench,
                      peak_memory_bytes, repo_stamp, rss_hwm_bytes,
                      write_bench)

__all__ = ["BenchEntry", "bench_callable", "check_regression",
           "enable_compilation_cache", "load_bench", "peak_memory_bytes",
           "repo_stamp", "rss_hwm_bytes", "write_bench"]
