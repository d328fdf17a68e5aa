"""Chip benchmark of the flit simulator (see `run.py` and BENCHMARK.json)."""
