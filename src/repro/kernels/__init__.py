"""Pallas TPU kernels for the framework's compute hot spots.

- minplus:     tiled (min,+)-semiring matmul - APSP / topology analysis
- attn_decode: GQA flash-decode over long KV caches - serving path
- alloc:       flit-simulator inner loops - W-round switch allocation
               and UGAL/VAL candidate scoring (DESIGN.md §9)
ops.py holds the jit'd public wrappers; ref.py the pure-jnp oracles.
On the CPU backend every kernel runs in interpret mode (bit-accurate);
any other backend compiles it with Mosaic (backend.py).
"""

from .alloc import alloc_rounds, ugal_select
from .ops import INF, apsp, decode_attention, minplus, seed_distance

__all__ = ["INF", "alloc_rounds", "apsp", "decode_attention", "minplus",
           "seed_distance", "ugal_select"]
