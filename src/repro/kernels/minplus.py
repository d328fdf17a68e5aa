"""Tiled (min,+)-semiring matmul Pallas TPU kernel.

C[b, i, j] = min_k ( A[b, i, k] + B[b, k, j] )

This is the hot spot of the Slim Fly analysis pipeline: all-pairs shortest
paths by repeated min-plus squaring (diameter, average distance — Fig 1 /
Table II — and the batched link-failure resiliency study §III-D, which
min-plus-squares hundreds of perturbed adjacency matrices).

TPU adaptation (DESIGN.md §3): BFS pointer-chasing is replaced by dense
blocked semiring algebra.  The MXU cannot evaluate a (min,+) contraction,
so the inner loop is a VPU-vectorized rank-1 sweep over the K tile: each
step does a [bm, bn] broadcast-add + min, which maps onto 8x128 VREGs.
Mosaic refuses a dynamic slice of a value, and a dynamic column read
from the ref too (its lane offset is not provably a multiple of 128), so
the A tile rides the loop carry and is rotated one lane per step: the
current column is always lane 0.
Block shapes keep the working set (3 tiles + accumulator) well inside VMEM:
bm = bn = bk = 128  =>  4 * 128*128*4 B = 256 KiB.

Grid: (B, M/bm, N/bn, K/bk), K innermost (sequential revisit of the output
block; the accumulator lives in the output ref, initialised at k == 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend

__all__ = ["minplus_pallas", "DEFAULT_BLOCK"]

DEFAULT_BLOCK = 128
_BIG = 3.0e38  # acts as +inf but keeps inf-free arithmetic (python literal
               # so the kernel does not capture a traced constant)


def _minplus_kernel(a_ref, b_ref, o_ref, *, bk: int):
    k_idx = pl.program_id(3)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, _BIG)

    def body(kk, carry):
        # rank-1 (min,+) update: acc = min(acc, a[:, kk] + b[kk, :]).
        # Column kk of A sits in lane 0 of the carried, lane-rotated
        # tile; row kk of B is read from its ref
        a, acc = carry
        acc = jnp.minimum(acc, a[:, :1] + b_ref[0, pl.ds(kk, 1), :])
        return pltpu.roll(a, bk - 1, 1), acc

    _, acc = lax.fori_loop(0, bk, body, (a_ref[0], o_ref[0]))
    o_ref[0] = acc


@functools.partial(jax.jit, static_argnames=("block",))
def minplus_pallas(a: jax.Array, b: jax.Array, block: int = DEFAULT_BLOCK):
    """Batched (min,+) matmul.  a: [B, M, K], b: [B, K, N] (or unbatched 2-D).
    float32/bfloat16.  Entries >= 1e38 are treated as +inf by convention."""
    squeeze = a.ndim == 2
    if squeeze:
        a, b = a[None], b[None]
    B, M, K = a.shape
    _, K2, N = b.shape
    assert K == K2 and b.shape[0] == B

    pad = lambda n: (-n) % block
    a = jnp.pad(a, ((0, 0), (0, pad(M)), (0, pad(K))), constant_values=_BIG)
    b = jnp.pad(b, ((0, 0), (0, pad(K)), (0, pad(N))), constant_values=_BIG)
    Mp, Kp, Np = a.shape[1], a.shape[2], b.shape[2]

    grid = (B, Mp // block, Np // block, Kp // block)
    out = pl.pallas_call(
        functools.partial(_minplus_kernel, bk=block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block, block), lambda bt, i, j, k: (bt, i, k)),
            pl.BlockSpec((1, block, block), lambda bt, i, j, k: (bt, k, j)),
        ],
        out_specs=pl.BlockSpec((1, block, block), lambda bt, i, j, k: (bt, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, Mp, Np), a.dtype),
        interpret=backend.interpret_mode(),
    )(a, b)
    out = out[:, :M, :N]
    # saturate accumulated "inf + inf" values back to _BIG
    out = jnp.minimum(out, _BIG)
    return out[0] if squeeze else out
