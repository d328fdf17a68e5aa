"""GQA flash-decode Pallas TPU kernel (one query token, long KV cache).

This is the perf-critical op of the serving path (decode_32k / long_500k
shapes): a single new token attends over an S-long KV cache.  The op is
memory-bound (arithmetic intensity ~ O(G)), so the kernel's job is to
stream K/V through VMEM exactly once with an online-softmax accumulator.

Layout: q [B, Hkv, G, d], k/v [B, Hkv, S, d]  (G = query heads per kv head,
pre-padded to a multiple of 8 by the ops.py wrapper; d multiple of 128).

Grid: (B, Hkv, S/bs) with the S dimension innermost/sequential; the
running max / sum / accumulator live in VMEM scratch that persists across
the S sweep of one (B, Hkv) block.  Block working set:
  k,v tiles 2 * bs*d*4 B  (bs=512, d=128: 512 KiB) + acc G*d*4 — << VMEM.

The valid KV length per batch row arrives via scalar prefetch (SMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend

__all__ = ["decode_attention_pallas"]

_NEG_BIG = -3.0e38


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, bs: int, scale: float,
                   cap: float | None):
    b_idx = pl.program_id(0)
    s_idx = pl.program_id(2)
    n_s = pl.num_programs(2)

    @pl.when(s_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)      # [G, d]
    k = k_ref[0, 0].astype(jnp.float32)      # [bs, d]
    v = v_ref[0, 0].astype(jnp.float32)      # [bs, dv]

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # [G, bs]
    if cap is not None:                                    # logit softcap
        scores = cap * jnp.tanh(scores / cap)

    # mask out positions beyond the valid cache length
    length = len_ref[b_idx]
    pos = s_idx * bs + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(pos < length, scores, _NEG_BIG)

    m_prev = m_scr[...]                       # [G, 1]
    m_new = jnp.maximum(m_prev, scores.max(axis=1, keepdims=True))
    p = jnp.exp(scores - m_new)               # [G, bs]
    corr = jnp.exp(m_prev - m_new)            # [G, 1]
    l_new = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)    # [G, dv]
    acc_new = acc_scr[...] * corr + pv

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(s_idx == n_s - 1)
    def _finalize():
        o_ref[...] = (acc_new / l_new).astype(o_ref.dtype)[None, None]


@functools.partial(jax.jit, static_argnames=("bs", "scale", "cap"))
def decode_attention_pallas(q, k, v, length=None, *, bs: int = 512,
                            scale: float | None = None,
                            cap: float | None = None):
    """q: [B, Hkv, G, d]; k, v: [B, Hkv, S, d]; length: [B] or None.
    Caller must pad G to a multiple of 8 and d to a multiple of 128
    (ops.py does this).  Returns [B, Hkv, G, dv]."""
    B, Hkv, G, d = q.shape
    S = k.shape[2]
    dv = v.shape[3]
    if scale is None:
        scale = float(1.0 / (d**0.5))
    if length is None:
        length = jnp.full((B,), S, dtype=jnp.int32)
    length = length.astype(jnp.int32)

    pad_s = (-S) % bs
    if pad_s:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
    Sp = S + pad_s

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, Sp // bs),
        in_specs=[
            pl.BlockSpec((1, 1, G, d), lambda b, h, s, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, d), lambda b, h, s, *_: (b, h, s, 0)),
            pl.BlockSpec((1, 1, bs, dv), lambda b, h, s, *_: (b, h, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, dv), lambda b, h, s, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, dv), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, bs=bs, scale=scale, cap=cap)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, dv), q.dtype),
        interpret=backend.interpret_mode(),
    )(length, q, k, v)
