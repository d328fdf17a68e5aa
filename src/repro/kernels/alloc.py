"""Pallas TPU kernels for the flit-simulator hot path (DESIGN.md §9).

Two kernels, mirroring the two inner loops that dominate engine runtime:

- ``alloc_rounds``: W rounds of rotating-priority switch allocation
  (ejection ranking + per-output-channel arbitration).  All state is
  router-local once desires/space are pre-gathered (see
  `repro.sim.engine.SwitchCore.alloc`), so the grid partitions routers
  into blocks of ``BN`` rows and each block runs the full W-round loop
  in VMEM.  Working set per block: ~W * (PV + PE) request words plus a
  [BN, P, PV+PE] match mask — ~200 KiB at q=25, comfortably in VMEM.

- ``ugal_select``: VAL/UGAL candidate scoring — score MIN vs C Valiant
  candidates from pre-gathered path lengths and occupancy terms and
  return the per-endpoint winner.  Blocked over endpoints; the C+1
  score lanes are narrow for the VPU, but the kernel fuses the scoring,
  liveness masking and first-min select into one pass over [BE, C+1].

Both kernels call the SAME row-local math helpers as the pure-jnp
oracles in `ref.py` (`_alloc_rounds_math`, `_ugal_score_math`), so the
``ref`` and ``pallas`` engine paths agree bit-for-bit by construction;
tests/test_engine_scaling.py asserts full-`SimResult` equality.  On
the CPU backend the kernels run in interpret mode (`backend.py`), like
every kernel of this package; tests/test_tpu_compile.py compiles them
for a TPU v5e.  Each `pallas_call` is named (`name=`), so the compiled
kernel is `<kernel>_pallas[.<n>]` in the HLO and in a profile, however
the jitted wrappers around it are named.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import backend, ref

__all__ = ["alloc_rounds", "alloc_rounds_pallas", "ugal_select",
           "ugal_select_pallas", "ALLOC_BLOCK_N", "UGAL_BLOCK_E"]

ALLOC_BLOCK_N = 8            # routers per allocation block
UGAL_BLOCK_E = 512           # endpoints per scoring block


def _pad_rows(x, rows, fill=0):
    pad = rows - x.shape[0]
    if pad == 0:
        return x
    cfg = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, cfg, constant_values=fill)


# ------------------------------------------------------------ allocation --
def _alloc_kernel(cycle_ref, out_n_ref, ej_n_ref, sp_n_ref, cnt_n_ref,
                  out_s_ref, ej_s_ref, sp_s_ref, cnt_s_ref, epr_ref,
                  cs_n_ref, es_n_ref, cs_s_ref, es_s_ref, win_req_ref,
                  *, W, P, V, PE, p_budget, NQ, R, BN):
    row0 = pl.program_id(0) * BN
    cs_n, es_n, cs_s, es_s, win_req = ref._alloc_rounds_math(
        cycle_ref[0, 0],
        out_n_ref[...], ej_n_ref[...], sp_n_ref[...], cnt_n_ref[...],
        out_s_ref[...], ej_s_ref[...], sp_s_ref[...], cnt_s_ref[...],
        epr_ref[...], row0,
        W=W, P=P, V=V, PE=PE, p_budget=p_budget, NQ=NQ, R=R,
        in_kernel=True)
    cs_n_ref[...] = cs_n
    es_n_ref[...] = es_n
    cs_s_ref[...] = cs_s
    es_s_ref[...] = es_s
    win_req_ref[...] = win_req


@functools.partial(jax.jit, static_argnames=(
    "W", "P", "V", "PE", "p_budget", "NQ", "R", "block"))
def alloc_rounds_pallas(cycle, out_net, ej_net, space_net, count_net,
                        out_src, ej_src, space_src, count_src, epr_index,
                        *, W: int, P: int, V: int, PE: int, p_budget: int,
                        NQ: int, R: int, block: int = ALLOC_BLOCK_N):
    """Pallas W-round allocation over router-major request arrays.

    Same contract as :func:`repro.kernels.ref.alloc_rounds_ref`.
    Rows are padded to a multiple of `block`; pad rows carry zero queue
    depth and are inert.
    """
    N = count_net.shape[0]
    PV = P * V
    n_pad = -N % block
    rows = N + n_pad
    cyc = jnp.asarray(cycle, jnp.int32).reshape(1, 1)
    out_net = _pad_rows(out_net.astype(jnp.int32), rows, -1)
    ej_net = _pad_rows(ej_net.astype(jnp.int32), rows)
    space_net = _pad_rows(space_net.astype(jnp.int32), rows)
    count_net = _pad_rows(count_net.astype(jnp.int32), rows)
    out_src = _pad_rows(out_src.astype(jnp.int32), rows, -1)
    ej_src = _pad_rows(ej_src.astype(jnp.int32), rows)
    space_src = _pad_rows(space_src.astype(jnp.int32), rows)
    count_src = _pad_rows(count_src.astype(jnp.int32), rows)
    epr = _pad_rows(epr_index.reshape(-1, 1).astype(jnp.int32), rows, -1)

    grid = (rows // block,)
    b3n = pl.BlockSpec((block, PV, W), lambda i: (i, 0, 0))
    b3s = pl.BlockSpec((block, PE, W), lambda i: (i, 0, 0))
    b2n = pl.BlockSpec((block, PV), lambda i: (i, 0))
    b2s = pl.BlockSpec((block, PE), lambda i: (i, 0))
    b2p = pl.BlockSpec((block, P), lambda i: (i, 0))
    b1 = pl.BlockSpec((block, 1), lambda i: (i, 0))
    bc = pl.BlockSpec((1, 1), lambda i: (0, 0))
    outs = pl.pallas_call(
        functools.partial(_alloc_kernel, W=W, P=P, V=V, PE=PE,
                          p_budget=p_budget, NQ=NQ, R=R, BN=block),
        grid=grid,
        in_specs=[bc, b3n, b3n, b3n, b2n, b3s, b3s, b3s, b2s, b1],
        out_specs=[b2n, b2n, b2s, b2s, b2p],
        out_shape=[
            jax.ShapeDtypeStruct((rows, PV), jnp.int32),
            jax.ShapeDtypeStruct((rows, PV), jnp.int32),
            jax.ShapeDtypeStruct((rows, PE), jnp.int32),
            jax.ShapeDtypeStruct((rows, PE), jnp.int32),
            jax.ShapeDtypeStruct((rows, P), jnp.int32),
        ],
        interpret=backend.interpret_mode(),
        name="alloc_rounds_pallas",
    )(cyc, out_net, ej_net, space_net, count_net,
      out_src, ej_src, space_src, count_src, epr)
    return tuple(o[:N] for o in outs)


def alloc_rounds(cycle, out_net, ej_net, space_net, count_net,
                 out_src, ej_src, space_src, count_src, epr_index,
                 *, W: int, P: int, V: int, PE: int, p_budget: int,
                 NQ: int, R: int, use_pallas: bool = False):
    """Dispatch between the Pallas kernel and the pure-jnp oracle.

    Lane axis (DESIGN.md §10): request arrays may carry one extra
    LEADING lane dimension ([L, N, PV, W] etc. — detected by rank).
    Lanes are mapped with jax.vmap, under which the Pallas grid grows a
    trailing lane dimension (`pl.program_id(0)` still indexes router
    blocks, so the in-kernel `row0` priority math is untouched); each
    lane's grants are bit-identical to a single-lane call
    (tests/test_sweep.py).  `cycle` may be scalar (shared) or [L];
    `epr_index` is placement-derived and always lane-invariant.
    """
    fn = alloc_rounds_pallas if use_pallas else ref.alloc_rounds_ref
    if out_net.ndim == 4:
        cycle = jnp.asarray(cycle)
        lane_fn = functools.partial(
            fn, W=W, P=P, V=V, PE=PE, p_budget=p_budget, NQ=NQ, R=R)
        return jax.vmap(
            lane_fn,
            in_axes=((0 if cycle.ndim else None,)
                     + (0,) * 8 + (None,)))(
            cycle, out_net, ej_net, space_net, count_net,
            out_src, ej_src, space_src, count_src, epr_index)
    return fn(cycle, out_net, ej_net, space_net, count_net,
              out_src, ej_src, space_src, count_src, epr_index,
              W=W, P=P, V=V, PE=PE, p_budget=p_budget, NQ=NQ, R=R)


# ------------------------------------------------------------ UGAL score --
def _ugal_kernel(lm_ref, lv_ref, om_ref, ov_ref, best_ref,
                 *, ugal_g, unreach, big):
    best_ref[...] = ref._ugal_score_math(
        lm_ref[...], lv_ref[...], om_ref[...], ov_ref[...],
        ugal_g=ugal_g, unreach=unreach, big=big)


@functools.partial(jax.jit, static_argnames=(
    "ugal_g", "unreach", "big", "block"))
def ugal_select_pallas(len_min, len_val, occ_min, occ_val,
                       *, ugal_g: bool, unreach: int, big: int,
                       block: int = UGAL_BLOCK_E):
    """Pallas UGAL/VAL candidate select; same contract as
    :func:`repro.kernels.ref.ugal_select_ref`.  Pad rows get
    len = unreach, score BIG everywhere, and are sliced off."""
    E = len_min.shape[0]
    C = len_val.shape[1]
    rows = E + (-E % block)
    lm = _pad_rows(len_min.reshape(-1, 1).astype(jnp.int32), rows, unreach)
    lv = _pad_rows(len_val.astype(jnp.int32), rows, unreach)
    om = _pad_rows(occ_min.reshape(-1, 1).astype(jnp.int32), rows)
    ov = _pad_rows(occ_val.astype(jnp.int32), rows)

    grid = (rows // block,)
    b1 = pl.BlockSpec((block, 1), lambda i: (i, 0))
    bC = pl.BlockSpec((block, C), lambda i: (i, 0))
    best = pl.pallas_call(
        functools.partial(_ugal_kernel, ugal_g=ugal_g, unreach=unreach,
                          big=big),
        grid=grid,
        in_specs=[b1, bC, b1, bC],
        out_specs=b1,
        out_shape=jax.ShapeDtypeStruct((rows, 1), jnp.int32),
        interpret=backend.interpret_mode(),
        name="ugal_select_pallas",
    )(lm, lv, om, ov)
    return best[:E, 0]


def ugal_select(len_min, len_val, occ_min, occ_val,
                *, ugal_g: bool, unreach: int, big: int,
                use_pallas: bool = False):
    """Dispatch between the Pallas kernel and the pure-jnp oracle.

    As with :func:`alloc_rounds`, one extra leading lane axis is
    accepted ([L, E] / [L, E, C]) and vmapped, bit-identically per
    lane."""
    fn = ugal_select_pallas if use_pallas else ref.ugal_select_ref
    if len_min.ndim == 2:
        lane_fn = functools.partial(fn, ugal_g=ugal_g, unreach=unreach,
                                    big=big)
        return jax.vmap(lane_fn)(len_min, len_val, occ_min, occ_val)
    return fn(len_min, len_val, occ_min, occ_val,
              ugal_g=ugal_g, unreach=unreach, big=big)
