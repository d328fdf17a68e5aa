"""Pure-jnp oracles for every Pallas kernel in this package.

The switch-allocation and UGAL-scoring oracles are written as
row-independent math helpers (`_alloc_rounds_math`, `_ugal_score_math`)
shared verbatim with the Pallas kernels in `alloc.py`: the kernel runs
the same function on a block of rows, so ref and pallas paths agree
bit-for-bit by construction (asserted end-to-end by
tests/test_engine_scaling.py).

Lane batching (DESIGN.md §10): the oracles are rank-fixed; an extra
leading lane axis is handled by the dispatchers in `alloc.py`, which
jax.vmap whichever implementation is selected.  vmap of a pure-jnp
oracle is value-preserving per lane by construction, and vmap of the
Pallas kernels appends a lane dimension to the grid without renumbering
`program_id`, so the per-lane bit-equality between the two paths is
unchanged (asserted per lane by tests/test_sweep.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "minplus_ref", "apsp_ref", "decode_attention_ref",
    "alloc_rounds_ref", "ugal_select_ref",
]


def minplus_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    """C[..., i, j] = min_k A[..., i, k] + B[..., k, j] (broadcast batch)."""
    return jnp.min(a[..., :, :, None] + b[..., None, :, :], axis=-2)


def apsp_ref(adj_dist: jax.Array, n_iter: int) -> jax.Array:
    """APSP by repeated (min,+) squaring of the seeded distance matrix."""
    d = adj_dist
    for _ in range(n_iter):
        d = minplus_ref(d, d)
    return d


def decode_attention_ref(q, k, v, scale: float | None = None, length=None,
                         cap: float | None = None):
    """GQA decode attention oracle.

    q: [B, Hkv, G, d]    (one new token; G = query heads per kv head)
    k: [B, Hkv, S, d]
    v: [B, Hkv, S, dv]
    length: optional [B] valid KV length (positions >= length masked out).
    returns [B, Hkv, G, dv]
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
    scores = jnp.einsum("bhgd,bhsd->bhgs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if cap is not None:
        scores = cap * jnp.tanh(scores / cap)
    if length is not None:
        pos = jnp.arange(k.shape[2])
        mask = pos[None, :] < length[:, None]          # [B, S]
        scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------- switch --
# W-round rotating-priority switch allocation (repro.sim.engine, DESIGN.md
# §5/§9).  All arrays are router-major: every row is one router, so the
# math below is row-local and a Pallas grid can partition rows freely.
#
# Priority note: the seed engine ranked channel requests by
# ``rot * R + qidx`` with ``rot = (qidx + cycle*7919 + w*131) % R`` — at
# paper scale (q=17, R = 65314 request queues) that product reaches
# ~4.3e9 and silently wraps int32.  Because qidx -> rot is a bijection
# (a shift mod R), all rot values are distinct and ``argmin(rot)``
# selects the same winner as ``argmin(rot * R + qidx)`` did where the
# latter was well-defined; we therefore rank by ``rot`` alone, which
# stays < R.  The additive term cycle*7919 + qidx + w*131 itself stays
# below int32 for cycle <= 200k (the closed-loop max) and R <= 2^18
# (q=25), asserted in tests/test_engine_scaling.py.


# Requests of a router are indexed 0..K-1 with K = PV + PE (net queues
# then source queues).  Channel arbitration packs (priority, request
# index) into one int32 — KSHIFT must exceed K and R * KSHIFT must stay
# below 2^31; q=25 (R = 208750, K = 167) leaves ~40x headroom.
KSHIFT = 256


def _excl_prefix(x, in_kernel: bool):
    """Exclusive prefix sum of int32 `x` [B, n] along its columns.

    Mosaic has no cumsum lowering, so the kernel form sums the columns
    strictly left of each column through a triangular compare mask."""
    if not in_kernel:
        return jnp.cumsum(x, axis=1) - x
    n = x.shape[1]
    left = (lax.broadcasted_iota(jnp.int32, (1, n, n), 2)
            < lax.broadcasted_iota(jnp.int32, (1, n, n), 1))  # [1, j, i]
    return jnp.sum(jnp.where(left, x[:, None, :], 0), axis=2)


def _alloc_rounds_math(cycle, out_n, ej_n, sp_n, cnt_n,
                       out_s, ej_s, sp_s, cnt_s, epr, row0,
                       *, W: int, P: int, V: int, PE: int,
                       p_budget: int, NQ: int, R: int,
                       in_kernel: bool = False):
    """W rounds of matching for a block of routers.

    Shapes (B = routers in this block, PV = P*V; the W axis is LAST so
    the engine's [N,P,V,W] desire arrays reshape in without copies):
      out_n/ej_n/sp_n: [B, PV, W] desired out port / eject flag / space
      cnt_n:           [B, PV]    queue depth at cycle start (0 = dead port)
      out_s/ej_s/sp_s: [B, PE, W] the router's endpoint (source) queues
      cnt_s:           [B, PE]
      epr:             [B, 1]     endpoint-block index of the router (-1)
      row0:            scalar     global id of row 0 (Pallas block offset)

    Returns (chan_slot_net [B, PV], ej_slot_net [B, PV],
             chan_slot_src [B, PE], ej_slot_src [B, PE],
             win_req [B, P]): the window offset granted per queue (-1 =
    no grant) split by grant kind, plus the winning request index (into
    the router's K requests; -1 = idle) per output channel — each
    channel carries at most one packet per cycle, so one [B, P] index
    array captures every arrival (the engine turns it into dense
    per-(router, port) gathers instead of a scatter).

    Masks are int32 0/1 throughout: Mosaic refuses bool arrays that are
    concatenated, sliced on a trailing axis or broadcast over a new
    axis.  `in_kernel` selects the Mosaic-lowerable forms of the prefix
    count and the winner test; both forms grant identically.
    """
    B = cnt_n.shape[0]
    PV = P * V
    K = PV + PE
    assert K < KSHIFT, f"request index overflows KSHIFT lanes: {K}"
    i32 = jnp.int32
    intmax = jnp.iinfo(jnp.int32).max

    col_pv = lax.broadcasted_iota(i32, (B, PV), 1)
    col_pe = lax.broadcasted_iota(i32, (B, PE), 1)
    col_k = lax.broadcasted_iota(i32, (B, K), 1)
    rows = row0 + lax.broadcasted_iota(i32, (B, 1), 0)
    qidx_n = rows * PV + col_pv                      # global queue ids
    qidx_s = NQ + epr * PE + col_pe                  # (junk when epr < 0:
    chan_ids = lax.broadcasted_iota(i32, (B, P, 1), 1)  # masked by cnt==0)

    s_rot = cycle % PV                               # ejection rotation
    net_first = (cycle % 2) == 0
    base = cycle * jnp.int32(7919)

    free_n = jnp.ones((B, PV), i32)                  # 1 = not yet granted
    free_s = jnp.ones((B, PE), i32)
    chan_free = jnp.ones((B, P), i32)
    budget = jnp.full((B, 1), p_budget, i32)
    cs_n = jnp.full((B, PV), -1, i32)
    es_n = jnp.full((B, PV), -1, i32)
    cs_s = jnp.full((B, PE), -1, i32)
    es_s = jnp.full((B, PE), -1, i32)
    win_req = jnp.full((B, P), -1, i32)

    # hoisted across rounds: the rotation base priorities
    qidx_k = jnp.concatenate([qidx_n, qidx_s], axis=1)
    rot0 = (qidx_k + base) % R                           # [B, K]

    for w in range(W):
        vn = jnp.where(cnt_n > w, free_n, 0)
        vs = jnp.where(cnt_s > w, free_s, 0)
        ejn = (ej_n[:, :, w] != 0).astype(i32)
        ejs = (ej_s[:, :, w] != 0).astype(i32)
        spn = (sp_n[:, :, w] != 0).astype(i32)
        sps = (sp_s[:, :, w] != 0).astype(i32)

        # --- ejection grants: rotating rank over the router's net
        # queues (start column rotates with the cycle), endpoints ranked
        # before/after by cycle parity, against the shared budget of p
        # ejection ports.  rank = exclusive prefix count in rotated
        # order, computed in closed form instead of roll+cumsum+roll.
        mn = vn * ejn
        ms = vs * ejs
        cn = _excl_prefix(mn, in_kernel)
        sn = mn.sum(axis=1, keepdims=True)
        c_at = jnp.sum(jnp.where(col_pv == s_rot, cn, 0), axis=1,
                       keepdims=True)
        rank_n = cn - c_at + jnp.where(col_pv < s_rot, sn, 0)
        cs_pre = _excl_prefix(ms, in_kernel)
        ss = ms.sum(axis=1, keepdims=True)
        rank_nf = rank_n + jnp.where(net_first, 0, ss)
        rank_sf = cs_pre + jnp.where(net_first, sn, 0)
        g_ej_n = jnp.where(rank_nf < budget, mn, 0)
        g_ej_s = jnp.where(rank_sf < budget, ms, 0)
        budget = (budget - g_ej_n.sum(axis=1, keepdims=True)
                  - g_ej_s.sum(axis=1, keepdims=True))

        # --- channel grants: lowest rotating priority among eligible
        # requests per output channel; one winner per channel per cycle.
        # Priorities are distinct (qidx -> rot is a bijection mod R), so
        # packing (rot, request index) into rot*KSHIFT + idx lets one
        # min-reduction produce both the winner's priority and its
        # identity; a channel with any eligible request always grants.
        elig_n = vn * (1 - ejn) * spn
        elig_s = vs * (1 - ejs) * sps
        cmb = ((rot0 + jnp.int32(w * 131)) % R) * KSHIFT + col_k  # [B, K]
        out_all = jnp.concatenate([out_n[:, :, w], out_s[:, :, w]], axis=1)
        elig = jnp.concatenate([elig_n, elig_s], axis=1)          # [B, K]
        live = ((out_all[:, None, :] == chan_ids)
                & (chan_free[:, :, None] * elig[:, None, :] != 0))  # [B,P,K]
        cmin = jnp.min(jnp.where(live, cmb[:, None, :], intmax),
                       axis=2)                       # [B, P]
        won = cmin < intmax
        if in_kernel:
            # gather-free winner test (cmb values are distinct, so ==
            # picks exactly the channel's winner)
            hit = jnp.where(live & (cmb[:, None, :] == cmin[:, :, None]),
                            1, 0)
            win_all = jnp.max(hit, axis=1)
        else:
            # per-request winner test via a [B, K] row gather of the
            # channel minima — cheap on CPU/GPU; taken/ineligible rows
            # can never match
            cmin_at = jnp.take_along_axis(cmin, jnp.maximum(out_all, 0),
                                          axis=1)
            win_all = jnp.where((out_all >= 0) & (cmb == cmin_at), elig, 0)
        win_n, win_s = win_all[:, :PV], win_all[:, PV:]
        chan_free = jnp.where(won, 0, chan_free)
        win_req = jnp.where(won, cmin % KSHIFT, win_req)

        free_n = free_n * (1 - win_n) * (1 - g_ej_n)
        free_s = free_s * (1 - win_s) * (1 - g_ej_s)
        cs_n = jnp.where(win_n != 0, w, cs_n)
        es_n = jnp.where(g_ej_n != 0, w, es_n)
        cs_s = jnp.where(win_s != 0, w, cs_s)
        es_s = jnp.where(g_ej_s != 0, w, es_s)

    return cs_n, es_n, cs_s, es_s, win_req


def alloc_rounds_ref(cycle, out_net, ej_net, space_net, count_net,
                     out_src, ej_src, space_src, count_src, epr_index,
                     *, W: int, P: int, V: int, PE: int, p_budget: int,
                     NQ: int, R: int):
    """Full-array oracle for the W-round allocation kernel."""
    return _alloc_rounds_math(
        jnp.asarray(cycle, jnp.int32), out_net, ej_net, space_net,
        count_net, out_src, ej_src, space_src, count_src,
        epr_index.reshape(-1, 1), jnp.int32(0),
        W=W, P=P, V=V, PE=PE, p_budget=p_budget, NQ=NQ, R=R,
        in_kernel=False)


# ------------------------------------------------------------ UGAL score --
def _ugal_score_math(len_min, len_val, occ_min, occ_val,
                     *, ugal_g: bool, unreach: int, big: int):
    """Score MIN vs the C VAL candidates and pick the best (first-min).

    len_min [E, 1] / len_val [E, C]: path lengths (int32, >= unreach =
    dead); occ_min/occ_val: the matching pre-gathered occupancy terms
    (first-hop queue for UGAL-L, whole-path sums for UGAL-G, already
    OCC_CAP-clamped by the engine).  Returns [E, 1] int32 index into
    the [MIN, cand_0, .., cand_{C-1}] row (0 = MIN; ties go to MIN,
    matching argmin-first).
    """
    if ugal_g:
        sm = occ_min + len_min
        sv = occ_val + len_val
    else:
        sm = len_min * occ_min
        sv = len_val * occ_val
    sm = jnp.where(len_min < unreach, sm, big)
    sv = jnp.where(len_val < unreach, sv, big)
    scores = jnp.concatenate([sm, sv], axis=1)       # [E, 1 + C]
    m = jnp.min(scores, axis=1, keepdims=True)
    idx = lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    first = jnp.min(jnp.where(scores == m, idx, scores.shape[1]),
                    axis=1, keepdims=True)
    return first.astype(jnp.int32)


def ugal_select_ref(len_min, len_val, occ_min, occ_val,
                    *, ugal_g: bool, unreach: int, big: int):
    """Full-array oracle for the UGAL candidate-scoring kernel.

    len_min/occ_min: [E]; len_val/occ_val: [E, C].  Returns best [E].
    """
    return _ugal_score_math(
        len_min[:, None], len_val, occ_min[:, None], occ_val,
        ugal_g=ugal_g, unreach=unreach, big=big)[:, 0]
