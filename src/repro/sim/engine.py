"""Cycle-based flit network simulator (paper §V), fully vectorized in
JAX with a lax.scan over cycles.

Model (faithful to the paper's setup):
  - single-flit packets, Bernoulli injection (§V), input-queued routers;
  - V virtual channels per input port, hop-indexed VC assignment (§IV-D)
    => deadlock-free by construction (verified by tests/test_routing.py);
  - per-cycle pipeline: route -> switch allocation -> link traversal;
  - switch allocation: rotating-priority matching over a lookahead window
    of W packets per input queue (W rounds of maximal matching).  This is
    the vectorized stand-in for Booksim's internal speedup 2 + iSLIP —
    without it an input-queued router caps at ~59% throughput from
    head-of-line blocking (cf. DESIGN.md §5);
  - one packet per output channel per cycle (channel rate 1 flit/cycle);
  - backpressure: a packet advances only if the downstream input queue for
    (port, VC) has a free slot (credit view);
  - ejection capacity p packets/router/cycle (one per endpoint downlink);
  - routing modes: 'min', 'val', 'ugal_l', 'ugal_g' (§IV), and 'ecmp'
    (adaptive equal-cost next-hop — the FT-3 ANCA stand-in).

The switch itself (credit view, per-flit route choice, W-round
allocation, window compaction) lives in :class:`SwitchCore` and is
shared between two engines that differ only in how source queues fill
and in what they fold over ejection grants:

  - `simulate` (this module): open-loop Bernoulli injection, the §V
    latency/throughput methodology;
  - `repro.sim.workloads.closed_loop`: dependency-triggered multi-flit
    message injection for closed-loop workload (JCT) runs; its packet
    records carry an extra bit-packed MSG field that the core passes
    through untouched.

Paper-scale hot path (DESIGN.md §9).  Queue state is bit-packed
(`repro.sim.packed`): every flit record is 3 int32 words and the big
routing tables are int16 on device.  A cycle gathers ONE W-slot window
of every queue up front, computes route desires for all W slots at
once, and hands the router-local conflict resolution to
`repro.kernels.alloc_rounds` (Pallas kernel or its bit-identical jnp
oracle, selected by ``SimConfig.kernel_path``); UGAL/VAL candidate
scoring likewise runs through `repro.kernels.ugal_select`.  Two
engine-level identities make the single-gather structure exact (the
grants are bit-identical to a per-round re-gather):

  1. arrivals land at offsets >= the cycle-start queue depth, and a
     window slot is only valid below that depth — this cycle's
     arrivals can never be granted this cycle;
  2. a downstream input queue (router, port) receives at most one
     packet per cycle, always via its unique upstream channel, and
     `chan_taken` blocks that channel after its win — so the
     backpressure (space) check against cycle-start depths is exact.

State layout: packed records [..., PK=3]; network queues [N, P, V, Qn,
PK] as shift-down FIFOs (head at slot 0) with a count array; source
queues [N_ep, Qs, PK].

`simulate` compiles one `(carry, rate) ->` scan per (tables, traffic,
static-config) signature and caches it: injection rate and PRNG seed
are traced operands, so a load sweep (fig6) traces and compiles the
network exactly once.  The routing tables stay CLOSURE CONSTANTS here
— XLA specialises the per-cycle gathers against constant index tables
(~2.5x at q=11) — so a new failure mask recompiles this path; sweeps
over masks belong on the lane-batched engine (`repro.sim.sweep`),
where the tables become traced operands shared by one compile across
all lanes (DESIGN.md §10).  The initial scan carry is donated.
"""

from __future__ import annotations

import copy
import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.routing import UNREACH
from ..kernels import alloc_rounds, ugal_select
from . import telemetry as tel
from .packed import (MAX_ROUTERS, PK, bump_hops_word, pack_record, pk_dst,
                     pk_hops, pk_inter, pk_msg, pk_phase, pk_time)
from .tables import SimTables
from .telemetry import TelemetryConfig, TelemetrySnapshot
from .traffic import Traffic

__all__ = ["SimConfig", "SimResult", "SwitchCore", "simulate",
           "TelemetryConfig"]

BIG = jnp.int32(1 << 30)
# occupancy values entering UGAL scores are clamped here so that the
# dead-port sentinel (occupancy() returns BIG for nbr < 0) cannot
# overflow int32 when multiplied by a path length, while still dwarfing
# any real queue depth (degraded fabrics, DESIGN.md §8)
OCC_CAP = jnp.int32(1 << 20)


@dataclasses.dataclass(frozen=True)
class SimConfig:
    injection_rate: float = 0.2       # packets / endpoint / cycle
    cycles: int = 2000
    warmup: int = 500
    vcs: int = 4                      # paper sims use 3; adaptive needs 4
    q_net: int = 16                   # per-(port,VC) buffer (64 flits/port @ 4 VC)
    q_src: int = 64
    mode: str = "min"                 # min | val | ugal_l | ugal_g | ecmp
    n_val_candidates: int = 4         # §IV-C: 4 works best
    lookahead: int = 4                # allocation window (HOL mitigation)
    seed: int = 0
    # hot-path implementation: 'auto' = Pallas kernels on TPU, jnp
    # oracles elsewhere; 'ref' / 'pallas' force a path (the kernels are
    # bit-identical — tests/test_engine_scaling.py)
    kernel_path: str = "auto"
    # opt-in counters/tracing threaded through the scan carry
    # (repro.sim.telemetry); the default is fully off and adds ZERO
    # carry leaves — bit-exact vs a build without the layer
    telemetry: TelemetryConfig = TelemetryConfig()

    def static_key(self) -> tuple:
        """Fields that shape the compiled graph (rate/seed are traced)."""
        return (self.cycles, self.vcs, self.q_net, self.q_src, self.mode,
                self.n_val_candidates, self.lookahead, self.kernel_path,
                self.telemetry.static_key())


@dataclasses.dataclass
class SimResult:
    name: str
    offered_load: float
    accepted_load: float              # delivered / cycle / active endpoint
    avg_latency: float                # cycles, measurement window
    delivered: int
    injected: int
    dropped_at_source: int
    src_occupancy: float              # mean source-queue depth (saturation)
    per_cycle_delivered: np.ndarray
    # end-of-cycle snapshots for the flit-conservation invariant
    # (tests/test_sim.py): cumsum(injected) == cumsum(delivered) +
    # in_flight at EVERY cycle prefix; dropped packets never enter the
    # network (refused at a full source queue).
    per_cycle_injected: Optional[np.ndarray] = None
    per_cycle_in_flight: Optional[np.ndarray] = None
    per_cycle_dropped: Optional[np.ndarray] = None
    # the configured source-queue depth, so `saturated` scales with the
    # run's actual backlog capacity instead of a hard-coded 64
    q_src: int = 64
    telemetry: Optional[TelemetrySnapshot] = None

    @property
    def saturated(self) -> bool:
        return (self.src_occupancy > 0.5 * self.q_src
                or self.dropped_at_source > 0)


class SwitchCore:
    """Shared input-queued switch pipeline for one (tables, config).

    Owns the device-resident routing tables and implements the four
    engine-independent stages of a cycle: credit-view `occupancy`,
    per-flit `route_decision`, and `alloc` (W rounds of
    rotating-priority matching with immediate arrivals, followed by
    window compaction and dequeues).  Engines inject into the source
    queues themselves and pass an `eject_fold(acc, grant_net [N,P,V]
    bool, grant_src [n_ep] bool, pkt_net [N,P,V,PK], pkt_src [n_ep,PK],
    cycle)` callback, called once per allocation round with that
    round's ejection grants and the (packed) granted head-window
    records, so open-loop stats (delivered/latency) and closed-loop
    stats (per-message flit counts) use the same matching machinery.
    The fold reads fields through `repro.sim.packed` accessors — no
    concat or unpack boundary sits on the hot path.
    """

    def __init__(self, tables: SimTables, cfg: SimConfig):
        assert tables.lanes == 1, \
            "SwitchCore is single-lane; stacked tables go to sim.sweep"
        self.tables = tables
        N, P, V = tables.n_routers, tables.P, cfg.vcs
        assert N < MAX_ROUTERS, f"router ids overflow packed records: {N}"
        self.N, self.P, self.V = N, P, V
        self.Qn, self.Qs = cfg.q_net, cfg.q_src
        self.n_ep = tables.n_endpoints
        self.p = int(tables.p)
        self.W = cfg.lookahead
        self.mode = cfg.mode
        self.C = cfg.n_val_candidates
        self.tel = cfg.telemetry
        kp = cfg.kernel_path
        assert kp in ("auto", "ref", "pallas"), kp
        self.use_pallas = (kp == "pallas"
                           or (kp == "auto"
                               and jax.default_backend() == "tpu"))
        # table-routed by default; bind_source_routes switches a copy
        # into source-routed mode (explicit per-message paths)
        self.src_route = None
        self.src_to_gid = None

        # narrow on-device tables (DESIGN.md §9): the O(N^2) tables are
        # int16 (ids < 2^15 asserted above) and gathered values are
        # widened to int32 at their use sites
        self.ecmp_ports = None
        for name, arr in self.device_tables(tables).items():
            setattr(self, name, arr)
        self.has_ecmp = tables.ecmp_ports is not None
        self.ep_router = jnp.asarray(tables.ep_router.astype(np.int32))

        # endpoint-router blocks for ejection ranking: endpoints are
        # sorted by router and each endpoint-router has exactly p
        # endpoints.
        ebr = tables.ep_router[::self.p].astype(np.int32)
        self.ep_block_router = jnp.asarray(ebr)
        self.n_epr = self.n_ep // self.p
        epr_index = np.full((N,), -1, dtype=np.int32)
        epr_index[ebr] = np.arange(self.n_epr, dtype=np.int32)
        self.epr_index = jnp.asarray(epr_index)

        self.unreach = jnp.int32(int(UNREACH))

        self.NQ = N * P * V
        self.R = self.NQ + self.n_ep
        self.eids = jnp.arange(self.n_ep)
        self.routers_n = jnp.arange(N)[:, None, None]          # [N,1,1]

    # -- table operands ------------------------------------------------------
    # Routing tables are TRACED OPERANDS of the compiled step, not
    # closure constants: with constants, every failure mask bakes a
    # different HLO (so each degraded fabric recompiles and the
    # persistent compilation cache can never hit), and the sweep
    # engine could not vmap over per-lane masks at all (DESIGN.md §10).
    @staticmethod
    def device_tables(tables: SimTables) -> dict:
        """The mask-dependent table arrays, as device operands."""
        ops = {
            "nbr": jnp.asarray(tables.nbr.astype(np.int32)),
            "rev_port": jnp.asarray(tables.rev_port.astype(np.int32)),
            "port_toward": jnp.asarray(tables.port_toward.astype(np.int16)),
            "dist": jnp.asarray(tables.dist.astype(np.int16)),
        }
        if tables.ecmp_ports is not None:
            ops["ecmp_ports"] = jnp.asarray(
                tables.ecmp_ports.astype(np.int16))
        return ops

    def table_operands(self) -> dict:
        """This core's current table arrays (pass back via bind_tables)."""
        ops = {"nbr": self.nbr, "rev_port": self.rev_port,
               "port_toward": self.port_toward, "dist": self.dist}
        if self.has_ecmp:
            ops["ecmp_ports"] = self.ecmp_ports
        return ops

    def bind_tables(self, ops: dict) -> "SwitchCore":
        """Shallow copy with the table arrays swapped for `ops` (tracers
        inside a jit/vmap, or another mask's concrete arrays)."""
        assert ("ecmp_ports" in ops) == self.has_ecmp
        c = copy.copy(self)
        for name, arr in ops.items():
            setattr(c, name, arr)
        return c

    def bind_source_routes(self, route_port, vc_base,
                           to_gid=None) -> "SwitchCore":
        """Shallow copy in SOURCE-ROUTED mode (DESIGN.md §13).

        `route_port [M, H]` gives the output port message m takes at
        hop h (indexed by the packed hop counter); a negative entry
        means "this router is the terminal hop — eject".  `vc_base [M]`
        is the message's VC class: hop h rides VC
        ``min(vc_base + h, V - 1)``.  `to_gid` maps the packed MSG
        field to a route_port row (identity when message ids are
        global).  Route choice from the routing tables is bypassed
        entirely; occupancy/credits, W-round allocation, compaction and
        ejection machinery are unchanged.  Both arrays may be closure
        constants (single-lane) or traced operands (the schedule-search
        lane sweep, which varies them per lane)."""
        c = copy.copy(self)
        c.src_route = (route_port, vc_base)
        c.src_to_gid = to_gid if to_gid is not None else (lambda f: f)
        return c

    # -- queue state ---------------------------------------------------------
    # Queues are shift-down FIFOs: the head packet always sits at slot 0
    # and slots 0..count-1 are occupied, so the W-slot allocation window
    # is a STATIC slice and dequeue+compaction is a static-shift select
    # — no circular-head gathers or scatters anywhere on the flit
    # arrays (DESIGN.md §9).  The abstract queue sequence is identical
    # to the seed's circular FIFOs, so grants are bit-identical.
    def init_queues(self) -> tuple:
        """(nq_pkt, nq_count, sq_pkt, sq_count) zeros."""
        N, P, V, Qn, Qs = self.N, self.P, self.V, self.Qn, self.Qs
        return (jnp.zeros((N, P, V, Qn, PK), jnp.int32),
                jnp.zeros((N, P, V), jnp.int32),
                jnp.zeros((self.n_ep, Qs, PK), jnp.int32),
                jnp.zeros((self.n_ep,), jnp.int32))

    @jax.named_scope("switch.occupancy")
    def occupancy(self, nq_count):
        """Credit view: occ[r, o] = downstream input-queue depth."""
        safe_nbr = jnp.maximum(self.nbr, 0)
        safe_rev = jnp.maximum(self.rev_port, 0)
        occ = nq_count[safe_nbr, safe_rev, :].sum(-1)          # [N, P]
        return jnp.where(self.nbr >= 0, occ, BIG)

    @jax.named_scope("switch.inject")
    def inject(self, sq_pkt, sq_count, want, new_pkt):
        """Masked tail enqueue into the per-endpoint source FIFOs.

        `want` must already account for backpressure (`sq_count < Qs`);
        both engines share these mechanics by construction.  Masked
        dense write: XLA CPU scatters serialise per row, a [n_ep, Qs]
        select does not (DESIGN.md §9).
        """
        ins = want[:, None] & (jnp.arange(self.Qs) == sq_count[:, None])
        sq_pkt = jnp.where(ins[..., None], new_pkt[:, None, :], sq_pkt)
        return sq_pkt, sq_count + want.astype(jnp.int32)

    # -- routing -------------------------------------------------------------
    def _dist32(self, s, t):
        return self.dist[s, t].astype(jnp.int32)

    @jax.named_scope("switch.route")
    def route_decision(self, dst_r, occ, key):
        """Per-endpoint injection-time path choice -> (inter, phase)."""
        mode, C, N, n_ep = self.mode, self.C, self.N, self.n_ep
        src_r = self.ep_router
        port_toward, nbr = self.port_toward, self.nbr
        if mode in ("min", "ecmp"):
            return dst_r, jnp.ones_like(dst_r)
        if mode == "val":
            i = jax.random.randint(key, (n_ep,), 0, N)
            for bump in (1, 1):
                bad = (i == src_r) | (i == dst_r)
                i = jnp.where(bad, (i + bump) % N, i)
            # degraded fabrics: only detour via intermediates that can
            # still reach both endpoints; dead draws fall back to MIN
            live = (self._dist32(src_r, i)
                    + self._dist32(i, dst_r)) < self.unreach
            return (jnp.where(live, i, dst_r),
                    (~live).astype(jnp.int32))

        # UGAL: score MIN vs C random VAL candidates (live ones only)
        cands = jax.random.randint(key, (n_ep, C), 0, N)
        for bump in (1, 2):
            bad = (cands == src_r[:, None]) | (cands == dst_r[:, None])
            cands = jnp.where(bad, (cands + bump) % N, cands)

        def first_occ(s, t):
            o = port_toward[s, t].astype(jnp.int32)
            return jnp.where(o >= 0,
                             jnp.minimum(occ[s, jnp.maximum(o, 0)], OCC_CAP),
                             0)

        def path_occ(s, t):
            """Occupancy sum along the MIN path (D <= 2 fast form)."""
            o1 = port_toward[s, t].astype(jnp.int32)
            m = nbr[s, jnp.maximum(o1, 0)]
            two = self._dist32(s, t) >= 2
            second = jnp.where(two, first_occ(m, t), 0)
            return first_occ(s, t) + second

        len_min = self._dist32(src_r, dst_r)                      # [n_ep]
        len_val = (self._dist32(src_r[:, None], cands)
                   + self._dist32(cands, dst_r[:, None]))
        if mode == "ugal_l":
            occ_min = first_occ(src_r, dst_r)
            occ_val = first_occ(src_r[:, None], cands)
        else:  # ugal_g: smallest sum of queues along the whole path
            occ_min = path_occ(src_r, dst_r)
            occ_val = (path_occ(src_r[:, None], cands)
                       + path_occ(cands, dst_r[:, None]))

        best = ugal_select(len_min, len_val, occ_min, occ_val,
                           ugal_g=(mode == "ugal_g"),
                           unreach=int(UNREACH), big=int(BIG),
                           use_pallas=self.use_pallas)
        inters = jnp.concatenate([dst_r[:, None], cands], axis=1)
        inter = jnp.take_along_axis(inters, best[:, None], 1)[:, 0]
        phase = (best == 0).astype(jnp.int32)                     # MIN: phase 1
        return inter, phase

    # -- allocation ----------------------------------------------------------
    def _desires(self, pkt, router, occ):
        if self.src_route is not None:
            return self._desires_src(pkt)
        dst, inter, phase = pk_dst(pkt), pk_inter(pkt), pk_phase(pkt)
        tgt = jnp.where(phase == 1, dst, inter)
        eject = (dst == router) & (phase == 1)
        min_port = self.port_toward[router, tgt].astype(jnp.int32)
        if self.has_ecmp:
            # dead alternates are skipped automatically: occupancy() is
            # BIG where nbr < 0, so argmin lands on a live port
            opts = self.ecmp_ports[router, tgt].astype(jnp.int32)  # [..., M]
            r_b = jnp.broadcast_to(router[..., None], opts.shape)
            o_occ = jnp.where(opts >= 0,
                              occ[r_b, jnp.maximum(opts, 0)], BIG)
            pick = jnp.argmin(o_occ, axis=-1)
            ecmp_port = jnp.take_along_axis(opts, pick[..., None],
                                            -1)[..., 0]
            if self.mode == "ecmp":
                out_port = ecmp_port
            else:
                # MIN first; equal-cost alternate only when the MIN
                # port is dead (transient failure mask on tables whose
                # routes have not re-converged, DESIGN.md §8)
                min_dead = ((min_port >= 0)
                            & (self.nbr[router,
                                        jnp.maximum(min_port, 0)] < 0))
                out_port = jnp.where(min_dead, ecmp_port, min_port)
            out_port = jnp.where(eject, -1, out_port)
        else:
            out_port = min_port
        out_vc = jnp.minimum(pk_hops(pkt), self.V - 1)
        return out_port, out_vc, eject

    def _desires_src(self, pkt):
        """Source-routed desires: the packet's own path table decides.

        Hop h of message m wants `route_port[gid, h]`; a negative port
        is the eject sentinel at the path's terminal router.  Garbage
        records in zero-initialised queue slots read row 0 harmlessly:
        the allocation kernel masks every request by the cycle-start
        queue depth, so out-of-count slots can never be granted."""
        route_port, vc_base = self.src_route
        M, H = route_port.shape[-2], route_port.shape[-1]
        hops = pk_hops(pkt)
        gid = jnp.clip(self.src_to_gid(pk_msg(pkt)), 0, M - 1)
        out_port = route_port[gid, jnp.minimum(hops, H - 1)]
        out_port = out_port.astype(jnp.int32)
        eject = out_port < 0
        out_vc = jnp.minimum(vc_base[gid].astype(jnp.int32) + hops,
                             self.V - 1)
        return out_port, out_vc, eject

    def alloc(self, nq_pkt, nq_count, sq_pkt, sq_count,
              occ, cycle, eject_fold: Callable, eject_acc,
              tel_state=None, trace_sample=None, trace_extra=None):
        """One cycle of W-round switch allocation + compaction.

        Returns the four queue arrays plus the folded ejection
        accumulator (see the class docstring for the fold contract).
        When `tel_state` is passed (a telemetry.TelemetryState, or `()`
        with telemetry off) it is updated from this cycle's allocation
        outcome and returned as a sixth element; `trace_sample` /
        `trace_extra` carry the engine's flow sampler and injection
        events into the trace ring (repro.sim.telemetry).
        """
        N, P, V, Qn, Qs, W = (self.N, self.P, self.V, self.Qn,
                              self.Qs, self.W)
        PV, PE = P * V, self.p
        n_ep, n_epr = self.n_ep, self.n_epr
        nbr, rev_port = self.nbr, self.rev_port
        ebr = self.ep_block_router

        # ---- the W-slot window is a static slice of the shift-down
        # FIFOs, taken once for all rounds (identities 1 and 2 in the
        # module docstring make this exact).  Slots past the buffer end
        # (W > Qn fig8 configs) are zero-padded; their depth check
        # (count > w) can never pass, matching the seed's wrap rule.
        def head_window(pkt_arr, depth_axis_len):
            wn = min(W, depth_axis_len)
            win = pkt_arr[..., :wn, :]
            if wn < W:
                pad = [(0, 0)] * win.ndim
                pad[-2] = (0, W - wn)
                win = jnp.pad(win, pad)
            return win
        with jax.named_scope("switch.desires"):
            win_net = head_window(nq_pkt, Qn)                  # [N,P,V,W,PK]
            win_src = head_window(sq_pkt, Qs)                  # [n_ep,W,PK]
            r_bcast = jnp.broadcast_to(self.routers_n[..., None],
                                       (N, P, V, W))
            ep_bcast = jnp.broadcast_to(self.ep_router[:, None], (n_ep, W))
            n_out, n_vc, n_ej = self._desires(win_net, r_bcast, occ)
            s_out, s_vc, s_ej = self._desires(win_src, ep_bcast, occ)

        def space_of(router, out, vc):
            dr = nbr[router, jnp.maximum(out, 0)]
            dp = rev_port[router, jnp.maximum(out, 0)]
            depth = nq_count[jnp.maximum(dr, 0), jnp.maximum(dp, 0), vc]
            return (out >= 0) & (dr >= 0) & (depth < Qn)
        with jax.named_scope("switch.space"):
            n_sp = space_of(r_bcast, n_out, n_vc)
            s_sp = space_of(ep_bcast, s_out, s_vc)

        # ---- router-major request arrays for the allocation kernel
        # (W-last layout: the [N,P,V,W] desire arrays reshape in free)
        def rm_net(x):                             # [N,P,V,W] -> [N,PV,W]
            return x.reshape(N, PV, W)

        # routers -> their endpoint block, as a GATHER through the
        # inverse map epr_index (non-endpoint routers gather row 0,
        # masked to zero): bit-identical to the scatter .at[ebr].set
        # it replaces, but XLA CPU serialises scatters per row — and
        # under the sweep engine's lane vmap (sweep.py) a batched
        # scatter is the single hottest lowering in the whole step
        def rm_src(x):                             # [n_ep,W] -> [N,PE,W]
            y = x.reshape(n_epr, PE, W)
            g = y[jnp.maximum(self.epr_index, 0)]
            return jnp.where((self.epr_index >= 0)[:, None, None], g, 0)

        with jax.named_scope("switch.alloc"):
            live_q = (nbr >= 0)[:, :, None]
            cnt_net = jnp.where(live_q, nq_count, 0).reshape(N, PV)
            cs_rows = sq_count.reshape(n_epr, PE)[
                jnp.maximum(self.epr_index, 0)]
            cnt_src = jnp.where((self.epr_index >= 0)[:, None], cs_rows, 0)

            i32 = jnp.int32
            chan_n, ej_n, chan_s, ej_s, win_req = alloc_rounds(
                cycle, rm_net(n_out), rm_net(n_ej.astype(i32)),
                rm_net(n_sp.astype(i32)), cnt_net,
                rm_src(s_out), rm_src(s_ej.astype(i32)),
                rm_src(s_sp.astype(i32)), cnt_src, self.epr_index,
                W=W, P=P, V=V, PE=PE, p_budget=self.p, NQ=self.NQ, R=self.R,
                use_pallas=self.use_pallas)
            cs_net = chan_n.reshape(N, P, V)           # granted window offset
            ej_net = ej_n.reshape(N, P, V)             # (-1 = none), by kind
            cs_src = chan_s[ebr].reshape(n_ep)
            ej_src = ej_s[ebr].reshape(n_ep)

        # ---- engine-specific ejection stats, one fold per round
        with jax.named_scope("switch.fold"):
            for w in range(W):
                eject_acc = eject_fold(eject_acc, ej_net == w, ej_src == w,
                                       win_net[:, :, :, w], win_src[:, w],
                                       cycle)

        # ---- arrivals, as a dense per-(router, port) view: each input
        # port receives at most one packet per cycle, always from its
        # unique upstream channel, so `win_req` of the upstream router
        # identifies the arriving packet with [N, P]-sized gathers — no
        # R-row scatter (XLA CPU scatters serialise per row)
        with jax.named_scope("switch.arrivals"):
            u_c = jnp.maximum(nbr, 0)                  # upstream router [N,P]
            uo_c = jnp.maximum(rev_port, 0)            # its out port
            wi = win_req[u_c, uo_c]                    # winning request id
            valid = (nbr >= 0) & (wi >= 0)
            is_net = wi < PV
            wi_n = jnp.clip(wi, 0, PV - 1)
            eid = jnp.clip(self.epr_index[u_c] * PE + jnp.maximum(wi - PV, 0),
                           0, n_ep - 1)
            slot = jnp.maximum(
                jnp.where(is_net, chan_n[u_c, wi_n], cs_src[eid]), 0)
            win_net_pm = win_net.reshape(N, PV, W, PK)
            pkt = jnp.where(is_net[..., None],
                            win_net_pm[u_c, wi_n, slot],      # [N,P,PK]
                            win_src[eid, slot])
            vc = jnp.where(is_net,
                           n_vc.reshape(N, PV, W)[u_c, wi_n, slot],
                           s_vc[eid, slot])
            here = jnp.arange(N)[:, None]
            w2 = bump_hops_word(pkt[..., 2],
                                (here == pk_inter(pkt)).astype(jnp.int32))
            pkt = jnp.concatenate([pkt[..., :2], w2[..., None]], axis=-1)
            arrived = valid[..., None] & (jnp.arange(V) == vc[..., None])

        # ---- telemetry (data-only: nothing below reads tel_state).
        # Placed before the dequeue so the counters see the same
        # cycle-start queue depths the kernel saw.
        if tel_state is not None and self.tel.enabled:
            with jax.named_scope("switch.telemetry"):
                cs_t, tr_t = tel_state
                if self.tel.counters:
                    cs_t = tel.counters.count_cycle(cs_t, nq_count)
                    cs_t = tel.counters.count_alloc(
                        cs_t, self, cycle, win_net, win_src, win_req,
                        cs_net, ej_net, cs_src, ej_src, cnt_net, sq_count)
                if self.tel.trace:
                    tr_t = tel.trace.trace_alloc(
                        tr_t, self, cycle, valid, pkt, win_net, win_src,
                        ej_net, ej_src, trace_sample, trace_extra)
                tel_state = tel.TelemetryState(cs_t, tr_t)

        # ---- dequeue + compaction: removing the granted packet at
        # offset g is a static-shift select (slots >= g take their
        # successor) — order-preserving, no gathers or scatters; then
        # the arrival is inserted at the post-dequeue tail by a masked
        # select (one arrival per (router, port) per cycle)
        with jax.named_scope("switch.compaction"):
            g_net = jnp.maximum(cs_net, ej_net)
            g_src = jnp.maximum(cs_src, ej_src)
            deq_net = (g_net >= 0).astype(jnp.int32)
            deq_src = (g_src >= 0).astype(jnp.int32)

            sidx = jnp.arange(Qn, dtype=jnp.int32)
            up_net = jnp.concatenate(
                [nq_pkt[:, :, :, 1:], jnp.zeros_like(nq_pkt[:, :, :, :1])],
                axis=3)
            drop_m = (g_net[..., None] >= 0) & (sidx >= g_net[..., None])
            nq_pkt = jnp.where(drop_m[..., None], up_net, nq_pkt)
            tail = (nq_count - deq_net)[..., None]             # [N,P,V,1]
            ins = arrived[..., None] & (sidx == tail)          # [N,P,V,Qn]
            nq_pkt = jnp.where(ins[..., None], pkt[:, :, None, None, :],
                               nq_pkt)

            s_sidx = jnp.arange(Qs, dtype=jnp.int32)
            up_src = jnp.concatenate(
                [sq_pkt[:, 1:], jnp.zeros_like(sq_pkt[:, :1])], axis=1)
            s_drop = (g_src[:, None] >= 0) & (s_sidx >= g_src[:, None])
            sq_pkt = jnp.where(s_drop[..., None], up_src, sq_pkt)

            nq_count = nq_count + arrived.astype(jnp.int32) - deq_net
            sq_count = sq_count - deq_src

        if tel_state is None:
            return (nq_pkt, nq_count, sq_pkt, sq_count, eject_acc)
        return (nq_pkt, nq_count, sq_pkt, sq_count, eject_acc, tel_state)


def _open_loop_fold(acc, g_net, g_src, pkt_net, pkt_src, cycle):
    """Open-loop ejection stats: delivered count + latency sum."""
    delivered, lat_sum = acc
    delivered = (delivered + g_net.sum().astype(jnp.int32)
                 + g_src.sum().astype(jnp.int32))
    lat = (jnp.where(g_net, cycle - pk_time(pkt_net) + 1, 0).sum()
           + jnp.where(g_src, cycle - pk_time(pkt_src) + 1, 0).sum())
    return delivered, lat_sum + lat.astype(jnp.float32)


# (tables, traffic, static-config) -> compiled (carry, rate) -> per-cycle
# stats.  The single-lane runner keeps the routing tables as CLOSURE
# CONSTANTS: XLA specialises the per-cycle gathers against constant
# index tables (measured ~2.5x at q=11 vs operand tables), so the
# single-lane hot path deliberately recompiles per failure mask — a
# sweep over masks belongs on the lane-batched path (repro.sim.sweep),
# which lifts the tables into traced operands and pays one compile for
# all masks (DESIGN.md §10).  Values pin the tables/traffic objects so
# the id() keys cannot be silently reused by the allocator; the FIFO
# bound keeps a long-lived process from accumulating compiled
# executables without limit.
_OPEN_LOOP_CACHE: dict = {}
_CACHE_MAX = 32


def _cache_put(cache: dict, key, value) -> None:
    while len(cache) >= _CACHE_MAX:
        cache.pop(next(iter(cache)))
    cache[key] = value


def tables_signature(tables: SimTables) -> tuple:
    """Compile-relevant structure of a table set: everything that shapes
    the traced step EXCEPT the mask-dependent array values."""
    return (tables.n_routers, tables.P, tables.p, tables.n_endpoints,
            None if tables.ecmp_ports is None
            else tables.ecmp_ports.shape[-1],
            tables.ep_router.tobytes())


def _open_loop_step(core: SwitchCore, traffic: Traffic, rate):
    """One-cycle step closure of the open-loop engine for `core`.

    Rank-polymorphic by construction: the sweep engine maps this exact
    function over a lane axis with jax.vmap, so per-lane results are
    bit-identical to L sequential runs (tests/test_sweep.py)."""
    active = jnp.asarray(traffic.active)
    n_ep, Qs = core.n_ep, core.Qs
    sample = traffic.sample
    tcfg = core.tel
    sampler = (tel.trace.flow_sampler(tcfg.trace_sample_shift)
               if tcfg.trace else None)

    def step(carry, cycle):
        nq_pkt, nq_count, sq_pkt, sq_count, key, ts = carry
        key, k_inj, k_dst, k_rt = jax.random.split(key, 4)

        occ = core.occupancy(nq_count)

        # ---- injection ----------------------------------------------------
        coin = jax.random.bernoulli(k_inj, rate, (n_ep,)) & active
        want = coin & (sq_count < Qs)
        dropped = (coin & (sq_count >= Qs)).sum()
        dst_ep = sample(k_dst)
        dst_r = core.ep_router[dst_ep]
        inter, phase = core.route_decision(dst_r, occ, k_rt)
        new_pkt = pack_record(dst_r, inter, cycle,
                              jnp.zeros((n_ep,), jnp.int32), phase)
        sq_pkt, sq_count = core.inject(sq_pkt, sq_count, want, new_pkt)
        injected = want.sum()

        # ---- telemetry at the injection point (data-only)
        extra = None
        if tcfg.counters:
            ts = tel.TelemetryState(
                tel.counters.count_routes(ts.counters, want, phase),
                ts.trace)
        if tcfg.trace:
            extra = (want & sampler(new_pkt),
                     tel.trace.pack_events(cycle, tel.trace.KIND_INJECT,
                                           core.ep_router,
                                           tel.trace.PORT_EP, new_pkt))

        # ---- shared switch pipeline ---------------------------------------
        (nq_pkt, nq_count, sq_pkt, sq_count,
         (delivered, lat_sum), ts) = core.alloc(
             nq_pkt, nq_count, sq_pkt, sq_count,
             occ, cycle, _open_loop_fold,
             (jnp.int32(0), jnp.float32(0.0)),
             tel_state=ts, trace_sample=sampler, trace_extra=extra)

        in_flight = (nq_count.sum() + sq_count.sum()).astype(jnp.int32)
        stats = (injected.astype(jnp.int32), delivered,
                 lat_sum, sq_count.sum().astype(jnp.int32),
                 dropped.astype(jnp.int32), in_flight)
        return (nq_pkt, nq_count, sq_pkt, sq_count, key, ts), stats

    return step


def _open_loop_runner(tables: SimTables, traffic: Traffic, cfg: SimConfig):
    """Compiled (carry0, rate) -> (final carry, per-cycle stats), with
    the initial carry DONATED (its buffers are reused for the scan
    state, DESIGN.md §10) and the tables baked in as constants."""
    key = (id(tables), id(traffic), cfg.static_key())
    hit = _OPEN_LOOP_CACHE.get(key)
    if hit is not None and hit[0] is tables and hit[1] is traffic:
        return hit[2]

    core = SwitchCore(tables, cfg)

    def run(carry, rate):
        step = _open_loop_step(core, traffic, rate)
        cycles = jnp.arange(cfg.cycles, dtype=jnp.int32)
        carry, stats = jax.lax.scan(step, carry, cycles)
        # the final carry is returned (and dropped by callers) so the
        # DONATED initial carry has aliasable targets: the queue-state
        # buffers are reused in place instead of being double-allocated
        # (peak-memory assertion in tests/test_engine_scaling.py)
        return carry, stats

    fn = jax.jit(run, donate_argnums=(0,))
    _cache_put(_OPEN_LOOP_CACHE, key, (tables, traffic, (core, fn)))
    return core, fn


def _assemble_result(tables: SimTables, traffic: Traffic, cfg: SimConfig,
                     n_active: int, stats: tuple,
                     telemetry: Optional[TelemetrySnapshot] = None
                     ) -> SimResult:
    """Host-side reduction of per-cycle scan stats into a SimResult
    (shared by `simulate` and the lane-batched sweep engine)."""
    inj, dlv, lat, occ_s, drop, infl = stats
    inj = np.asarray(inj, dtype=np.int64)
    dlv = np.asarray(dlv, dtype=np.int64)
    lat = np.asarray(lat, dtype=np.float64)
    occ_s = np.asarray(occ_s, dtype=np.float64)
    drop = np.asarray(drop, dtype=np.int64)
    infl = np.asarray(infl, dtype=np.int64)

    n_ep = tables.n_endpoints
    w = cfg.warmup
    meas = slice(w, cfg.cycles)
    m_cycles = cfg.cycles - w
    delivered_m = int(dlv[meas].sum())
    accepted = delivered_m / (m_cycles * max(n_active, 1))
    avg_lat = float(lat[meas].sum() / max(delivered_m, 1))
    return SimResult(
        name=f"{traffic.name}-{cfg.mode}",
        offered_load=cfg.injection_rate,
        accepted_load=float(accepted),
        avg_latency=avg_lat,
        delivered=int(dlv.sum()),
        injected=int(inj.sum()),
        dropped_at_source=int(drop.sum()),
        src_occupancy=float(occ_s[meas].mean() / max(n_ep, 1)),
        per_cycle_delivered=dlv,
        per_cycle_injected=inj,
        per_cycle_in_flight=infl,
        per_cycle_dropped=drop,
        q_src=cfg.q_src,
        telemetry=telemetry,
    )


def _init_carry(core: SwitchCore, seed) -> tuple:
    """The open-loop scan's initial carry (donated to the runner)."""
    return core.init_queues() + (jax.random.PRNGKey(seed),
                                 tel.init_state(core.tel, core))


def compiled_runner_hlo() -> list:
    """Optimised HLO text of each open-loop runner compiled in this
    process.  Its `op_name` metadata carries the stage scopes
    (`switch.*`), which is how a profile's device ops are attributed to
    the stages of a cycle.  Lowering again with the runner's abstract
    arguments reuses the executable that ran (from JAX's caches)."""
    out = []
    for _, _, (core, fn) in list(_OPEN_LOOP_CACHE.values()):
        carry = jax.eval_shape(lambda: _init_carry(core, 0))
        rate = jax.ShapeDtypeStruct((), jnp.float32)
        out.append(fn.lower(carry, rate).compile().as_text())
    return out


def simulate(tables: SimTables, traffic: Traffic, cfg: SimConfig) -> SimResult:
    # host spans on the profiler's clock (inert unless it is tracing):
    # the dispatch of the compiled scan returns at once, so the wait on
    # the device falls in `sim.assemble`, where stats reach the host
    with jax.profiler.TraceAnnotation("sim.simulate", seed=cfg.seed):
        n_active = int(traffic.active.sum())
        core, fn = _open_loop_runner(tables, traffic, cfg)
        with jax.profiler.TraceAnnotation("sim.init_carry"):
            carry0 = _init_carry(core, cfg.seed)
        with jax.profiler.TraceAnnotation("sim.scan"):
            carry, stats = fn(carry0, jnp.float32(cfg.injection_rate))
        with jax.profiler.TraceAnnotation("sim.assemble"):
            snap = tel.snapshot(cfg.telemetry, carry[5], cfg.cycles)
            return _assemble_result(tables, traffic, cfg, n_active, stats,
                                    snap)
