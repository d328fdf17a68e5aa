"""Closed-loop dependency-triggered workload engine (DESIGN.md §7, §11).

Runs one or more :class:`~repro.sim.workloads.ir.Workload` message-DAGs
to completion on the cycle-level flit simulator and measures job
completion time — the quantity the open-loop Bernoulli engine
(`repro.sim.engine.simulate`) structurally cannot produce.

The engine shares :class:`repro.sim.engine.SwitchCore` (credit view,
route choice, W-round allocation, compaction) with the open-loop
simulator; only injection and the ejection fold differ:

  - packet records carry an extra MSG field (bit-packed, see
    repro.sim.packed) naming the message a flit belongs to, so the
    ejection fold can scatter-add per-message delivered-flit counts;
  - each cycle the ready set is re-derived as a dense mask over DAG
    messages from the carried delivered-flit counters (`done[dep]`
    gather over the padded dep matrix), every endpoint injects one flit
    of its lowest-id ready unfinished message, and a message completes
    when its delivered count reaches its size;
  - the scan runs in fixed-size compiled chunks with a host-side
    all-done check between chunks: one trace/compile per (tables,
    workload, placement, config) signature regardless of makespan, and
    early exit at chunk granularity.

Multi-job generalisation (DESIGN.md §11): the compiled step works on a
CONCATENATED message space over J jobs (`_MsgSpace`).  Message ids are
global; the packed MSG field carries ``job << MSG_JOB_SHIFT | local``
so the ejection fold can recover the global id with one [J+1]-offset
gather.  Sendability is additionally gated on a per-job admit-cycle
vector carried in the scan state (set host-side by the admission
scheduler in `repro.sim.workloads.jobs`), and per-cycle stats report
per-job done-message counts.  A single job admitted at cycle 0 makes
every added term the identity, so `run_workload` results are
bit-identical to the pre-job-layer engine (golden-pinned in
tests/test_jobs.py).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry as tel
from ..engine import (BIG, SimConfig, SwitchCore, _cache_put,
                      tables_signature)
from ..packed import (MAX_JOB_MSGS, MAX_JOBS, MSG_JOB_SHIFT, pack_record,
                      pk_msg)
from ..tables import SimTables
from ..telemetry import TelemetryConfig, TelemetrySnapshot
from .ir import Workload
from .mapping import place_ranks

__all__ = ["WorkloadSimConfig", "WorkloadResult", "run_workload"]


@dataclasses.dataclass(frozen=True)
class WorkloadSimConfig:
    vcs: int = 4
    q_net: int = 16
    q_src: int = 64
    mode: str = "min"                 # min | val | ugal_l | ugal_g | ecmp
    # "table": route choice from the routing tables (the modes above);
    # "source": per-message explicit paths from a PolicyWorkload's
    # route_port/vc_base arrays (DESIGN.md §13) — requires mode="min"
    # (source routing bypasses adaptive choice; injection stays on the
    # MIN record layout so table-MIN runs stay bit-comparable)
    routing: str = "table"
    n_val_candidates: int = 4
    lookahead: int = 4
    seed: int = 0
    placement: str = "linear"         # see workloads.mapping.PLACEMENTS
    chunk: int = 256                  # cycles per compiled scan chunk
    max_cycles: int = 200_000         # give up (makespan = inf) past this
    kernel_path: str = "auto"         # auto | ref | pallas (DESIGN.md §9)
    # opt-in counters/tracing (repro.sim.telemetry); default off adds
    # zero carry leaves and is bit-exact vs a build without the layer
    telemetry: TelemetryConfig = TelemetryConfig()

    def to_sim_config(self) -> SimConfig:
        return SimConfig(vcs=self.vcs, q_net=self.q_net, q_src=self.q_src,
                         mode=self.mode,
                         n_val_candidates=self.n_val_candidates,
                         lookahead=self.lookahead, seed=self.seed,
                         kernel_path=self.kernel_path,
                         telemetry=self.telemetry)

    def static_key(self) -> tuple:
        # `routing` MUST be part of the key: a source-routed and a
        # table-routed runner for the same (tables, workload) trace
        # different steps, and sharing a cache slot would silently run
        # the wrong one (regression test in tests/test_policy.py)
        return (self.vcs, self.q_net, self.q_src, self.mode, self.routing,
                self.n_val_candidates, self.lookahead, self.placement,
                self.chunk, self.kernel_path,
                self.telemetry.static_key())


@dataclasses.dataclass
class WorkloadResult:
    name: str
    mode: str
    placement: str
    n_ranks: int
    n_messages: int
    completed: bool
    makespan: float                   # cycles; inf if hit max_cycles
    cycles_run: int
    flits_injected: int
    flits_delivered: int
    msg_size: np.ndarray              # [M]
    msg_phase: np.ndarray             # [M]
    msg_sent: np.ndarray              # [M] flits injected per message
    msg_delivered: np.ndarray         # [M] flits ejected per message
    msg_start: np.ndarray             # [M] first-injection cycle (-1 never)
    msg_done: np.ndarray              # [M] completion cycle (-1 never)
    per_cycle_delivered: np.ndarray   # [cycles_run]
    ep_of_rank: np.ndarray            # [n_ranks] the placement used
    telemetry: Optional[TelemetrySnapshot] = None

    @property
    def achieved_bw(self) -> float:
        """Delivered flits per cycle, fabric-level.

        Completed runs average over the makespan; incomplete (timed
        out) runs average over the cycles actually run — a degraded
        fabric that still moves flits must not plot as zero bandwidth
        just because the DAG missed the max_cycles deadline
        (`benchmarks/faults_sweep.py` relies on this).
        """
        span = (self.makespan if np.isfinite(self.makespan)
                else float(self.cycles_run))
        if span <= 0:
            return 0.0
        return float(self.flits_delivered / span)

    @property
    def avg_msg_latency(self) -> float:
        """Mean message start->completion time, completed messages."""
        ok = self.msg_done >= 0
        if not ok.any():
            return float("nan")
        return float((self.msg_done[ok] - self.msg_start[ok]).mean())


# ---------------------------------------------------------------------------
# concatenated multi-job message space
# ---------------------------------------------------------------------------

def _pick_rows(src_ep: np.ndarray, n_ep: int):
    """The rows of the per-endpoint pick, (msgs [n_act, kmax], row
    [n_ep]).  ``msgs[r]`` lists, in ascending global id and -1 padded,
    the messages of the r-th endpoint that owns any; ``row[e]`` is
    endpoint e's row, ``n_act`` for one that owns none.  Only the
    endpoints with messages are scanned: a 256-rank ring on 10,830
    endpoints gathers 2.4% of the dense [n_ep, kmax] predicates."""
    counts = np.bincount(src_ep, minlength=n_ep)
    act = np.flatnonzero(counts)
    kmax = max(1, int(counts.max(initial=0)))
    # a stable sort keeps each endpoint's messages in ascending id
    order = np.argsort(src_ep, kind="stable").astype(np.int32)
    ep_sorted = src_ep[order]
    col = np.arange(len(order)) - np.searchsorted(ep_sorted, ep_sorted)
    row = np.full(n_ep, len(act), dtype=np.int32)
    row[act] = np.arange(len(act), dtype=np.int32)
    msgs = np.full((len(act), kmax), -1, dtype=np.int32)
    msgs[row[ep_sorted], col] = order
    return msgs, row


def _pick(msgs, row, sendable):
    """Each endpoint's lowest-id sendable message over the rows of
    `_pick_rows`: (has [n_ep], mpick [n_ep]) from sendable [M], mpick
    0 where an endpoint has nothing to send."""
    cand = (msgs >= 0) & sendable[jnp.maximum(msgs, 0)]
    has = cand.any(axis=1)
    slot = jnp.argmax(cand, axis=1)
    mpick = jnp.where(has, msgs[jnp.arange(msgs.shape[0]), slot], 0)
    # back to [n_ep] by a gather; the appended row answers for the
    # endpoints that own no message
    return jnp.append(has, False)[row], jnp.append(mpick, 0)[row]


@dataclasses.dataclass(frozen=True)
class _MsgSpace:
    """Host-side concatenation of J workload DAGs into one message
    space (global message ids; per-job offsets recover job-local ids).

    ``fid`` is the value injected into the packed MSG field:
    ``job << MSG_JOB_SHIFT | local_id``.  For J=1 it equals the global
    id, so single-job packet records are unchanged bit-for-bit.
    """
    n_jobs: int
    n_messages: int                   # Mtot over all jobs
    job_off: np.ndarray               # [J+1] cumulative message offsets
    src_ep: np.ndarray                # [Mtot]
    dst_ep: np.ndarray                # [Mtot]
    size: np.ndarray                  # [Mtot]
    dep: np.ndarray                   # [Mtot, Dmax] global ids, -1 pad
    fid: np.ndarray                   # [Mtot] packed MSG-field values


def _build_space(wls: Sequence[Workload],
                 eps: Sequence[np.ndarray]) -> _MsgSpace:
    assert len(wls) == len(eps) and len(wls) >= 1
    assert len(wls) <= MAX_JOBS, \
        f"{len(wls)} jobs overflow the {MAX_JOBS}-job MSG field budget"
    off = np.zeros(len(wls) + 1, dtype=np.int64)
    src_l, dst_l, size_l, dep_l, fid_l = [], [], [], [], []
    dmax = max(max(1, w.dep_matrix().shape[1]) for w in wls)
    for j, (wl, ep) in enumerate(zip(wls, eps)):
        m = wl.n_messages
        assert m < MAX_JOB_MSGS, \
            f"job {j}: {m} messages overflow the per-job id budget"
        off[j + 1] = off[j] + m
        src_l.append(ep[wl.src])
        dst_l.append(ep[wl.dst])
        size_l.append(wl.size.astype(np.int32))
        dm = np.full((m, dmax), -1, dtype=np.int32)
        d = wl.dep_matrix()
        dm[:, :d.shape[1]] = np.where(d >= 0, d + off[j], -1)
        dep_l.append(dm)
        fid_l.append((j << MSG_JOB_SHIFT) + np.arange(m, dtype=np.int32))
    return _MsgSpace(
        n_jobs=len(wls), n_messages=int(off[-1]), job_off=off,
        src_ep=np.concatenate(src_l).astype(np.int32),
        dst_ep=np.concatenate(dst_l).astype(np.int32),
        size=np.concatenate(size_l),
        dep=np.concatenate(dep_l, axis=0),
        fid=np.concatenate(fid_l))


# (tables, workloads, placement-bytes, static-config) -> compiled chunk
# runner.  The single-lane runner keeps the tables as closure constants
# (gather specialisation, see repro.sim.engine) and so recompiles per
# failure mask; the lane-batched sweep below lifts them into operands
# so all masks of one topology share one executable (DESIGN.md §10).
# Values pin the keyed objects against id() reuse, and the shared FIFO
# bound caps compiled-executable retention.
_RUNNER_CACHE: dict = {}


def _source_operands(wls: Sequence[Workload]) -> tuple:
    """Concatenated source-routing arrays over a job mix: route_port
    [Mtot, Hmax] (short paths right-padded with the eject sentinel) and
    vc_base [Mtot].  Every workload must be a lowered PolicyWorkload."""
    for j, w in enumerate(wls):
        if getattr(w, "route_port", None) is None:
            raise ValueError(
                f"job {j} ({w.name!r}): routing='source' needs "
                f"PolicyWorkloads (Policy.lower / emit_policy), got a "
                f"plain Workload with no route_port")
    H = max(w.route_port.shape[1] for w in wls)
    rps = [np.pad(w.route_port,
                  ((0, 0), (0, H - w.route_port.shape[1])),
                  constant_values=-1) for w in wls]
    return (np.concatenate(rps, axis=0).astype(np.int32),
            np.concatenate([w.vc_base for w in wls]).astype(np.int32))


def _space_runner(tables: SimTables, wls: Tuple[Workload, ...],
                  eps: Tuple[np.ndarray, ...], cfg: WorkloadSimConfig):
    """Compiled chunk runner over the concatenated message space of
    `wls` placed at `eps`.  Returns (jitted_runner, init_carry,
    (run_chunk_const, run_chunk_ops), space)."""
    key = (id(tables), tuple(id(w) for w in wls),
           tuple(e.tobytes() for e in eps), cfg.static_key())
    hit = _RUNNER_CACHE.get(key)
    if hit is not None and hit[0] is tables and hit[1] == tuple(wls):
        return hit[2]

    space = _build_space(wls, eps)
    core = SwitchCore(tables, cfg.to_sim_config())
    n_ep, Qs = core.n_ep, core.Qs
    M, J = space.n_messages, space.n_jobs

    size = jnp.asarray(space.size)
    dep = jnp.asarray(space.dep)                            # [M, Dmax]
    fid = jnp.asarray(space.fid)                            # [M]
    job_off = jnp.asarray(space.job_off.astype(np.int32))   # [J+1]
    dst_r_of_msg = jnp.asarray(
        tables.ep_router[space.dst_ep].astype(np.int32))    # [M]
    job_of_msg = jnp.asarray(np.repeat(
        np.arange(J, dtype=np.int32), np.diff(space.job_off)))  # [M]
    mid_mask = jnp.int32(MAX_JOB_MSGS - 1)

    # per-endpoint message rows (ascending GLOBAL id: topological
    # within each job, earlier-arriving job first across jobs)
    pick_msgs, pick_row = map(jnp.asarray, _pick_rows(space.src_ep, n_ep))

    def to_gid(field):
        # MSG field -> global message id; job ids of live packets are
        # always < J, min() only guards garbage in zero-initialised
        # queue slots (those are g=False and dropped anyway)
        j = jnp.minimum(field >> MSG_JOB_SHIFT, J - 1)
        return job_off[j] + (field & mid_mask)

    assert cfg.routing in ("table", "source"), cfg.routing
    if cfg.routing == "source":
        # explicit paths replace table route choice in the core; the
        # arrays ride as closure constants here (single schedule), the
        # schedule-search lane sweep below lifts them into operands
        assert cfg.mode == "min", \
            "routing='source' bypasses adaptive route choice; use " \
            "mode='min' (the paths themselves encode any detour)"
        rp, vb = _source_operands(wls)
        core = core.bind_source_routes(jnp.asarray(rp), jnp.asarray(vb),
                                       to_gid)

    def fold(acc, g_net, g_src, pkt_net, pkt_src, cycle):
        # per-message flit accounting; message latency comes from the
        # carried start/done cycles, not a per-flit sum
        flits_del, delivered = acc
        mn = jnp.where(g_net, to_gid(pk_msg(pkt_net)), M)    # M = OOB drop
        ms = jnp.where(g_src, to_gid(pk_msg(pkt_src)), M)
        flits_del = flits_del.at[mn.reshape(-1)].add(1, mode="drop")
        flits_del = flits_del.at[ms].add(1, mode="drop")
        delivered = (delivered + g_net.sum().astype(jnp.int32)
                     + g_src.sum().astype(jnp.int32))
        return flits_del, delivered

    def make_step(c):
        """Step closure over a table-bound core (rank-polymorphic: the
        sweep engine vmaps it over a lane axis, DESIGN.md §10)."""
        return lambda carry, cycle: step(c, carry, cycle)

    tcfg = core.tel
    # closed-loop tracing samples whole MESSAGES: every flit and hop of
    # a sampled message hashes the same packed MSG field
    sampler = (tel.trace.msg_sampler(tcfg.trace_sample_shift)
               if tcfg.trace else None)

    def step(c, carry, cycle):
        (nq_pkt, nq_count, sq_pkt, sq_count, admit,
         sent, flits_del, start_c, done_c, key, ts) = carry
        key, k_rt = jax.random.split(key)

        occ = c.occupancy(nq_count)

        # ---- ready set over the DAGs (dense mask, carried counters);
        # a message is sendable only once its job has been admitted
        with jax.named_scope("closed.ready"):
            done = flits_del >= size                        # [M]
            dep_ok = jnp.where(dep >= 0, done[jnp.maximum(dep, 0)],
                               True).all(axis=1)
            admitted = (cycle >= admit)[job_of_msg]         # [M]
            sendable = dep_ok & (sent < size) & admitted    # [M]

        # ---- per-endpoint pick: lowest-id sendable message
        with jax.named_scope("closed.pick"):
            has, mpick = _pick(pick_msgs, pick_row, sendable)   # [n_ep]

        # ---- inject one flit (same source-queue mechanics as open loop)
        want = has & (sq_count < Qs)
        dst_r = dst_r_of_msg[mpick]
        inter, phase = c.route_decision(dst_r, occ, k_rt)
        new_pkt = pack_record(dst_r, inter, cycle,
                              jnp.zeros((n_ep,), jnp.int32), phase,
                              msg=fid[mpick])
        sq_pkt, sq_count = c.inject(sq_pkt, sq_count, want, new_pkt)
        with jax.named_scope("closed.account"):
            msel = jnp.where(want, mpick, M)                # M = OOB drop
            sent = sent.at[msel].add(1, mode="drop")
            start_c = start_c.at[msel].min(cycle, mode="drop")

        # ---- telemetry at the injection point (data-only)
        extra = None
        if tcfg.counters:
            ts = tel.TelemetryState(
                tel.counters.count_routes(ts.counters, want, phase),
                ts.trace)
        if tcfg.trace:
            extra = (want & sampler(new_pkt),
                     tel.trace.pack_events(cycle, tel.trace.KIND_INJECT,
                                           c.ep_router,
                                           tel.trace.PORT_EP, new_pkt))

        # ---- shared switch pipeline with the per-message fold
        (nq_pkt, nq_count, sq_pkt, sq_count,
         (flits_del, delivered), ts) = c.alloc(
             nq_pkt, nq_count, sq_pkt, sq_count,
             occ, cycle, fold, (flits_del, jnp.int32(0)),
             tel_state=ts, trace_sample=sampler, trace_extra=extra)

        with jax.named_scope("closed.account"):
            now_done = flits_del >= size
            done_c = jnp.where(now_done & (done_c == BIG), cycle + 1,
                               done_c)
            # per-job done-message counts without a scatter: job
            # segments are contiguous, so a cumsum difference at the
            # offsets does it
            ncs = jnp.concatenate([
                jnp.zeros((1,), jnp.int32),
                jnp.cumsum(now_done.astype(jnp.int32))])
            n_done_job = ncs[job_off[1:]] - ncs[job_off[:-1]]   # [J]
        stats = (want.sum().astype(jnp.int32), delivered, n_done_job)
        return (nq_pkt, nq_count, sq_pkt, sq_count, admit,
                sent, flits_del, start_c, done_c, key, ts), stats

    def run_chunk_const(carry, offset):
        cycles = offset + jnp.arange(cfg.chunk, dtype=jnp.int32)
        return jax.lax.scan(make_step(core), carry, cycles)

    def run_chunk_ops(table_ops, carry, offset):
        c = core.bind_tables(table_ops)
        cycles = offset + jnp.arange(cfg.chunk, dtype=jnp.int32)
        return jax.lax.scan(make_step(c), carry, cycles)

    def init_carry(key0, admit0=None):
        if admit0 is None:
            admit0 = jnp.zeros((J,), jnp.int32)             # all at cycle 0
        return core.init_queues() + (
            jnp.asarray(admit0, jnp.int32),                 # admit cycles
            jnp.zeros((M,), jnp.int32),                     # sent
            jnp.zeros((M,), jnp.int32),                     # flits_delivered
            jnp.full((M,), BIG, jnp.int32),                 # start cycle
            jnp.full((M,), BIG, jnp.int32),                 # done cycle
            key0,
            tel.init_state(tcfg, core))                     # telemetry

    # the carry is donated: it is threaded through every chunk call and
    # aliases the returned carry, so queue state is updated in place
    # across the whole chunked run (DESIGN.md §10).  run_chunk_ops is
    # the operand-tables variant the mask-varying lane sweep vmaps.
    fn = (jax.jit(run_chunk_const, donate_argnums=(0,)), init_carry,
          (run_chunk_const, run_chunk_ops), space)
    _cache_put(_RUNNER_CACHE, key, (tables, tuple(wls), fn))
    return fn


def compiled_runner_hlo() -> list:
    """Optimised HLO text of each single-lane chunk runner compiled in
    this process (`run_workload`, `run_jobs`).  Its `op_name` metadata
    carries the stage scopes (`switch.*`, `closed.*`); see
    `repro.sim.engine.compiled_runner_hlo`."""
    out = []
    for key, (_, _, fn) in list(_RUNNER_CACHE.items()):
        if isinstance(key[0], str):             # the lane-batched sweeps
            continue
        run, init_carry, _, _ = fn
        carry = jax.eval_shape(lambda: init_carry(jax.random.PRNGKey(0)))
        offset = jax.ShapeDtypeStruct((), jnp.int32)
        out.append(run.lower(carry, offset).compile().as_text())
    return out


def _workload_result(wl: Workload, cfg: WorkloadSimConfig,
                     ep_of_rank: np.ndarray, msg_state: tuple,
                     per_cycle_dlv: np.ndarray, completed: bool,
                     cycles_run: int, tel_state=None) -> WorkloadResult:
    """Host-side reduction of final message counters into a
    WorkloadResult (shared by `run_workload` and the lane sweep)."""
    sent, flits_del, start_c, done_c = (
        np.asarray(a, dtype=np.int64) for a in msg_state)
    big = int(BIG)
    msg_start = np.where(start_c < big, start_c, -1)
    msg_done = np.where(done_c < big, done_c, -1)
    makespan = float(done_c.max()) if completed else float("inf")
    if completed:
        # the chunked host loop runs past completion to the chunk
        # boundary; trim the accounting to the true makespan (the
        # trailing cycles are post-completion and deliver nothing)
        cycles_run = int(done_c.max())
        per_cycle_dlv = per_cycle_dlv[:cycles_run]
    # counters normalise over the trimmed span: the overrun cycles are
    # post-drain (queues empty, no grants) so only occ_sum would be
    # diluted by including them
    snap = tel.snapshot(cfg.telemetry, tel_state, cycles_run)

    return WorkloadResult(
        name=wl.name, mode=cfg.mode, placement=cfg.placement,
        n_ranks=wl.n_ranks, n_messages=wl.n_messages, completed=completed,
        makespan=makespan, cycles_run=cycles_run,
        flits_injected=int(sent.sum()),
        flits_delivered=int(flits_del.sum()),
        msg_size=wl.size.copy(), msg_phase=wl.phase.copy(),
        msg_sent=sent, msg_delivered=flits_del,
        msg_start=msg_start, msg_done=msg_done,
        per_cycle_delivered=per_cycle_dlv,
        ep_of_rank=ep_of_rank,
        telemetry=snap,
    )


def run_workload(tables: SimTables, wl: Workload,
                 cfg: WorkloadSimConfig = WorkloadSimConfig(),
                 ep_of_rank: Optional[np.ndarray] = None) -> WorkloadResult:
    """Simulate `wl` to completion (or cfg.max_cycles) and report JCT."""
    if ep_of_rank is None:
        # a lowered PolicyWorkload bakes the placement its explicit
        # paths assume; honour it in BOTH routing modes so source vs
        # table comparisons run the same ranks on the same endpoints
        ep_of_rank = getattr(wl, "ep_of_rank", None)
    if ep_of_rank is None:
        ep_of_rank = place_ranks(tables, wl.n_ranks, cfg.placement,
                                 seed=cfg.seed)
    ep_of_rank = np.asarray(ep_of_rank, dtype=np.int32)
    run_chunk, init_carry, _, space = _space_runner(
        tables, (wl,), (ep_of_rank,), cfg)
    # the pick's rows are the endpoints that own messages, its width
    # the most messages any of them owns
    counts = np.bincount(space.src_ep)
    pick_rows = int(np.count_nonzero(counts))
    pick_width = max(1, int(counts.max(initial=0)))

    # host spans on the profiler's clock (inert unless it is tracing):
    # each chunk's span holds its dispatch and the host's wait for its
    # per-cycle stats, so the gaps between chunks fall inside them; the
    # pick's shape says how many endpoints its gather scans
    with jax.profiler.TraceAnnotation("workload.run", seed=cfg.seed,
                                      pick_rows=pick_rows,
                                      pick_width=pick_width):
        with jax.profiler.TraceAnnotation("workload.init_carry"):
            carry = init_carry(jax.random.PRNGKey(cfg.seed))
        M = wl.n_messages
        per_cycle_dlv = []
        completed = False
        t = 0
        while t < cfg.max_cycles:
            with jax.profiler.TraceAnnotation("workload.chunk", start=t):
                carry, (inj, dlv, n_done) = run_chunk(carry, jnp.int32(t))
                per_cycle_dlv.append(np.asarray(dlv, dtype=np.int64))
                t += cfg.chunk
                if int(n_done[-1, 0]) == M:
                    completed = True
                    break

        with jax.profiler.TraceAnnotation("workload.result"):
            (_, _, _, _, _, sent, flits_del, start_c, done_c, _, ts) = carry
            return _workload_result(wl, cfg, ep_of_rank,
                                    (sent, flits_del, start_c, done_c),
                                    np.concatenate(per_cycle_dlv),
                                    completed, t, tel_state=ts)


def _sweep_run_workload(tables: SimTables, wl: Workload,
                        cfg: Optional[WorkloadSimConfig] = None,
                        seeds=None,
                        ep_of_rank: Optional[np.ndarray] = None) -> list:
    """Lane-batched closed-loop runs over (tables, seed) lanes — the
    implementation behind `repro.sim.sweep.sweep_run_workload`.

    One vmap-ed chunk runner is compiled for all L lanes; the host
    loop keeps stepping until every lane reports all messages done (a
    finished lane idles inertly: nothing sendable, queues drained,
    done/start counters guarded against rewrite).  Per-lane results
    are bit-identical to sequential `run_workload` calls.

    Lanes vary DATA only (DESIGN.md §10): the job mix and placement
    are part of the traced step, so the sweep runs the single-job
    (J=1, admitted-at-0) degenerate of the multi-job engine.
    """
    from ..sweep import _lane_count

    cfg = cfg or WorkloadSimConfig()
    if ep_of_rank is None:
        ep_of_rank = getattr(wl, "ep_of_rank", None)
    seeds_l = ([cfg.seed] if seeds is None
               else [int(s) for s in np.atleast_1d(seeds)])
    L = _lane_count([("tables", tables.lanes), ("seeds", len(seeds_l))])
    seeds_l = seeds_l * (L if len(seeds_l) == 1 else 1)
    cfgs = [dataclasses.replace(cfg, seed=s) for s in seeds_l]

    if L == 1:
        return [run_workload(tables.lane(0), wl, cfgs[0],
                             ep_of_rank=ep_of_rank)]

    tab0 = tables.lane(0)
    if ep_of_rank is None:
        # placement must be lane-invariant (it shapes the pick's rows
        # and is baked into the compiled step); a seed-sensitive
        # placement with per-lane seeds would silently break the
        # bit-exactness contract, so refuse it instead of placing all
        # lanes with one seed
        placements = [place_ranks(tab0, wl.n_ranks, cfg.placement,
                                  seed=s) for s in seeds_l]
        if any(not np.array_equal(p, placements[0])
               for p in placements[1:]):
            raise ValueError(
                f"placement {cfg.placement!r} depends on the seed, so "
                f"per-lane seeds would place ranks differently per "
                f"lane; pass ep_of_rank= explicitly to pin one "
                f"placement for every lane")
        ep_of_rank = placements[0]
    ep_of_rank = np.asarray(ep_of_rank, dtype=np.int32)
    tables_vary = tables.lanes > 1
    _, init_carry, (chunk_const, chunk_ops), _ = _space_runner(
        tab0, (wl,), (ep_of_rank,), cfg)

    # mask-varying sweeps key structurally (one executable for any set
    # of failure samples of this topology); shared-table sweeps keep
    # the constants and key by table identity, like the single-lane path
    tab_key = tables_signature(tab0) if tables_vary else id(tab0)
    key = ("sweep", tab_key, id(wl), ep_of_rank.tobytes(),
           cfg.static_key(), L, tables_vary)
    hit = _RUNNER_CACHE.get(key)
    if hit is not None and hit[0] is wl and \
            (tables_vary or hit[1] is tab0):
        fn = hit[2]
    else:
        if tables_vary:
            table_axes = jax.tree_util.tree_map(
                lambda _: 0, SwitchCore.device_tables(tab0))
            fn = jax.jit(jax.vmap(chunk_ops,
                                  in_axes=(table_axes, 0, None)),
                         donate_argnums=(1,))
        else:
            fn = jax.jit(jax.vmap(chunk_const, in_axes=(0, None)),
                         donate_argnums=(0,))
        _cache_put(_RUNNER_CACHE, key, (wl, tab0, fn))

    lanes0 = [init_carry(jax.random.PRNGKey(s)) for s in seeds_l]
    # tree_map (not a per-element jnp.stack): the telemetry carry
    # element is a nested pytree — or () when telemetry is off
    carry = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *lanes0)
    table_ops = SwitchCore.device_tables(tables) if tables_vary else None

    M = wl.n_messages
    per_cycle_dlv = []
    done_lane = np.zeros(L, dtype=bool)
    t = 0
    while t < cfg.max_cycles:
        if tables_vary:
            carry, (inj, dlv, n_done) = fn(table_ops, carry, jnp.int32(t))
        else:
            carry, (inj, dlv, n_done) = fn(carry, jnp.int32(t))
        per_cycle_dlv.append(np.asarray(dlv, dtype=np.int64))   # [L, chunk]
        t += cfg.chunk
        done_lane = np.asarray(n_done)[:, -1, 0] == M
        if done_lane.all():
            break

    (_, _, _, _, _, sent, flits_del, start_c, done_c, _, ts) = carry
    dlv_all = np.concatenate(per_cycle_dlv, axis=1)             # [L, t]
    out = []
    for i in range(L):
        ts_i = jax.tree_util.tree_map(lambda a, i=i: a[i], ts)
        out.append(_workload_result(
            wl, cfgs[i], ep_of_rank,
            (sent[i], flits_del[i], start_c[i], done_c[i]),
            dlv_all[i], bool(done_lane[i]), t, tel_state=ts_i))
    return out


# ---------------------------------------------------------------------------
# lane-batched policy scoring (schedule search, DESIGN.md §13)
# ---------------------------------------------------------------------------

def _policy_sweep_runner(tables: SimTables, cfg: WorkloadSimConfig,
                         M: int, dmax: int, kmax: int, hmax: int,
                         n_ep: int):
    """Compiled lane-batched SOURCE-ROUTED runner whose WORKLOAD arrays
    are traced operands: one executable scores any generation of
    candidate schedules padded to the common shapes (M messages, dmax
    dep fan-in, kmax messages/endpoint, hmax path hops).

    This is the §10 lane contract pushed one level further: lanes here
    vary not just rate/seed/mask DATA but the schedule itself —
    size/dep/dst_r/msgs_by_ep/route_port/vc_base all become per-lane
    operands, while the routing tables stay closure constants (the
    search fixes one topology).  Per-lane results are bit-identical to
    single-lane `run_workload(routing='source')` calls on the same
    padded arrays (tests/test_policy.py).
    """
    key = ("policy-sweep", id(tables), cfg.static_key(),
           M, dmax, kmax, hmax)
    hit = _RUNNER_CACHE.get(key)
    if hit is not None and hit[0] is tables:
        return hit[2]

    assert cfg.routing == "source" and cfg.mode == "min"
    assert not cfg.telemetry.enabled, \
        "schedule search runs with telemetry off (per-lane traces of " \
        "operand-varying workloads are not supported)"
    core = SwitchCore(tables, cfg.to_sim_config())
    assert n_ep == core.n_ep
    Qs, eids = core.Qs, core.eids
    mid_mask = jnp.int32(MAX_JOB_MSGS - 1)

    def to_gid(field):
        # single-job id space: MSG field == global message id (the
        # mask only launders garbage in zero-initialised queue slots)
        return field & mid_mask

    def run_chunk(ops, carry, offset):
        c = core.bind_source_routes(ops["route_port"], ops["vc_base"],
                                    to_gid)
        size, dep = ops["size"], ops["dep"]
        dst_r_of_msg, msgs_by_ep = ops["dst_r"], ops["msgs_by_ep"]

        def fold(acc, g_net, g_src, pkt_net, pkt_src, cyc):
            flits_del, delivered = acc
            mn = jnp.where(g_net, to_gid(pk_msg(pkt_net)), M)
            ms = jnp.where(g_src, to_gid(pk_msg(pkt_src)), M)
            flits_del = flits_del.at[mn.reshape(-1)].add(1, mode="drop")
            flits_del = flits_del.at[ms].add(1, mode="drop")
            delivered = (delivered + g_net.sum().astype(jnp.int32)
                         + g_src.sum().astype(jnp.int32))
            return flits_del, delivered

        def step(carry, cycle):
            (nq_pkt, nq_count, sq_pkt, sq_count, admit,
             sent, flits_del, start_c, done_c, key, ts) = carry
            key, k_rt = jax.random.split(key)
            occ = c.occupancy(nq_count)

            done = flits_del >= size
            dep_ok = jnp.where(dep >= 0, done[jnp.maximum(dep, 0)],
                               True).all(axis=1)
            sendable = dep_ok & (sent < size) & (cycle >= admit[0])
            cand = (msgs_by_ep >= 0) & sendable[jnp.maximum(msgs_by_ep, 0)]
            has = cand.any(axis=1)
            # first sendable slot in the ROW ORDER of msgs_by_ep — the
            # entry-ordering knob the search permutes per lane
            slot = jnp.argmax(cand, axis=1)
            mpick = jnp.where(has, msgs_by_ep[eids, slot], 0)

            want = has & (sq_count < Qs)
            dst_r = dst_r_of_msg[mpick]
            inter, phase = c.route_decision(dst_r, occ, k_rt)
            new_pkt = pack_record(dst_r, inter, cycle,
                                  jnp.zeros((n_ep,), jnp.int32), phase,
                                  msg=mpick)
            sq_pkt, sq_count = c.inject(sq_pkt, sq_count, want, new_pkt)
            msel = jnp.where(want, mpick, M)
            sent = sent.at[msel].add(1, mode="drop")
            start_c = start_c.at[msel].min(cycle, mode="drop")

            (nq_pkt, nq_count, sq_pkt, sq_count,
             (flits_del, delivered), ts) = c.alloc(
                 nq_pkt, nq_count, sq_pkt, sq_count,
                 occ, cycle, fold, (flits_del, jnp.int32(0)),
                 tel_state=ts)

            now_done = flits_del >= size
            done_c = jnp.where(now_done & (done_c == BIG), cycle + 1,
                               done_c)
            n_done = now_done.astype(jnp.int32).sum()[None]     # [J=1]
            stats = (want.sum().astype(jnp.int32), delivered, n_done)
            return (nq_pkt, nq_count, sq_pkt, sq_count, admit,
                    sent, flits_del, start_c, done_c, key, ts), stats

        cycles = offset + jnp.arange(cfg.chunk, dtype=jnp.int32)
        return jax.lax.scan(step, carry, cycles)

    def init_carry(key0):
        return core.init_queues() + (
            jnp.zeros((1,), jnp.int32),                 # admit (cycle 0)
            jnp.zeros((M,), jnp.int32),                 # sent
            jnp.zeros((M,), jnp.int32),                 # flits_delivered
            jnp.full((M,), BIG, jnp.int32),             # start cycle
            jnp.full((M,), BIG, jnp.int32),             # done cycle
            key0,
            tel.init_state(cfg.telemetry, core))        # () — tel off

    ops_axes = {"size": 0, "dep": 0, "dst_r": 0, "msgs_by_ep": 0,
                "route_port": 0, "vc_base": 0}
    fn = (jax.jit(jax.vmap(run_chunk, in_axes=(ops_axes, 0, None)),
                  donate_argnums=(1,)), init_carry)
    _cache_put(_RUNNER_CACHE, key, (tables, None, fn))
    return fn


def _policy_operands(wl, M: int, dmax: int, kmax: int, hmax: int,
                     n_ep: int) -> dict:
    """One candidate's step operands, padded to the generation's common
    shapes.  Pad messages get size 0: 'done' from cycle one (0 >= 0)
    yet never sendable (sent < 0 is false), so they are inert and the
    all-done count M is lane-uniform."""
    m = wl.n_messages
    assert m <= M and wl.route_port.shape[1] <= hmax
    size = np.zeros(M, np.int32)
    size[:m] = wl.size
    dep = np.full((M, dmax), -1, np.int32)
    d = wl.dep_matrix()
    assert d.shape[1] <= dmax
    dep[:m, :d.shape[1]] = d
    dst_r = np.zeros(M, np.int32)
    dst_r[:m] = wl.dst_r_of_msg
    rp = np.full((M, hmax), -1, np.int32)
    rp[:m, :wl.route_port.shape[1]] = wl.route_port
    vb = np.zeros(M, np.int32)
    vb[:m] = wl.vc_base
    src_ep = wl.src_ep_of_msg
    mbe = np.full((n_ep, kmax), -1, np.int32)
    for e in range(n_ep):
        v = np.nonzero(src_ep == e)[0]
        assert len(v) <= kmax
        mbe[e, :len(v)] = v
    return {"size": size, "dep": dep, "dst_r": dst_r, "msgs_by_ep": mbe,
            "route_port": rp, "vc_base": vb}


def _sweep_run_policies(tables: SimTables, wls: Sequence[Workload],
                        cfg: Optional[WorkloadSimConfig] = None,
                        pad_to: Optional[tuple] = None) -> list:
    """Score L candidate schedules (lowered PolicyWorkloads) in ONE
    lane-batched source-routed run — the fitness evaluator behind
    `repro.sim.workloads.search` (exposed as
    `repro.sim.sweep.sweep_run_policies`).

    Candidates may differ in message count, chunking, dependency
    structure, paths, VC classes, per-endpoint ordering and placement:
    everything is padded to common shapes (`pad_to` = (M, dmax, kmax,
    hmax) pins them across generations so the whole search reuses one
    compiled executable) and varied per lane as traced operands.
    Returns one WorkloadResult per candidate, bit-identical to
    sequential `run_workload(routing='source')` calls.
    """
    cfg = cfg or WorkloadSimConfig(routing="source")
    assert tables.lanes == 1, \
        "policy sweeps vary the SCHEDULE per lane; topology is fixed"
    wls = list(wls)
    assert wls, "empty candidate list"
    n_ep = tables.n_endpoints
    for w in wls:
        if getattr(w, "route_port", None) is None:
            raise ValueError(f"{w.name!r}: candidates must be lowered "
                             f"PolicyWorkloads")
        w.dst_r_of_msg = tables.ep_router[
            w.ep_of_rank[w.dst]].astype(np.int32)
        w.src_ep_of_msg = w.ep_of_rank[w.src].astype(np.int32)

    need = (max(w.n_messages for w in wls),
            max(w.dep_matrix().shape[1] for w in wls),
            max(int(np.bincount(w.src_ep_of_msg,
                                minlength=n_ep).max()) for w in wls),
            max(w.route_port.shape[1] for w in wls))
    if pad_to is None:
        pad_to = need
    assert all(p >= n for p, n in zip(pad_to, need)), (pad_to, need)
    M, dmax, kmax, hmax = pad_to

    fn, init_carry = _policy_sweep_runner(tables, cfg, M, dmax, kmax,
                                          hmax, n_ep)
    ops_l = [_policy_operands(w, M, dmax, kmax, hmax, n_ep) for w in wls]
    ops = {k: jnp.asarray(np.stack([o[k] for o in ops_l]))
           for k in ops_l[0]}
    lanes0 = [init_carry(jax.random.PRNGKey(cfg.seed)) for _ in wls]
    carry = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *lanes0)

    L = len(wls)
    per_cycle_dlv = []
    done_lane = np.zeros(L, dtype=bool)
    t = 0
    while t < cfg.max_cycles:
        carry, (inj, dlv, n_done) = fn(ops, carry, jnp.int32(t))
        per_cycle_dlv.append(np.asarray(dlv, dtype=np.int64))
        t += cfg.chunk
        done_lane = np.asarray(n_done)[:, -1, 0] == M
        if done_lane.all():
            break

    (_, _, _, _, _, sent, flits_del, start_c, done_c, _, _) = carry
    dlv_all = np.concatenate(per_cycle_dlv, axis=1)
    out = []
    for i, w in enumerate(wls):
        m = w.n_messages
        out.append(_workload_result(
            w, cfg, w.ep_of_rank,
            (sent[i][:m], flits_del[i][:m], start_c[i][:m],
             done_c[i][:m]),
            dlv_all[i], bool(done_lane[i]), t))
    return out
