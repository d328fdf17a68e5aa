"""Closed-loop workload engine (DESIGN.md §7): IR builders, rank
placement, deadlock freedom of the routes the engine uses, DAG
conservation (every message delivered exactly once, finite makespan),
determinism, and the FabricModel cross-validation the acceptance
criterion pins at 2x."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import build_slimfly
from repro.core.layout import make_layout
from repro.core.routing import build_routing, is_deadlock_free, valiant_path
from repro.sim import SimTables
from repro.sim.workloads import (
    PLACEMENTS,
    WorkloadSimConfig,
    all_to_all,
    fabric_crosscheck,
    graph_scatter,
    place_ranks,
    recursive_doubling_all_reduce,
    ring_all_reduce,
    run_workload,
    stencil,
    summarize,
)
from repro.sim.workloads.closed_loop import (
    WorkloadResult,
    _build_space,
    _pick,
    _pick_rows,
)
from repro.sim.workloads.jobs import Job, place_jobs

RING_K, RING_CHUNK = 16, 8


@pytest.fixture(scope="module")
def sf5_tables():
    return SimTables.build(build_slimfly(5))


@pytest.fixture(scope="module")
def ring_run(sf5_tables):
    """One ring all-reduce JCT run shared by the sim-level tests."""
    wl = ring_all_reduce(RING_K, RING_CHUNK)
    cfg = WorkloadSimConfig(mode="min", chunk=128, seed=0)
    return wl, cfg, run_workload(sf5_tables, wl, cfg)


# ---------------------------------------------------------------------------
# IR builders
# ---------------------------------------------------------------------------

def _assert_acyclic_kahn(wl):
    """Independent acyclicity check (Kahn), not the id-order shortcut."""
    m = wl.n_messages
    indeg = np.array([len(d) for d in wl.deps])
    succs = [[] for _ in range(m)]
    for i, d in enumerate(wl.deps):
        for j in d:
            succs[j].append(i)
    stack = list(np.nonzero(indeg == 0)[0])
    seen = 0
    while stack:
        v = stack.pop()
        seen += 1
        for w in succs[v]:
            indeg[w] -= 1
            if indeg[w] == 0:
                stack.append(w)
    assert seen == m, "dependency cycle"


@pytest.mark.parametrize("wl_fn", [
    lambda: ring_all_reduce(8, 4),
    lambda: recursive_doubling_all_reduce(8, 16),
    lambda: all_to_all(6, 3),
    lambda: stencil((4, 4), 8, iters=3),
    lambda: stencil((3, 3, 2), 8, iters=2),
    lambda: graph_scatter(24, 8, iters=2, seed=1),
])
def test_builders_valid_dags(wl_fn):
    wl = wl_fn()
    wl.validate()
    _assert_acyclic_kahn(wl)
    dm = wl.dep_matrix()
    assert dm.shape[0] == wl.n_messages and dm.shape[1] >= 1
    assert (wl.size > 0).all() and (wl.src != wl.dst).all()


def test_ring_all_reduce_shape():
    k = 8
    wl = ring_all_reduce(k, 4)
    assert wl.n_messages == 2 * (k - 1) * k
    # each rank sends exactly 2(k-1) chunks; phases split at step k-1
    counts = np.bincount(wl.src, minlength=k)
    assert (counts == 2 * (k - 1)).all()
    assert set(np.unique(wl.phase)) == {0, 1}


def test_graph_scatter_degree_skew():
    wl = graph_scatter(64, 4, iters=1, skew=1.3, seed=3)
    deg = np.bincount(wl.src, minlength=64)
    # Zipf out-degrees: some fan-out well above the median hub-style
    assert deg.max() >= 4 * max(1, int(np.median(deg)))
    assert deg.min() >= 1


# ---------------------------------------------------------------------------
# placement
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", PLACEMENTS)
def test_placement_injective(sf5_tables, scheme):
    eps = place_ranks(sf5_tables, 48, scheme, seed=2)
    assert len(np.unique(eps)) == 48
    assert eps.min() >= 0 and eps.max() < sf5_tables.n_endpoints


def test_placement_blocked_groups_by_router(sf5_tables):
    p = sf5_tables.p
    eps = place_ranks(sf5_tables, 4 * p, "blocked")
    routers = sf5_tables.ep_router[eps]
    # consecutive p-blocks of ranks land on a single router each
    for b in range(4):
        assert len(set(routers[b * p:(b + 1) * p])) == 1
    assert len(set(routers)) == 4


def test_placement_spread_distinct_routers(sf5_tables):
    n_epr = sf5_tables.n_endpoints // sf5_tables.p
    eps = place_ranks(sf5_tables, n_epr, "spread")
    assert len(set(sf5_tables.ep_router[eps])) == n_epr


@functools.lru_cache(maxsize=None)
def _prop_tables(q):
    # q=7 (N=98 routers) is expensive to build; share across draws
    return SimTables.build(build_slimfly(q))


@settings(max_examples=20, deadline=None)
@given(q=st.sampled_from([5, 7]), scheme=st.sampled_from(PLACEMENTS),
       full=st.sampled_from([False, True]), seed=st.integers(0, 7))
def test_placement_property_injective_convention(q, scheme, full, seed):
    """Property (satellite): every scheme returns an injective map into
    the p-endpoints-per-router numbering, for n_ranks both < and ==
    n_endpoints; n_ranks == n_endpoints is a permutation of the fabric
    (the total order the job layer slices)."""
    tables = _prop_tables(q)
    n_ep, p = tables.n_endpoints, tables.p
    n_ranks = n_ep if full else 1 + (seed * 9173 + q) % (n_ep - 1)
    eps = place_ranks(tables, n_ranks, scheme, seed=seed)
    assert eps.shape == (n_ranks,) and eps.dtype == np.int32
    assert len(np.unique(eps)) == n_ranks                 # injective
    assert eps.min() >= 0 and eps.max() < n_ep
    # endpoint numbering convention: endpoint e lives on router
    # ep_router[e], p consecutive endpoint ids per router
    routers = tables.ep_router[eps]
    assert np.array_equal(routers, tables.ep_router[::p][eps // p])
    if full:
        assert np.array_equal(np.sort(eps), np.arange(n_ep))
    if scheme == "blocked":
        # rack-ordering against make_layout: rack ids are
        # non-decreasing along rank order, and every complete p-block
        # of consecutive ranks shares one router
        racks = make_layout(tables.topo).rack_of[routers]
        assert (np.diff(racks) >= 0).all()
        nb = n_ranks // p
        if nb:
            blocks = routers[:nb * p].reshape(nb, p)
            assert (blocks == blocks[:, :1]).all()


def test_placement_random_is_seed_sensitive(sf5_tables):
    """Premise of the `_sweep_run_workload` guard (tested end-to-end in
    tests/test_sweep.py): `random` placement varies with the seed, so
    per-lane seeds cannot share one compiled placement silently."""
    a = place_ranks(sf5_tables, 32, "random", seed=0)
    b = place_ranks(sf5_tables, 32, "random", seed=1)
    assert not np.array_equal(a, b)


# ---------------------------------------------------------------------------
# deadlock freedom of the routes the engine uses (satellite)
# ---------------------------------------------------------------------------

def test_workload_routes_deadlock_free(sf5_tables):
    """MIN and VAL path sets for the messages the engine injects on SF
    q=5 keep the hop-indexed-VC channel dependency graph acyclic."""
    rt = build_routing(sf5_tables.topo, use_pallas=False)
    n = sf5_tables.n_routers
    rng = np.random.default_rng(0)

    pairs = set()
    for wl, scheme in [(ring_all_reduce(RING_K, RING_CHUNK), "spread"),
                       (graph_scatter(24, 4, iters=1, seed=2), "random")]:
        eps = place_ranks(sf5_tables, wl.n_ranks, scheme, seed=1)
        src_r = sf5_tables.ep_router[eps[wl.src]]
        dst_r = sf5_tables.ep_router[eps[wl.dst]]
        pairs |= set(zip(src_r.tolist(), dst_r.tolist()))

    paths = []
    for s, d in sorted(pairs):
        if s == d:
            continue
        paths.append(rt.min_path(s, d))
        # VAL through sampled intermediates, as route_decision draws them
        for _ in range(3):
            i = int(rng.integers(n))
            while i in (s, d):
                i = (i + 1) % n
            paths.append(valiant_path(rt, s, d, i))
    assert len(paths) > 4 * RING_K
    assert is_deadlock_free(paths, n)


# ---------------------------------------------------------------------------
# closed-loop engine invariants
# ---------------------------------------------------------------------------

def test_dag_conservation_and_finite_makespan(ring_run):
    """Every DAG message injected is delivered exactly once (per-flit
    counts match message sizes on both ends) and the makespan is
    finite."""
    wl, _, r = ring_run
    assert r.completed
    assert np.isfinite(r.makespan) and r.makespan > 0
    np.testing.assert_array_equal(r.msg_sent, wl.size)
    np.testing.assert_array_equal(r.msg_delivered, wl.size)
    assert r.flits_delivered == wl.total_flits
    assert int(r.per_cycle_delivered.sum()) == wl.total_flits
    # causality: nothing completes before it starts, deps before users
    assert (r.msg_start >= 0).all() and (r.msg_done > r.msg_start).all()
    dm = wl.dep_matrix()
    for mid in range(wl.n_messages):
        for d in dm[mid]:
            if d >= 0:
                assert r.msg_done[d] <= r.msg_start[mid] + 1


def test_dependency_serialization_orders_phases(ring_run):
    """Ring steps are dependency-serialized: mean completion time of
    all-gather-phase messages exceeds the reduce-scatter phase's."""
    wl, _, r = ring_run
    done = r.msg_done.astype(float)
    assert done[wl.phase == 1].mean() > done[wl.phase == 0].mean()


def test_closed_loop_deterministic(sf5_tables, ring_run):
    wl, cfg, r1 = ring_run
    r2 = run_workload(sf5_tables, wl, cfg)
    assert r1.makespan == r2.makespan
    np.testing.assert_array_equal(r1.msg_done, r2.msg_done)


def _lowest_sendable(src_ep, sendable, n_ep):
    """Each endpoint's lowest-id sendable message, by a plain loop."""
    has = np.zeros(n_ep, bool)
    mpick = np.zeros(n_ep, np.int32)
    for m in range(len(src_ep) - 1, -1, -1):
        if sendable[m]:
            has[src_ep[m]], mpick[src_ep[m]] = True, m
    return has, mpick


def _pick_space(tables, case):
    if case == "ring_8_of_150":
        wls = (ring_all_reduce(8, 2),)
        eps = (place_ranks(tables, 8, "spread"),)
    elif case == "every_endpoint":
        wls = (ring_all_reduce(tables.n_endpoints, 1),)
        eps = (place_ranks(tables, tables.n_endpoints, "linear"),)
    elif case == "two_jobs":
        jobs = [Job("ring", ring_all_reduce(8, 2)),
                Job("a2a", all_to_all(6, 1), arrival=3)]
        wls = tuple(j.workload for j in jobs)
        eps = tuple(place_jobs(tables, jobs, "spread"))
    else:                                       # ragged rows
        wls = (graph_scatter(24, 1, iters=2, seed=3),)
        eps = (place_ranks(tables, 24, "random", seed=4),)
    return wls, tuple(np.asarray(e, np.int32) for e in eps)


@pytest.mark.parametrize("case", ["ring_8_of_150", "every_endpoint",
                                  "two_jobs", "graph_scatter"])
def test_pick_is_lowest_sendable_message(sf5_tables, case):
    """The closed loop's pick scans only the endpoints that own a
    message, and answers as a dense scan over every endpoint would."""
    n_ep = sf5_tables.n_endpoints
    wls, eps = _pick_space(sf5_tables, case)
    space = _build_space(wls, eps)
    msgs, row = _pick_rows(space.src_ep, n_ep)

    senders = np.unique(np.concatenate(
        [ep[wl.src] for wl, ep in zip(wls, eps)]))
    n_act, kmax = msgs.shape
    assert n_act == len(senders)
    assert kmax == np.bincount(space.src_ep).max()
    if case == "every_endpoint":
        assert n_act == n_ep
    else:
        assert n_act < n_ep
    np.testing.assert_array_equal(np.flatnonzero(row < n_act), senders)
    if case == "graph_scatter":
        assert np.bincount(space.src_ep)[senders].min() < kmax

    run = jax.jit(lambda s: _pick(jnp.asarray(msgs), jnp.asarray(row), s))
    rng = np.random.default_rng(0)
    M = space.n_messages
    masks = [np.zeros(M, bool), np.ones(M, bool)] + [
        rng.random(M) < p for p in (0.02, 0.3, 0.7)]
    for sendable in masks:
        has, mpick = run(jnp.asarray(sendable))
        want_has, want_pick = _lowest_sendable(space.src_ep, sendable, n_ep)
        np.testing.assert_array_equal(np.asarray(has), want_has)
        np.testing.assert_array_equal(np.asarray(mpick), want_pick)


# ---------------------------------------------------------------------------
# analytic cross-validation (acceptance criterion: within 2x)
# ---------------------------------------------------------------------------

def test_ring_all_reduce_matches_fabric_model(sf5_tables, ring_run):
    """Cycle-sim ring all-reduce makespan on SF q=5 agrees with the
    cycle-calibrated FabricModel ring estimate within 2x."""
    wl, _, r = ring_run
    cc = fabric_crosscheck(sf5_tables.topo, "all_reduce",
                           RING_K * RING_CHUNK, r.ep_of_rank, r.makespan)
    assert 0.5 <= cc["ratio"] <= 2.0, cc


# ---------------------------------------------------------------------------
# accounting regressions (PR 6 satellites)
# ---------------------------------------------------------------------------

def test_cycles_run_trimmed_to_makespan(ring_run):
    """Regression: completed runs used to report cycles_run rounded up
    to the chunk boundary, with up to chunk-1 trailing post-completion
    entries in per_cycle_delivered.  Both must be trimmed to the true
    makespan.  The fixture's makespan is deliberately NOT a multiple of
    cfg.chunk, so the pre-fix rounding is observable."""
    wl, cfg, r = ring_run
    assert r.completed
    assert int(r.makespan) % cfg.chunk != 0, \
        "fixture no longer exercises the rounding path; pick a new chunk"
    assert r.cycles_run == int(r.makespan)
    assert len(r.per_cycle_delivered) == r.cycles_run
    assert int(r.per_cycle_delivered.sum()) == wl.total_flits


def test_incomplete_run_reports_partial_bw(sf5_tables):
    """Regression: achieved_bw returned 0.0 whenever makespan was inf,
    so timed-out degraded runs plotted as zero bandwidth.  Incomplete
    runs must report delivered/cycles_run, and the report table must
    mark the distinction."""
    wl = ring_all_reduce(RING_K, RING_CHUNK)
    cfg = WorkloadSimConfig(mode="min", chunk=32, max_cycles=32, seed=0)
    r = run_workload(sf5_tables, wl, cfg)
    assert not r.completed and not np.isfinite(r.makespan)
    assert r.cycles_run == 32                    # no trimming: ran out
    assert r.flits_delivered > 0
    assert r.achieved_bw == pytest.approx(r.flits_delivered / 32)
    table = summarize(wl, r).table()
    assert "INCOMPLETE" in table
    assert "run did not complete" in table


def _fake_result(wl, msg_start, msg_done):
    return WorkloadResult(
        name=wl.name, mode="min", placement="linear", n_ranks=wl.n_ranks,
        n_messages=wl.n_messages, completed=True,
        makespan=float(msg_done.max()), cycles_run=int(msg_done.max()),
        flits_injected=wl.total_flits, flits_delivered=wl.total_flits,
        msg_size=wl.size, msg_phase=wl.phase,
        msg_sent=wl.size.copy(), msg_delivered=wl.size.copy(),
        msg_start=msg_start, msg_done=msg_done,
        per_cycle_delivered=np.zeros(int(msg_done.max()), np.int64),
        ep_of_rank=np.arange(wl.n_ranks, dtype=np.int32))


def test_summarize_shared_hist_edges(ring_run):
    """Regression: per-phase auto histogram ranges made hist_edges
    differ across phases (cross-phase comparison meaningless); every
    phase must share one set of edges spanning the whole run.

    The synthetic result gives the two ring phases DISJOINT latency
    ranges (phase 0 constant at 5, phase 1 spread over [2, 40]), so the
    pre-fix per-phase auto ranges are observably different."""
    wl = ring_all_reduce(4, 2)                   # 2 phases, 24 messages
    m = wl.n_messages
    start = np.arange(m, dtype=np.int64) + 1
    lat = np.where(wl.phase == 0, 5,
                   2 + (38 * np.arange(m)) // max(m - 1, 1))
    r = _fake_result(wl, start, start + lat)
    rep = summarize(wl, r)
    assert len(rep.phases) == 2
    edges0 = rep.phases[0].hist_edges
    assert edges0[0] == pytest.approx(lat.min())
    assert edges0[-1] == pytest.approx(lat.max())
    for ph in rep.phases[1:]:
        np.testing.assert_array_equal(ph.hist_edges, edges0)
    for ph in rep.phases:
        assert int(ph.hist_counts.sum()) == ph.n_completed

    # end-to-end on a real run: still one shared set of edges
    wl2, _, r2 = ring_run
    rep2 = summarize(wl2, r2)
    for ph in rep2.phases[1:]:
        np.testing.assert_array_equal(ph.hist_edges,
                                      rep2.phases[0].hist_edges)


def test_summarize_constant_latency_guard():
    """When EVERY completed latency is equal, the shared lo==hi range
    must widen instead of collapsing to zero-width edges."""
    wl = all_to_all(2, 4)                        # 2 messages, 4 flits
    m = wl.n_messages
    start = np.full(m, 5, dtype=np.int64)
    r = _fake_result(wl, start, start + 7)
    rep = summarize(wl, r)
    for ph in rep.phases:
        edges = ph.hist_edges
        assert np.isfinite(edges).all() and edges[0] < edges[-1]
        assert int(ph.hist_counts.sum()) == ph.n_completed
