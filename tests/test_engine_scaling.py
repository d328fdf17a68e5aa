"""Paper-scale engine tests (DESIGN.md §9): ref-vs-pallas bit
equivalence, dtype-packing overflow guards, and the repro.bench
regression harness."""

import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from conftest import cached_slimfly
from repro.bench import (bench_callable, check_regression, load_bench,
                         write_bench)
from repro.core.resiliency import failure_edge_sample
from repro.kernels import alloc_rounds, ugal_select
from repro.kernels.alloc import alloc_rounds_pallas, ugal_select_pallas
from repro.kernels.ref import KSHIFT, alloc_rounds_ref, ugal_select_ref
from repro.sim import SimConfig, SimTables, make_traffic, simulate
from repro.sim.packed import (HOPS_MAX, MAX_MSGS, MAX_ROUTERS,
                              bump_hops_word, pack_record, unpack_record)


# ---------------------------------------------------------- equivalence --
def _assert_same_result(ra, rb):
    assert ra.delivered == rb.delivered
    assert ra.injected == rb.injected
    assert ra.dropped_at_source == rb.dropped_at_source
    assert ra.avg_latency == rb.avg_latency
    np.testing.assert_array_equal(ra.per_cycle_delivered,
                                  rb.per_cycle_delivered)
    np.testing.assert_array_equal(ra.per_cycle_in_flight,
                                  rb.per_cycle_in_flight)


def _run_both(tables, traffic, mode, cycles=60):
    cfg = SimConfig(injection_rate=0.35, cycles=cycles, warmup=10,
                    mode=mode, seed=3, kernel_path="ref")
    r_ref = simulate(tables, traffic, cfg)
    r_pal = simulate(tables, traffic,
                     dataclasses.replace(cfg, kernel_path="pallas"))
    _assert_same_result(r_ref, r_pal)
    assert r_ref.delivered > 0
    return r_ref


@pytest.mark.parametrize("mode", ["min", "ugal_l"])
def test_pallas_matches_ref_q5_healthy(mode):
    tables = SimTables.build(cached_slimfly(5))
    _run_both(tables, make_traffic(tables, "uniform"), mode)


@pytest.mark.parametrize("mode", ["min", "ugal_l"])
def test_pallas_matches_ref_q5_degraded(mode):
    """10% failed links (routes re-converged): the engine's dead-port
    handling must be identical on both kernel paths."""
    topo = cached_slimfly(5)
    fe = failure_edge_sample(topo, 0.10, np.random.default_rng(1))
    tables = SimTables.build(topo, failed_edges=fe)
    _run_both(tables, make_traffic(tables, "uniform"), mode)


def test_pallas_matches_ref_q7():
    tables = SimTables.build(cached_slimfly(7))
    _run_both(tables, make_traffic(tables, "uniform"), "ugal_l",
              cycles=40)


def test_alloc_rounds_kernel_matches_ref():
    """Unit-level: random request tensors, including a router count that
    exercises the pallas row padding."""
    rng = np.random.default_rng(0)
    N, P, V, PE, W = 11, 5, 2, 3, 4
    PV = P * V
    NQ, R = N * PV, N * PV + N * PE
    shapes = dict(
        out_net=rng.integers(-1, P, (N, PV, W)),
        ej_net=rng.integers(0, 2, (N, PV, W)),
        space_net=rng.integers(0, 2, (N, PV, W)),
        count_net=rng.integers(0, 5, (N, PV)),
        out_src=rng.integers(-1, P, (N, PE, W)),
        ej_src=rng.integers(0, 2, (N, PE, W)),
        space_src=rng.integers(0, 2, (N, PE, W)),
        count_src=rng.integers(0, 5, (N, PE)),
    )
    args = {k: jnp.asarray(v.astype(np.int32)) for k, v in shapes.items()}
    epr = jnp.arange(N, dtype=jnp.int32)
    kw = dict(W=W, P=P, V=V, PE=PE, p_budget=PE, NQ=NQ, R=R)
    ref_out = alloc_rounds_ref(jnp.int32(7), **args, epr_index=epr, **kw)
    pal_out = alloc_rounds_pallas(jnp.int32(7), *args.values(), epr,
                                  **kw)
    for a, b in zip(ref_out, pal_out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("ugal_g", [False, True])
def test_ugal_select_kernel_matches_ref(ugal_g):
    rng = np.random.default_rng(1)
    E, C = 700, 4
    unreach, big = 1 << 14, 1 << 30
    len_min = jnp.asarray(
        rng.choice([1, 2, unreach], E).astype(np.int32))
    len_val = jnp.asarray(
        rng.choice([2, 3, 4, unreach], (E, C)).astype(np.int32))
    occ_min = jnp.asarray(rng.integers(0, 1 << 20, E).astype(np.int32))
    occ_val = jnp.asarray(
        rng.integers(0, 1 << 20, (E, C)).astype(np.int32))
    a = ugal_select_ref(len_min, len_val, occ_min, occ_val,
                        ugal_g=ugal_g, unreach=unreach, big=big)
    b = ugal_select_pallas(len_min, len_val, occ_min, occ_val,
                           ugal_g=ugal_g, unreach=unreach, big=big)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_golden_outcomes_q5():
    """The packed-dtype / shift-FIFO / kernel refactor must not change
    any simulated outcome: these numbers pin the engine's results.

    Recorded under jax 0.9.0 (the version pinned in requirements.txt);
    the exact integers depend on jax.random's sampler bits, so a jax
    upgrade with NO engine change may shift them — re-derive them from
    the new jax then, and check the ref==pallas equivalence tests above,
    which hold on any version."""
    tables = SimTables.build(cached_slimfly(5))
    uni = make_traffic(tables, "uniform")
    r = simulate(tables, uni, SimConfig(
        injection_rate=0.35, cycles=150, warmup=40, mode="min", seed=7))
    assert r.delivered == 10391 and r.injected == 10562
    assert round(r.avg_latency, 9) == 3.407100285
    r = simulate(tables, uni, SimConfig(
        injection_rate=0.35, cycles=150, warmup=40, mode="ugal_l", seed=7))
    assert r.delivered == 10244 and r.injected == 10562
    assert round(r.avg_latency, 9) == 5.074401665


# ------------------------------------------------------ overflow guards --
def test_packed_record_boundaries():
    """Round-trip at the field-budget edges (q=25-scale router ids, max
    hops/phase/msg, near-int32 inject cycles)."""
    dst = jnp.int32(1249)
    inter = jnp.int32(MAX_ROUTERS - 1)
    time = jnp.int32(2_000_000_000)
    pkt = pack_record(dst, inter, time, jnp.int32(HOPS_MAX), jnp.int32(1),
                      msg=jnp.int32(MAX_MSGS - 1))
    got = np.asarray(unpack_record(pkt, 6))
    assert got.tolist() == [1249, MAX_ROUTERS - 1, 2_000_000_000,
                            HOPS_MAX, 1, MAX_MSGS - 1]
    assert (np.asarray(pkt) >= 0).all()          # no sign-bit corruption


def test_hops_saturate_not_wrap():
    """hops pins at HOPS_MAX instead of carrying into the phase bit."""
    pkt = pack_record(jnp.int32(3), jnp.int32(4), jnp.int32(0),
                      jnp.int32(HOPS_MAX), jnp.int32(0),
                      msg=jnp.int32(12345))
    w2 = bump_hops_word(pkt[..., 2], jnp.int32(0))
    got = np.asarray(unpack_record(pkt.at[..., 2].set(w2), 6))
    assert got[3] == HOPS_MAX                    # saturated
    assert got[4] == 0 and got[5] == 12345       # neighbors untouched


def test_alloc_priority_fits_int32_at_paper_scale():
    """The seed's rot*R+qidx priority wrapped int32 at q=17
    (R=65314); the replacement rot/KSHIFT packing must keep every
    intermediate below 2^31 up to q=25 and closed-loop max_cycles."""
    from repro.core import slimfly_params
    max_cycle = 200_000
    for q in (17, 25):
        par = slimfly_params(q)
        PV = par["kprime"] * 4
        NQ = par["n_routers"] * PV
        R = NQ + par["n_endpoints"]
        K = PV + par["p"]
        worst_rot_arg = (R - 1) + max_cycle * 7919 + 3 * 131
        assert worst_rot_arg < 2**31, (q, worst_rot_arg)
        assert (R - 1) * KSHIFT + K < 2**31, (q, R)
        assert K < KSHIFT, (q, K)
        # and the seed formula really did overflow — the regression this
        # guards against is real, not hypothetical
        if q == 17:
            assert (R - 1) * R + (R - 1) >= 2**31


def test_q17_saturated_sim_no_wraparound():
    """Acceptance-scale run: q=17 (N=578, ~7.5k endpoints) at a
    saturating injection rate pushes queue occupancy against its caps;
    conservation must hold at every cycle prefix and all counters stay
    in range."""
    tables = SimTables.build(cached_slimfly(17))
    uni = make_traffic(tables, "uniform")
    cfg = SimConfig(injection_rate=1.0, cycles=40, warmup=0,
                    mode="ugal_l", seed=2)
    r = simulate(tables, uni, cfg)
    cum_inj = np.cumsum(r.per_cycle_injected)
    cum_dlv = np.cumsum(r.per_cycle_delivered)
    np.testing.assert_array_equal(cum_inj,
                                  cum_dlv + r.per_cycle_in_flight)
    assert (r.per_cycle_in_flight >= 0).all()
    cap = (tables.n_routers * tables.P * cfg.vcs * cfg.q_net
           + tables.n_endpoints * cfg.q_src)
    assert (r.per_cycle_in_flight <= cap).all()
    assert r.delivered > 0 and r.avg_latency > 0


# ------------------------------------------------- donation / peak memory --
def test_donated_carry_stays_donatable():
    """The scan carry is donated (jax.jit donate_argnums) and must keep
    an aliasable target: if aliasing breaks, jax emits the 'Some
    donated buffers were not usable' UserWarning again."""
    import warnings

    tables = SimTables.build(cached_slimfly(5))
    tr = make_traffic(tables, "uniform")
    cfg = SimConfig(injection_rate=0.3, cycles=30, warmup=0, mode="min",
                    seed=11)
    simulate(tables, tr, cfg)                    # compile outside the net
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        r = simulate(tables, tr, dataclasses.replace(cfg, seed=12))
    assert r.delivered > 0


def test_steady_state_memory_bounded():
    """Steady-state re-execution of the compiled scan must not grow the
    process high-water mark by more than a loose cap (a donation or
    buffer-retention regression shows up as per-call growth on the
    order of the full queue state x cycles)."""
    from repro.bench import peak_memory_bytes

    tables = SimTables.build(cached_slimfly(7))
    tr = make_traffic(tables, "uniform")
    state = {"seed": 20}

    def call():
        cfg = SimConfig(injection_rate=0.3, cycles=60, warmup=0,
                        mode="min", seed=state["seed"])
        state["seed"] += 1
        simulate(tables, tr, cfg)

    call()                                       # compile + set the HWM
    peak, probe = peak_memory_bytes(call, cheap=True)
    assert probe in ("rss", "rss-total", "none")
    if probe == "rss":                           # the HWM moved: bound it
        assert peak < 256 * 1024 * 1024, peak


# --------------------------------------------------------- bench harness --
def test_rss_probe_never_null():
    """The cheap RSS probe (paper-scale entries) always yields a
    number on Linux — peak_mem_bytes must not be null at q=17 again."""
    from repro.bench import peak_memory_bytes, rss_hwm_bytes

    assert rss_hwm_bytes() is None or rss_hwm_bytes() > 0

    peak, probe = peak_memory_bytes(lambda: np.zeros(1 << 22), cheap=True)
    if probe != "none":                          # /proc or getrusage found
        assert peak is not None and peak > 0
        assert probe in ("rss", "rss-total")

    e = bench_callable("toy/rss", lambda: None, repeats=1,
                       measure_memory="rss")
    assert e.mem_probe in ("rss", "rss-total", "none")
    if e.mem_probe != "none":
        assert e.peak_mem_bytes is not None


def test_enable_compilation_cache_states(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR is honoured without the code setting a
    directory; unset, the cache goes to the fixed in-checkout path.
    Cold on an empty dir, warm once it holds serialized executables."""
    import os

    import jax

    from repro.bench import enable_compilation_cache
    from repro.bench.harness import DEFAULT_CACHE_DIR

    before = jax.config.jax_compilation_cache_dir
    cache = tmp_path / "jc"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    try:
        state, d = enable_compilation_cache()
        assert state == "cold" and d == str(cache)
        assert jax.config.jax_compilation_cache_dir == before
        cache.mkdir()
        (cache / "jit_foo-0123-cache").write_bytes(b"x")
        assert enable_compilation_cache() == ("warm", str(cache))

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        _, d = enable_compilation_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert d == DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == DEFAULT_CACHE_DIR
    finally:
        # don't leave the suite persisting executables
        jax.config.update("jax_compilation_cache_dir", before)


def test_bench_extra_metrics_roundtrip(tmp_path):
    """extra_metrics (sweep_points_per_sec & co) serialize beside the
    standard fields and are addressable by check_regression."""
    from repro.bench import BenchEntry

    e = BenchEntry(name="sweep/q0/t", wall_s=2.0, wall_mean_s=2.0,
                   compile_s=1.0, repeats=1, cycles=100,
                   meta={"lanes": 5},
                   extra_metrics={"sweep_points_per_sec": 2.5})
    path = tmp_path / "BENCH_x.json"
    write_bench(str(path), "engine_scaling", [e])
    doc = load_bench(str(path))
    ent = doc["entries"]["sweep/q0/t"]
    assert ent["sweep_points_per_sec"] == 2.5
    ok, msg = check_regression(doc, "sweep/q0/t", "sweep_points_per_sec",
                               1.0, factor=2.0, higher_is_better=True)
    assert not ok and "REGRESSION" in msg
    ok, _ = check_regression(doc, "sweep/q0/t", "sweep_points_per_sec",
                             1.5, factor=2.0, higher_is_better=True)
    assert ok


def test_bench_harness_roundtrip(tmp_path):
    calls = []

    def fn():
        calls.append(1)

    e = bench_callable("toy/q0", fn, repeats=3, cycles=1000,
                       measure_memory=True, meta={"q": 0})
    assert e.repeats == 3 and len(calls) >= 4      # warmup + repeats (+mem)
    assert e.cycles_per_sec is not None and e.cycles_per_sec > 0
    # "none" is legitimate on device-stats backends: a pure-Python fn
    # moves no device memory, and the probe refuses misleading zeros
    assert e.mem_probe in ("device", "tracemalloc", "tracemalloc-nested",
                           "none")

    path = tmp_path / "BENCH_toy.json"
    doc = write_bench(str(path), "toy", [e], extra_meta={"note": "t"})
    loaded = load_bench(str(path))
    assert loaded == doc
    ent = loaded["entries"]["toy/q0"]
    assert ent["cycles"] == 1000 and ent["meta"]["q"] == 0
    assert ent["cycles_per_sec"] == pytest.approx(e.cycles_per_sec)


def test_check_regression_gate():
    baseline = {"schema": 1, "entries": {
        "engine/q5/ugal_l": {"cycles_per_sec": 100.0}}}
    ok, _ = check_regression(baseline, "engine/q5/ugal_l",
                             "cycles_per_sec", 60.0, factor=2.0)
    assert ok                                       # within 2x
    ok, msg = check_regression(baseline, "engine/q5/ugal_l",
                               "cycles_per_sec", 40.0, factor=2.0)
    assert not ok and "REGRESSION" in msg           # > 2x slower
    ok, msg = check_regression(baseline, "engine/q99/ugal_l",
                               "cycles_per_sec", 1.0, factor=2.0)
    assert ok and "no baseline" in msg              # new entry passes
    # lower-is-better metrics flip the comparison
    base2 = {"schema": 1, "entries": {"e": {"wall_s": 1.0}}}
    ok, _ = check_regression(base2, "e", "wall_s", 3.0, factor=2.0,
                             higher_is_better=False)
    assert not ok
