"""In-scan telemetry (DESIGN.md §12): telemetry-off bit-exactness vs
the golden-pinned configs, counters-ON core-result invariance (data-only
contract), counter conservation on healthy and degraded fabrics,
per-lane sweep counters, trace ring semantics, sampling determinism,
the export layer's JSON, and the `SimResult.saturated` q_src fix."""

import dataclasses
import json

import numpy as np
import pytest

from conftest import cached_slimfly
from repro.core.resiliency import failure_edge_sample
from repro.sim import (SimConfig, SimTables, TelemetryConfig, make_traffic,
                       simulate, sweep_simulate)
from repro.sim.engine import SimResult
from repro.sim.telemetry import export, sampled_fids
from repro.sim.telemetry.trace import KIND_EJECT, KIND_HOP, KIND_INJECT
from repro.sim.workloads import (WorkloadSimConfig, ring_all_reduce,
                                 run_workload)

_FULL_TRACE = TelemetryConfig(counters=True, trace=True,
                              trace_sample_shift=0, trace_capacity=1 << 14)


@pytest.fixture(scope="module")
def sf5_tables():
    return SimTables.build(cached_slimfly(5))


def _conserve(r):
    """The drained-run conservation identities (counters.py docstring).
    `r` is a completed WorkloadResult with counters on."""
    cs = r.telemetry.counters
    chan, ej, grants = (int(cs.chan_flits.sum()), int(cs.ej_count.sum()),
                        int(cs.alloc_grant.sum()))
    assert ej == r.flits_delivered
    assert chan == int(cs.ej_hops_sum.sum())
    assert grants == chan + ej
    # every delivered flit was injected exactly once and made a
    # MIN-or-VAL route decision at injection
    assert int(cs.route_min.sum() + cs.route_val.sum()) == r.flits_delivered


# ---------------------------------------------------------------------------
# telemetry OFF: bit-exact vs the pinned goldens (PR 4 / PR 6 values)
# ---------------------------------------------------------------------------

def test_open_loop_golden_bitexact_telemetry_default(sf5_tables):
    """Default TelemetryConfig() must reproduce the goldens of
    test_engine_scaling.test_golden_outcomes_q5 exactly (recorded under
    jax 0.9.0): the off-path carry gains zero pytree leaves, so the
    jaxpr is unchanged."""
    uni = make_traffic(sf5_tables, "uniform")
    cfg = SimConfig(injection_rate=0.35, cycles=150, warmup=40,
                    mode="min", seed=7, telemetry=TelemetryConfig())
    r = simulate(sf5_tables, uni, cfg)
    assert r.telemetry is None
    assert r.delivered == 10391 and r.injected == 10562
    assert round(r.avg_latency, 9) == 3.407100285


def test_closed_loop_golden_bitexact_telemetry_on(sf5_tables):
    """The golden closed-loop run (test_jobs case 1, recorded under jax
    0.9.0) keeps its exact outcome even with counters AND tracing
    enabled — telemetry is data-only: no RNG consumed, no engine value
    reads a telemetry value."""
    wl = ring_all_reduce(12, 5)
    base = dict(mode="ugal_l", placement="spread", chunk=96, seed=3)
    r = run_workload(sf5_tables, wl, WorkloadSimConfig(**base))
    t = run_workload(sf5_tables, wl,
                     WorkloadSimConfig(telemetry=_FULL_TRACE, **base))
    assert r.telemetry is None and t.telemetry is not None
    for got in (r, t):
        assert got.completed and got.makespan == 183.0
        assert got.flits_delivered == 1320
        assert int(got.msg_done.sum()) == 24559
        assert int(got.msg_start.sum()) == 22423
    np.testing.assert_array_equal(r.msg_done, t.msg_done)
    np.testing.assert_array_equal(r.msg_start, t.msg_start)
    np.testing.assert_array_equal(r.per_cycle_delivered,
                                  t.per_cycle_delivered)


def test_open_loop_counters_core_results_identical(sf5_tables):
    """Open loop: enabling telemetry never perturbs the simulated
    outcome — every core field is bit-identical off vs on."""
    uni = make_traffic(sf5_tables, "uniform")
    cfg = SimConfig(injection_rate=0.3, cycles=80, warmup=20,
                    mode="ugal_l", seed=11)
    off = simulate(sf5_tables, uni, cfg)
    on = simulate(sf5_tables, uni, dataclasses.replace(
        cfg, telemetry=_FULL_TRACE))
    assert (off.delivered, off.injected, off.dropped_at_source) == \
           (on.delivered, on.injected, on.dropped_at_source)
    assert off.avg_latency == on.avg_latency
    assert off.src_occupancy == on.src_occupancy
    np.testing.assert_array_equal(off.per_cycle_delivered,
                                  on.per_cycle_delivered)
    np.testing.assert_array_equal(off.per_cycle_in_flight,
                                  on.per_cycle_in_flight)


# ---------------------------------------------------------------------------
# counter conservation: q in {5, 7}, healthy and 10%-failed
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q,failed", [(5, False), (5, True),
                                      (7, False), (7, True)])
def test_counter_conservation(q, failed):
    """On a drained closed-loop run: channel forwards == hops taken,
    ejections == flits delivered, grants == forwards + ejections, and
    route decisions == flits injected — on healthy AND degraded
    fabrics (failures reroute traffic but can't break accounting)."""
    topo = cached_slimfly(q)
    fe = (failure_edge_sample(topo, 0.10, np.random.default_rng(q))
          if failed else None)
    tables = SimTables.build(topo, failed_edges=fe)
    r = run_workload(
        tables, ring_all_reduce(8, 4),
        WorkloadSimConfig(mode="ugal_l", placement="spread", chunk=64,
                          seed=2, telemetry=TelemetryConfig(counters=True)))
    assert r.completed
    _conserve(r)
    cs = r.telemetry.counters
    # per-channel forwards can't exceed 1 flit/cycle; dead channels
    # (failed or absent) forward nothing
    assert cs.chan_flits.max() <= cs.cycles
    nbr = np.asarray(tables.nbr)
    assert cs.chan_flits[nbr < 0].sum() == 0


def test_route_counters_min_mode(sf5_tables):
    """mode=min never takes a VAL path, and every injection is
    counted: route_min == flits delivered on a drained run."""
    r = run_workload(
        sf5_tables, ring_all_reduce(8, 4),
        WorkloadSimConfig(mode="min", placement="linear", chunk=64,
                          telemetry=TelemetryConfig(counters=True)))
    assert r.completed
    cs = r.telemetry.counters
    assert int(cs.route_val.sum()) == 0
    assert int(cs.route_min.sum()) == r.flits_delivered


# ---------------------------------------------------------------------------
# lane-batched sweeps report per-lane counters (DESIGN.md §10)
# ---------------------------------------------------------------------------

def test_sweep_lane_counters_match_sequential(sf5_tables):
    tr = make_traffic(sf5_tables, "uniform")
    cfg = SimConfig(cycles=60, warmup=15, mode="ugal_l",
                    telemetry=TelemetryConfig(counters=True))
    rates, seeds = [0.15, 0.45], [3, 5]
    swept = sweep_simulate(sf5_tables, tr, cfg, rates=rates, seeds=seeds)
    for rate, seed, got in zip(rates, seeds, swept):
        want = simulate(sf5_tables, tr, dataclasses.replace(
            cfg, injection_rate=rate, seed=seed))
        assert got.delivered == want.delivered
        a, b = got.telemetry.counters, want.telemetry.counters
        np.testing.assert_array_equal(a.chan_flits, b.chan_flits)
        np.testing.assert_array_equal(a.alloc_grant, b.alloc_grant)
        np.testing.assert_array_equal(a.alloc_deny, b.alloc_deny)
        np.testing.assert_array_equal(a.ej_lat_sum, b.ej_lat_sum)
        np.testing.assert_array_equal(a.occ_max, b.occ_max)


# ---------------------------------------------------------------------------
# trace: event/span well-formedness, ring wrap, sampling
# ---------------------------------------------------------------------------

def _traced_run(sf5_tables, **tel_kw):
    tc = TelemetryConfig(counters=True, trace=True, **tel_kw)
    return run_workload(
        sf5_tables, ring_all_reduce(12, 5),
        WorkloadSimConfig(mode="ugal_l", placement="spread", chunk=96,
                          seed=3, telemetry=tc))


def test_trace_full_sample_spans(sf5_tables):
    """shift=0 traces everything: event counts match the counters
    exactly and every span is complete (inject + hops + eject)."""
    r = _traced_run(sf5_tables, trace_sample_shift=0,
                    trace_capacity=1 << 14)
    snap = r.telemetry
    assert snap.events_dropped == 0
    kinds = snap.events["kind"]
    n_inj = int((kinds == KIND_INJECT).sum())
    n_hop = int((kinds == KIND_HOP).sum())
    n_ej = int((kinds == KIND_EJECT).sum())
    cs = snap.counters
    assert n_inj == n_ej == r.flits_delivered
    assert n_hop == int(cs.chan_flits.sum())
    spans = snap.spans()
    assert len(spans) == r.flits_delivered
    for sp in spans:
        assert sp["start"] is not None and sp["end"] is not None
        assert sp["end"] >= sp["start"]
        assert sp["n_hops"] == len(sp["hops"])
        # hop cycles sit inside the span and are strictly ordered
        cycles = [c for c, _, _ in sp["hops"]]
        assert cycles == sorted(cycles)
        assert all(sp["start"] <= c <= sp["end"] for c in cycles)


def test_trace_ring_wrap(sf5_tables):
    """A tiny ring wraps: only the newest `capacity` events survive, in
    chronological order, and span decode tolerates the missing heads."""
    r = _traced_run(sf5_tables, trace_sample_shift=0, trace_capacity=64)
    snap = r.telemetry
    assert len(snap.events) <= 64
    c = snap.events["cycle"]
    assert (np.diff(c.astype(np.int64)) >= 0).all()
    # the survivors are the newest events of the run
    assert c[-1] == snap.events["cycle"].max()
    spans = snap.spans()            # partial spans decode, no crash
    assert spans and all(sp["end"] is not None or sp["hops"] or
                         sp["start"] is not None for sp in spans)


def test_trace_sampling_deterministic(sf5_tables):
    """shift>0 traces exactly the messages the host-side predicate
    selects — the device hash and `sampled_fids` agree."""
    r = _traced_run(sf5_tables, trace_sample_shift=2,
                    trace_capacity=1 << 14)
    snap = r.telemetry
    msgs = np.unique(snap.events["msg"])
    assert 0 < len(msgs) < r.n_messages          # a strict subset
    assert sampled_fids(msgs, 2).all()
    # and nothing selected was silently skipped: every sampled message
    # that delivered flits appears in the trace
    want = np.flatnonzero(sampled_fids(np.arange(r.n_messages), 2))
    done = want[np.asarray(r.msg_done)[want] >= 0]
    assert np.isin(done, msgs).all()
    # re-running is bit-identical (hash sampling, no RNG)
    r2 = _traced_run(sf5_tables, trace_sample_shift=2,
                     trace_capacity=1 << 14)
    np.testing.assert_array_equal(snap.events, r2.telemetry.events)


# ---------------------------------------------------------------------------
# export layer
# ---------------------------------------------------------------------------

def test_export_chrome_trace_and_heatmap(sf5_tables, tmp_path):
    r = _traced_run(sf5_tables, trace_sample_shift=1,
                    trace_capacity=1 << 14)
    doc = export.chrome_trace(r.telemetry,
                              per_cycle_counter=r.per_cycle_delivered)
    json.loads(json.dumps(doc))                  # fully serialisable
    evs = doc["traceEvents"]
    assert any(e["ph"] == "X" for e in evs)      # flit spans
    assert any(e["ph"] == "M" for e in evs)      # track metadata
    assert any(e["ph"] == "C" for e in evs)      # run counter track
    assert doc["otherData"]["n_spans"] > 0
    p = tmp_path / "trace.json"
    export.write_chrome_trace(str(p), r.telemetry)
    assert json.loads(p.read_text())["traceEvents"]

    hp = tmp_path / "heat.json"
    hdoc = export.write_channel_heatmap(
        str(hp), [r.telemetry], lane_labels=["run"])
    loaded = json.loads(hp.read_text())
    assert loaded["kind"] == "repro.telemetry.channel_load"
    lane = loaded["lanes"][0]
    assert lane["label"] == "run"
    load = np.asarray(lane["channel_load"])
    assert load.shape == np.asarray(sf5_tables.nbr).shape
    assert (load >= 0).all() and (load <= 1).all()
    assert hdoc["n_lanes"] == 1

    lines = export.telemetry_summary(r.telemetry.counters, top=3)
    assert any("channel" in ln for ln in lines)


# ---------------------------------------------------------------------------
# SimResult.saturated derives from the configured q_src (satellite fix)
# ---------------------------------------------------------------------------

def test_saturated_uses_configured_q_src():
    def mk(occ, q_src):
        return SimResult(
            name="t", offered_load=0.5, accepted_load=0.4,
            avg_latency=1.0, delivered=1, injected=1,
            dropped_at_source=0, src_occupancy=occ,
            per_cycle_delivered=np.zeros(1), q_src=q_src)
    # occupancy 20: saturated for a depth-8 queue, fine for depth-64
    assert mk(20.0, 8).saturated
    assert not mk(20.0, 64).saturated
    # any source drop saturates regardless of depth
    r = dataclasses.replace(mk(0.0, 64), dropped_at_source=3)
    assert r.saturated


def test_simulate_plumbs_q_src(sf5_tables):
    uni = make_traffic(sf5_tables, "uniform")
    r = simulate(sf5_tables, uni, SimConfig(
        injection_rate=0.1, cycles=40, warmup=10, q_src=16))
    assert r.q_src == 16
