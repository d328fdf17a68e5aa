"""The reduction of a profile by stage (`bench/stages.py`), on hand-made
profiles and on small traces recorded on a TPU v5e with the stage
scopes in place (`data/q5_stages_*`, written by `record_q5_stages.py`:
a q=5 open-loop call of 6 cycles under UGAL-L and a q=5 ring all-reduce
call of two 4-cycle chunks, each profile with the optimised HLO text of
the runner that ran)."""

import collections
import gzip
import json
import os
import re

import pytest

from bench import harness, stages, trace
from bench.tests.conftest import ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

Ev = collections.namedtuple("Ev", "name start_ns duration_ns")
Line = collections.namedtuple("Line", "name events")
Plane = collections.namedtuple("Plane", "name lines")

HLO = """HloModule jit_run, entry_computation_layout={()->()}

%fused_computation.1 (param_0: s32[8]) -> s32[8] {
  %gather.3 = s32[8]{0} gather(s32[8]{0} %param_0), metadata={op_name="jit(run)/while/body/closed_call/switch.space/gather" source_file="e.py" source_line=2}
}

ENTRY %main.5 () -> () {
  %fusion.1 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(run)/while/body/closed_call/switch.desires/gather" source_file="e.py" source_line=1}
  %alloc_rounds_pallas.3 = (s32[56,28]{1,0}) custom-call(s32[1,1]{1,0} %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/while/body/closed_call/switch.alloc/jit(alloc_rounds_pallas)/alloc_rounds_pallas/pallas_call" stack_frame_id=9}
  %fusion.2 = s32[8]{0} fusion(s32[8]{0} %q), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(run)/while/body/closed_call/switch.route/jit(f)/closed.pick/argmax"}
  %copy.9 = s32[8]{0} copy(s32[8]{0} %x), metadata={}
  ROOT %while.4 = (s32[]) while(s32[] %p), condition=%cond, body=%body, metadata={op_name="jit(run)/while"}
}
"""
SMAP = {"gather.3": "switch.space", "fusion.1": "switch.desires",
        "alloc_rounds_pallas.3": "switch.alloc", "fusion.2": "closed.pick"}


def test_stage_map_reads_the_innermost_stage_of_each_instruction():
    assert stages.stage_map([HLO]) == SMAP


def planes():
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.call", 50, 1850),
        Ev("workload.run", 110, 1780),
        Ev("workload.init_carry", 110, 40),
        Ev("workload.chunk", 150, 750),
        Ev("np.asarray(jax.Array)", 800, 100),
        Ev("workload.chunk", 900, 800),
        Ev("workload.result", 1700, 190)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_run", 160, 540)]),
        Line("XLA Ops", [
            Ev("%while.4 = (s32[]) while(s32[] %p)", 160, 540),
            Ev("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p)", 160, 100),
            Ev("%alloc_rounds_pallas.3 = (s32[56,28]{1,0}) "
               "custom-call(s32[1,1]{1,0} %b)", 300, 100),
            Ev("%fusion.2 = s32[8]{0} fusion(s32[8]{0} %q)", 450, 50),
            Ev("%copy.9 = s32[8]{0} copy(s32[8]{0} %x)", 500, 20),
            # another program's op of the same name, outside the loop
            Ev("%fusion.1 = s32[4]{0} fusion()", 720, 40),
            Ev("%while.4 = (s32[]) while(s32[] %p)", 1000, 600),
            Ev("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p)", 1000, 100)])])
    return [host, dev]


def test_stage_seconds_are_self_times_inside_the_loop():
    s = trace.reduce_planes(planes())
    got = stages.stage_seconds(s, SMAP)
    ns = {k: round(v * 1e9) for k, v in got.items()}
    assert ns == {
        "switch.desires": 100 + 100,
        "switch.alloc": 100,
        "closed.pick": 50,
        # the whiles keep what their bodies do not cover (270 and 500),
        # an op no stage names (20) and the op outside the loops (40)
        stages.UNSCOPED: (540 - 100 - 100 - 50 - 20) + 20 + 40 + (600 - 100)}
    assert sum(ns.values()) == s.busy_ns == 540 + 40 + 600


def test_idle_by_span_takes_the_innermost_program_span():
    got = {k: round(v * 1e9) for k, v in stages.idle_by_span(planes())}
    assert got == {
        stages.NO_SPAN: 160 - 50,             # before workload.init_carry
        # [700, 720) and [760, 1000), the second under np.asarray
        "workload.chunk": 20 + 240,
        "workload.result": 1900 - 1600}
    assert sum(got.values()) == 1850 - (540 + 40 + 600)
    assert stages.idle_by_span(planes())[0][0] == "workload.result"


def ctx_of(summary):
    return {"trace": summary, "window": {}, "device": {}, "sizes": {}}


def test_time_share_reads_the_program_map_once(monkeypatch):
    calls = []
    monkeypatch.setattr(stages, "program_stage_map",
                        lambda: calls.append(1) or SMAP)
    ctx = ctx_of(trace.reduce_planes(planes()))
    assert stages.time_share(ctx, "switch.alloc") == pytest.approx(
        100.0 * 100 / 1180)
    assert stages.time_share(ctx, stages.UNSCOPED) == pytest.approx(
        100.0 * 830 / 1180)
    assert stages.time_share(ctx, "switch.route") is None   # no such op
    assert calls == [1]


def test_time_share_is_none_without_scopes_or_trace(monkeypatch):
    monkeypatch.setattr(stages, "program_stage_map", dict)
    assert stages.time_share(ctx_of(trace.reduce_planes(planes())),
                             "switch.alloc") is None
    assert stages.time_share(ctx_of(None), "switch.alloc") is None


def stage_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return [m for m in bench["per_layer"]
            if m["name"].startswith(("switch.", "closed.", "unscoped."))]


def test_every_stage_metric_has_a_reader_of_its_stage(monkeypatch):
    monkeypatch.setattr(stages, "program_stage_map", lambda: SMAP)
    cell = harness.Cell(ROOT, "sf_q19.ring_allreduce_min")
    metrics = stage_metrics()
    assert len(metrics) == 15
    for m in metrics:
        stage = m["name"].split(".time_share.")[0]
        assert m["layer"] == f"stage {stage}"
        assert m["unit"] == "%" and m["better"] == "lower"
        ctx = ctx_of(trace.reduce_planes(planes()))
        got = cell.metric_reader(m["name"]).read(ctx)
        want = stages.stage_seconds(ctx["trace"], SMAP).get(stage)
        assert (got is None) == (want is None), m["name"]
        if want is not None:
            assert got == pytest.approx(100.0 * want * 1e9 / 1180)


SWITCH = {"switch.occupancy", "switch.route", "switch.inject",
          "switch.desires", "switch.space", "switch.alloc", "switch.fold",
          "switch.arrivals", "switch.compaction"}
RECORDED = {
    # MIN reads no occupancy and chooses no route: both compile away
    "open": SWITCH,
    "ring": (SWITCH - {"switch.occupancy", "switch.route"})
    | {"closed.ready", "closed.pick", "closed.account"}}


def recorded_planes(call):
    from jax.profiler import ProfileData

    with gzip.open(os.path.join(DATA, f"q5_stages_{call}.xplane.pb.gz")) as f:
        return ProfileData.from_serialized_xspace(f.read()).planes


@pytest.fixture(scope="module", params=sorted(RECORDED))
def recorded(request):
    call = request.param
    with gzip.open(os.path.join(DATA, f"q5_stages_{call}.hlo.gz"),
                   "rt") as f:
        smap = stages.stage_map([f.read()])
    return call, trace.reduce_planes(recorded_planes(call)), smap


def test_recorded_stage_shares_add_up_to_busy_time(recorded):
    call, summary, smap = recorded
    got = stages.stage_seconds(summary, smap)
    assert set(got) == RECORDED[call] | {stages.UNSCOPED}
    assert sum(got.values()) == pytest.approx(summary.busy_s, rel=0.01)
    assert got[stages.UNSCOPED] < 0.10 * summary.busy_s


def test_recorded_kernels_run_inside_their_stages(recorded):
    call, summary, smap = recorded
    kernels = {n for n, *_ in summary.ops
               if re.fullmatch(trace.KERNELS["alloc_rounds"], n)}
    assert kernels and {smap[n] for n in kernels} == {"switch.alloc"}
    ugal = {n for n, *_ in summary.ops
            if re.fullmatch(trace.KERNELS["ugal_select"], n)}
    assert {smap[n] for n in ugal} == (
        {"switch.route"} if call == "open" else set())


def test_recorded_idle_gaps_fall_under_program_spans(recorded):
    call, summary, _ = recorded
    got = dict(stages.idle_by_span(recorded_planes(call)))
    prefix = "sim." if call == "open" else "workload."
    assert got and all(k.startswith(prefix) for k in got)
    if call == "ring":
        # the host reads each chunk's stats before it dispatches the next
        assert got["workload.chunk"] > 0
    assert sum(got.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)
