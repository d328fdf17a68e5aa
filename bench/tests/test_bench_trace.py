"""The trace reduction, on a hand-made profile and on a small trace
recorded on a TPU v5e (`data/q5_call.xplane.pb.gz`: a q=5 open-loop
call of 6 cycles under UGAL-L, a 10 ms host span `bench.check`, and a
q=5 ring all-reduce call of 8 cycles, each call in a `bench.call`
span)."""

import collections
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "q5_call.xplane.pb.gz")

Ev = collections.namedtuple("Ev", "name start_ns duration_ns")
Line = collections.namedtuple("Line", "name events")
Plane = collections.namedtuple("Plane", "name lines")


def planes():
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.call", 100, 900),
        Ev("PjitFunction(run)", 120, 50),
        Ev("np.asarray(jax.Array)", 820, 180)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_run", 150, 700)]),
        Line("XLA Ops", [
            Ev("%while.4 = (s32[], s32[8]) while(s32[] %p)", 150, 700),
            Ev("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %a)", 150, 100),
            Ev("%alloc_rounds_pallas.3 = (s32[56,28]{1,0}, s32[56,4]{1,0}) "
               "custom-call(s32[1,1]{1,0} %b)", 250, 100),
            Ev("%fusion.2 = s32[8]{0} fusion(s32[56,28]{1,0} "
               "%alloc_rounds_pallas.3)", 400, 50),     # names the kernel
            Ev("%ugal_select_pallas.7 = s32[512,1]{1,0} custom-call()",
               700, 100),
            Ev("%fusion.3 = s32[8]{0} fusion()", 1500, 10)])])  # outside
    return [host, dev]


def test_hand_made_profile():
    s = trace.reduce_planes(planes())
    assert s.window == (100, 1000)
    # busy: the while, [150, 850)
    assert s.busy_ns == 700
    assert s.op_seconds(trace.KERNELS["alloc_rounds"]) == (100e-9, 1)
    assert s.op_seconds(trace.KERNELS["ugal_select"]) == (100e-9, 1)
    # self times: the while keeps what its body does not cover
    assert s.self_ns == {"while.4 s32[]": 700 - 100 - 100 - 50 - 100,
                         "fusion.1 s32[8]": 100,
                         "alloc_rounds_pallas.3 s32[56,28]": 100,
                         "fusion.2 s32[8]": 50,
                         "ugal_select_pallas.7 s32[512,1]": 100}
    assert s.top_ops(1) == [["while.4 s32[]", 350e-9]]
    # gaps: [100,150) and [850,1000)
    assert [g[1] for g in s.gaps] == [150, 50]
    assert s.gaps[0][0] == "np.asarray(jax.Array)"     # innermost at 925
    assert s.gaps[1][0] == "PjitFunction(run)"
    assert len(s.top_gaps(1)) == 1


def test_no_call_span_is_an_error():
    with pytest.raises(ValueError, match="bench.call"):
        trace.reduce_planes(planes()[1:])


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce_file(DATA)


def test_recorded_trace_busy_and_window(recorded):
    assert 0 < recorded.busy_s < recorded.window_s
    assert recorded.ops


def test_recorded_trace_kernels(recorded):
    # one allocation per cycle of both calls, one UGAL choice per cycle
    # of the open-loop call
    _, n_alloc = recorded.op_seconds(trace.KERNELS["alloc_rounds"])
    _, n_ugal = recorded.op_seconds(trace.KERNELS["ugal_select"])
    assert (n_alloc, n_ugal) == (6 + 8, 6)


def test_recorded_trace_gap_labels(recorded):
    labels = dict((g[0], g[1]) for g in recorded.gaps)
    assert labels.get("bench.check", 0) >= 0.01 * 1e9 * 0.9
    assert recorded.top_gaps(10)[0][1] >= recorded.top_gaps(10)[-1][1]
