"""`correct` on the CPU, at a size a test run holds: the control and
each fault the timed path can have must come out as not correct.

The control is the plain reference in the program's place, computed one
step down: the open-loop latency sum in bfloat16 instead of float32,
and, for the closed loop (which keeps no float), the dependency state
read one cycle late.  The faults are planted in the program under a
full harness run with the chip checks steered off.  The exchange
between chips is no fault here: every cell runs on one chip.
"""

import os

import jax.numpy as jnp
import pytest

from bench.harness import load_module
from bench.tests.conftest import CELLS, MIXES, ROOT, SF_Q5


def engine(name):
    return load_module(os.path.join(ROOT, "bench", "engines", name + ".py"))


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 17, 987654321])
def test_open_loop_control_is_caught(seed):
    eng = engine("open_loop")
    state = eng.setup(SF_Q5, MIXES["uniform_short"])
    want = eng.reference(state, seed)
    control = eng.reference(state, seed, control=True)
    assert eng.parts(control, want)["summary_fields"] >= 1
    assert eng.compare(control, want)[0][1] > 0
    assert eng.compare(want, want) == [("open.mismatches", 0, 0)]


LANE_SEED = 2 ** 31 + 17


@pytest.fixture(scope="module")
def lane_call():
    """One call of the five-rate lane mix: (engine, state, answer)."""
    eng = engine("open_loop")
    state = eng.setup(SF_Q5, MIXES["uniform_lanes"])
    return eng, state, eng.observe(eng.call(state, LANE_SEED))


@pytest.mark.parametrize("lane", range(5))
def test_every_lane_matches_the_reference(lane_call, lane):
    eng, state, got = lane_call
    want = eng.reference(state, LANE_SEED, lane=lane)
    rate = MIXES["uniform_lanes"]["rates"][lane]
    assert eng.compare(got, want) == [
        ("open.mismatches", 0, 0, {"lane": lane, "injection_rate": rate})]


def test_lane_control_is_caught(lane_call):
    """The control in the program's place on the lane path.  At q=5 a
    light lane's per-cycle latency sums are a few hundred, and their
    bfloat16 roundings can cancel in the study's mean; the 0.9 lane's
    do not.  At the cell's size every lane's control fails (PERF.md)."""
    eng, state, _ = lane_call
    control = {"lanes": [eng.reference(state, LANE_SEED, control=True,
                                       lane=i) for i in range(5)]}
    want = eng.reference(state, LANE_SEED, lane=4)
    assert eng.parts(control, want)["summary_fields"] >= 1
    assert eng.compare(control, want)[0][1] > 0


def test_closed_loop_control_is_caught():
    eng = engine("closed_loop")
    state = eng.setup(SF_Q5, MIXES["ring_short"])
    want = eng.reference(state, 5)
    control = eng.reference(state, 5, control=True)
    assert eng.parts(control, want)["messages"] > 0
    assert eng.compare(control, want)[0][1] > 0


def _frozen_switch(monkeypatch):
    from repro.sim.engine import SwitchCore

    alloc = SwitchCore.alloc

    def frozen(self, nq_pkt, nq_count, sq_pkt, sq_count, *a, **k):
        out = alloc(self, nq_pkt, nq_count, sq_pkt, sq_count, *a, **k)
        return (nq_pkt, nq_count, sq_pkt, sq_count) + tuple(out[4:])
    monkeypatch.setattr(SwitchCore, "alloc", frozen)


def _half_the_endpoints(monkeypatch):
    from repro.sim.engine import SwitchCore

    inject = SwitchCore.inject

    def half(self, sq_pkt, sq_count, want, new_pkt):
        keep = self.ep_router % 2 == 0          # every other router
        return inject(self, sq_pkt, sq_count, want & keep, new_pkt)
    monkeypatch.setattr(SwitchCore, "inject", half)


def _altered_answer(monkeypatch):
    from repro.sim import engine as eng_mod
    from repro.sim.workloads import closed_loop

    fold = eng_mod._open_loop_fold

    def off_by_one(acc, g_net, g_src, pkt_net, pkt_src, cycle):
        delivered, lat = fold(acc, g_net, g_src, pkt_net, pkt_src, cycle)
        return delivered + (cycle == 5).astype(jnp.int32), lat
    monkeypatch.setattr(eng_mod, "_open_loop_fold", off_by_one)
    pack = closed_loop.pack_record

    def wrong_msg(*a, msg=None):
        return pack(*a, msg=None if msg is None else msg.at[0].add(1))
    monkeypatch.setattr(closed_loop, "pack_record", wrong_msg)


FAULTS = {"state_unchanged": _frozen_switch,
          "half_the_batch": _half_the_endpoints,
          "answer_altered": _altered_answer}


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_caught(checkout, run_cell, monkeypatch, cell, fault):
    FAULTS[fault](monkeypatch)
    line, _ = run_cell(checkout, cell)
    assert line["correct"] is False
    assert line["failed"] == 1
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
