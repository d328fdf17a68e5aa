"""Attribute a profile's device time to the stages of a simulated cycle.

The simulator wraps each stage of a cycle in a flat `jax.named_scope`:
`switch.*` in `SwitchCore` (occupancy, route, inject, desires, space,
alloc, fold, arrivals, telemetry, compaction) and `closed.*` in the
closed loop's step (ready, pick, account).  A scope reaches the device
only as the `op_name` metadata of the compiled program's instructions;
the profile's op events carry the instruction's name alone.  So:

- `stage_map` reads the optimised HLO text of the runner that ran and
  maps each instruction name to the innermost stage component of its
  `op_name` (a fusion goes by its own metadata);
- `program_stage_map` takes that text from the program itself
  (`compiled_runner_hlo` of `repro.sim.engine` and of
  `repro.sim.workloads.closed_loop`, which lower the cached runners
  again), and is empty where the program has no such function;
- `stage_seconds` sums the self time (`trace._self_times`: an op's time
  less that of the ops nested in it, so the scan's `while` does not
  count its body twice) of the ops of each stage.  Only ops nested in a
  `while` are attributed to a stage: every stage runs in the scan's
  body, and an op of another program (an eager `jnp.zeros`, say) may
  share an instruction name with one of the runner's.  Ops under no
  stage count as `UNSCOPED`;
- `idle_by_span` sums the idle gaps of the traced window by the
  innermost program host span (`sim.*` in `simulate`, `workload.*` in
  `run_workload`) covering each.
"""

from __future__ import annotations

import collections
import importlib
import re

from bench import trace

UNSCOPED = "unscoped"
# the innermost stage component of an op_name
_STAGE = re.compile(r"(?:^|/)((?:switch|closed)\.[A-Za-z_]+)(?=/|$)")
# an instruction of the HLO text, with the op_name of its metadata
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?'
                    r'metadata=\{[^}]*?op_name="([^"]*)"', re.M)
PROGRAM_SPAN = re.compile(r"^(?:sim|workload)\.[A-Za-z_]+")
NO_SPAN = "no program span"
_Plane = collections.namedtuple("_Plane", "name lines")
_Line = collections.namedtuple("_Line", "name events")


def stage_map(hlo_texts) -> dict:
    """{instruction name: stage} over the optimised HLO texts given."""
    out = {}
    for text in hlo_texts:
        for name, op_name in _INSTR.findall(text):
            stages = _STAGE.findall(op_name)
            if stages:
                out[name] = stages[-1]
    return out


def program_stage_map() -> dict:
    """`stage_map` of every simulator runner compiled in this process;
    empty where the program has no `compiled_runner_hlo`.  An
    instruction name is unique within one program only: a process that
    compiled several runners (a benchmark run compiles one) gets the
    stage of the last runner that has the name."""
    texts = []
    for mod in ("repro.sim.engine", "repro.sim.workloads.closed_loop"):
        hlo = getattr(importlib.import_module(mod), "compiled_runner_hlo",
                      None)
        if hlo is not None:
            texts.extend(hlo())
    return stage_map(texts)


def stage_seconds(summary: trace.Summary, smap: dict) -> dict:
    """{stage or UNSCOPED: self seconds, per device} of the summary's
    device ops (those inside the traced window)."""
    by_dev = collections.defaultdict(list)
    for name, s, e, dev in summary.ops:
        by_dev[dev].append((name, s, e))
    ns = collections.Counter()
    for ops in by_dev.values():
        labelled, loops = [], []       # loops: ends of the open whiles
        for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
            while loops and loops[-1] <= s:
                loops.pop()
            stage = smap.get(name, UNSCOPED) if loops else UNSCOPED
            labelled.append((name, stage, s, e))
            if name.startswith("while"):
                loops.append(e)
        trace._self_times(labelled, ns)
    n_dev = max(1, len(by_dev))
    return {stage: t / 1e9 / n_dev for stage, t in ns.items()}


def time_share(ctx: dict, stage: str):
    """Share of device busy time, in %, in `stage` (or UNSCOPED) for a
    per-layer metric's `read(ctx)`; None where the trace holds nothing
    or the program names no stages.  The stage times are worked out
    once per run and kept in `ctx`."""
    t = ctx["trace"]
    if t is None or t.busy_s <= 0:
        return None
    if "stage_seconds" not in ctx:
        smap = program_stage_map()
        ctx["stage_seconds"] = stage_seconds(t, smap) if smap else {}
    seconds = ctx["stage_seconds"].get(stage, 0.0)
    return 100.0 * seconds / t.busy_s if seconds > 0 else None


def idle_by_span(planes) -> list:
    """[[span, seconds]]: the traced window's idle gaps (as
    `trace.reduce_planes` finds them), summed by the innermost program
    span covering each gap's midpoint, or NO_SPAN; longest first."""
    kept = []
    for plane in planes:
        lines = list(plane.lines)
        if not trace.DEVICE_PLANE.match(plane.name):
            lines = [_Line(line.name, [
                e for e in line.events
                if e.name == trace.CALL_SPAN or PROGRAM_SPAN.match(e.name)])
                for line in lines]
        kept.append(_Plane(plane.name, lines))
    gaps = collections.Counter()
    for label, ns in trace.reduce_planes(kept).gaps:
        m = PROGRAM_SPAN.match(label)
        gaps[m.group(0) if m else NO_SPAN] += ns
    return [[label, ns / 1e9] for label, ns in gaps.most_common()]
