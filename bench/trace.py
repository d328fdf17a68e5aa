"""Reduce a JAX profiler trace to what the per-layer metrics read.

A trace (`.xplane.pb`, read with `jax.profiler.ProfileData`) holds one
plane per device (`/device:TPU:<i>`) and one for the host.  From it:

- the traced window: the span of the benchmark's own `bench.call` host
  spans (`jax.profiler.TraceAnnotation` around each call);
- device busy time: the union of the intervals in which an operation
  ran on a device (its "XLA Ops" line), clipped to the window and
  averaged over the devices that ran anything;
- idle gaps: the stretches of the window in which the device ran
  nothing, each labelled by what the host thread that made the call
  was doing at the gap's midpoint (its innermost span there);
- the device operations that took most self time (time not covered by
  operations nested inside them, as a scan's body is inside its
  `while`), and the time and count of the operations whose names match a
  kernel's pattern.

A device op event is named by its HLO text, `%<op name> = <result type>
<opcode>(<operands>) ...`; operations are known by the op name alone, so
that an operand that mentions a kernel's output does not count as the
kernel.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import os
import re

CALL_SPAN = "bench.call"
# the simulator's Pallas kernels, by HLO op name (the name JAX gives a
# `pallas_call`: its jitted wrapper's, plus a numeric suffix)
KERNELS = {"alloc_rounds": r"alloc_rounds_pallas(\.\d+)?",
           "ugal_select": r"ugal_select_pallas(\.\d+)?"}
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Summary:
    window: tuple                 # (start ns, end ns) of the traced calls
    ops: list                     # [(op name, start ns, end ns, device)]
    self_ns: dict                 # {op name and result type: self ns}
    busy_ns: float                # busy time, mean over devices
    gaps: list                    # [(label, ns)] idle stretches, longest first

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def op_seconds(self, pattern: str) -> tuple:
        """(seconds, events), per device, of the device operations whose
        op name matches `pattern`."""
        rx = re.compile(pattern)
        hit = [o for o in self.ops if rx.fullmatch(o[0])]
        n_dev = max(1, len({o[3] for o in self.ops}))
        return sum(o[2] - o[1] for o in hit) / 1e9 / n_dev, len(hit) / n_dev

    def top_ops(self, n: int) -> list:
        """[[op name and result type, self seconds per device]]."""
        n_dev = max(1, len({o[3] for o in self.ops}))
        top = collections.Counter(self.self_ns).most_common(n)
        return [[name, ns / 1e9 / n_dev] for name, ns in top]

    def top_gaps(self, n: int) -> list:
        return [[label, ns / 1e9] for label, ns in self.gaps[:n]]


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _op_name(hlo: str) -> tuple:
    """(op name, op name and result type) of a device op event."""
    name, _, rest = hlo.partition(" = ")
    name = name.lstrip("%")
    shape = re.match(r"\(?([a-z]+[0-9]*\[[0-9,]*\])", rest)
    return name, f"{name} {shape.group(1)}" if shape else name


def _self_times(events: list, into: collections.Counter) -> None:
    """Add each event's time minus that of the events nested in it."""
    stack = []
    for _, label, s, e in sorted(events, key=lambda x: (x[2], -x[3])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            into[stack[-1][0]] -= e - s
        into[label] += e - s
        stack.append((label, e))


def _label(host: list, t: float) -> str:
    """Innermost host span covering time `t` (the latest-starting one)."""
    best = None
    for name, s, e in host:
        if s <= t <= e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "no host span"


def reduce_planes(planes) -> Summary:
    """Reduce the planes of one profile (see the module docstring)."""
    host, calls = [], []
    dev_ops = {}
    for plane in planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events]
            if is_dev:
                if line.name == OPS_LINE:
                    dev_ops.setdefault(plane.name, []).extend(events)
            elif any(n == CALL_SPAN for n, _, _ in events):
                calls.extend(ev for ev in events if ev[0] == CALL_SPAN)
                host.extend(events)
    if not calls:
        raise ValueError(f"no {CALL_SPAN!r} span in the trace")
    w0, w1 = min(s for _, s, _ in calls), max(e for _, _, e in calls)
    ops, busy, self_ns = [], [], collections.Counter()
    for dev, events in dev_ops.items():
        inside = [_op_name(n) + (max(s, w0), min(e, w1)) for n, s, e in events
                  if e > w0 and s < w1]
        ops.extend((n, s, e, dev) for n, _, s, e in inside)
        _self_times(inside, self_ns)
        if inside:
            busy.append(_union([(s, e) for _, _, s, e in inside]))
    busy_ns = (sum(sum(e - s for s, e in b) for b in busy) / len(busy)
               if busy else 0.0)
    gaps = []
    if busy:
        # gaps of the first device that ran anything, window edges included
        edges = [w0] + [x for s, e in busy[0] for x in (s, e)] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((_label(host, (s + e) / 2), e - s))
    gaps.sort(key=lambda g: -g[1])
    return Summary(window=(w0, w1), ops=ops, self_ns=dict(self_ns),
                   busy_ns=busy_ns, gaps=gaps)


def load_profile(path: str):
    """The `jax.profiler.ProfileData` of an `.xplane.pb` file, or of its
    gzip (`.xplane.pb.gz`); its `planes` can be read more than once."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path) as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def reduce_file(path: str) -> Summary:
    """Reduce an `.xplane.pb` file, or its gzip (`.xplane.pb.gz`)."""
    return reduce_planes(load_profile(path).planes)


def find_profile(log_dir: str) -> str:
    """The one profile that `jax.profiler.trace(log_dir)` wrote."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, "
                         f"found {len(found)}")
    return found[0]

