"""Share of device busy time spent in the `alloc_rounds` Pallas kernel
(the summed device time of its events over the busy time)."""

from bench import trace


def read(ctx):
    t = ctx["trace"]
    if t is None or t.busy_s <= 0:
        return None
    seconds, _ = t.op_seconds(trace.KERNELS["alloc_rounds"])
    return 100.0 * seconds / t.busy_s if seconds > 0 else None
