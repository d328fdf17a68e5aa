"""Share of device busy time in the `switch.alloc` stage of a simulated
cycle: the router-major request re-layout and the `alloc_rounds` call
(the kernel's own share is `alloc_rounds.time_share`). Self time of the
ops the compiled runner's `op_name` metadata puts under the scope, over
busy time (`bench/stages.py`)."""

from bench import stages


def read(ctx):
    return stages.time_share(ctx, "switch.alloc")
