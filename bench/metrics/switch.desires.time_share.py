"""Share of device busy time in the `switch.desires` stage of a simulated
cycle: the route desires of every window slot: the head-window slices
and both `_desires` gathers from the routing tables. Self time of the
ops the compiled runner's `op_name` metadata puts under the scope, over
busy time (`bench/stages.py`)."""

from bench import stages


def read(ctx):
    return stages.time_share(ctx, "switch.desires")
