"""Share of device busy time in the `switch.arrivals` stage of a simulated
cycle: the arrivals: the winning packet of each upstream channel,
gathered from the window, with its hop count bumped. Self time of the
ops the compiled runner's `op_name` metadata puts under the scope, over
busy time (`bench/stages.py`)."""

from bench import stages


def read(ctx):
    return stages.time_share(ctx, "switch.arrivals")
