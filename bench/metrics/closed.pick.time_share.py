"""Share of device busy time in the `closed.pick` stage of a simulated
cycle: the closed loop's per-endpoint pick of the lowest-id sendable
message. Self time of the ops the compiled runner's `op_name` metadata
puts under the scope, over busy time (`bench/stages.py`)."""

from bench import stages


def read(ctx):
    return stages.time_share(ctx, "closed.pick")
