"""Share of device busy time in the `switch.space` stage of a simulated
cycle: the downstream-space check of every window slot (`space_of`'s
gathers of the credit view). Self time of the ops the compiled runner's
`op_name` metadata puts under the scope, over busy time
(`bench/stages.py`)."""

from bench import stages


def read(ctx):
    return stages.time_share(ctx, "switch.space")
