"""Share of device busy time in the `switch.route` stage of a simulated
cycle: the injection-time route choice (`route_decision`, with the
`ugal_select` kernel). Self time of the ops the compiled runner's
`op_name` metadata puts under the scope, over busy time
(`bench/stages.py`)."""

from bench import stages


def read(ctx):
    return stages.time_share(ctx, "switch.route")
