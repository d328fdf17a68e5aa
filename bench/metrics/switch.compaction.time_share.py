"""Share of device busy time in the `switch.compaction` stage of a
simulated cycle: dequeue, insert and the source-FIFO shift of the shift-
down queues. Self time of the ops the compiled runner's `op_name`
metadata puts under the scope, over busy time (`bench/stages.py`)."""

from bench import stages


def read(ctx):
    return stages.time_share(ctx, "switch.compaction")
