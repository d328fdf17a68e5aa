"""Share of device busy time under no stage scope: the scan's own `while`,
what the runner does outside the loop, the ops of a cycle that no stage
names, and other programs in the traced call. The stage shares and this
one add up to the busy time (`bench/stages.py`)."""

from bench import stages


def read(ctx):
    return stages.time_share(ctx, stages.UNSCOPED)
