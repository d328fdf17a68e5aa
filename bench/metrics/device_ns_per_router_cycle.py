"""Device busy time of the traced call per router-cycle it simulated:
the cost of one router's share of one cycle of the compiled scan."""


def read(ctx):
    t = ctx["trace"]
    work = sum(c["work"] for c in ctx["window"]["calls"])
    if t is None or t.busy_s <= 0 or work <= 0:
        return None
    return t.busy_s * 1e9 / work
