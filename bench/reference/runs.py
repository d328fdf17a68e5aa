"""Plain reference runs: the open-loop study and the closed-loop
collective, on `network.Network`, with the statistics the simulator
reports for them."""

from __future__ import annotations

import numpy as np

from . import by_name
from .network import Network, Switch

BIG = 1 << 30


def open_loop_draws(seed: int, cycles: int, n_ep: int, n_routers: int,
                    rate: float, draw_route=None) -> tuple:
    """Per-cycle (injection coins, destination draws, route draws) from
    jax.random: key -> (key, k_inj, k_dst, k_route) each cycle.  The
    route draws are the mode's `draw(k_route, n_ep, n_routers)`, or
    None for a mode that draws nothing."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draws(key):
        def step(key, _):
            key, k_inj, k_dst, k_rt = jax.random.split(key, 4)
            return key, (jax.random.bernoulli(k_inj, jnp.float32(rate),
                                              (n_ep,)),
                         jax.random.randint(k_dst, (n_ep,), 0, n_ep - 1),
                         None if draw_route is None
                         else draw_route(k_rt, n_ep, n_routers))
        return jax.lax.scan(step, key, None, length=cycles)[1]

    coins, dsts, route = draws(jax.random.PRNGKey(seed))
    return (np.asarray(coins), np.asarray(dsts),
            [None] * cycles if route is None else np.asarray(route))


def open_loop(fab, sw: Switch, *, pattern: str, rate: float, mode: str,
              cycles: int, warmup: int, seed: int,
              latency_dtype=np.float32) -> dict:
    """Bernoulli injection at `rate` with uniform destinations.

    Returns the per-cycle statistics (injected, delivered, latency sum,
    source backlog, dropped, in flight) and the study's summary numbers.
    `latency_dtype` is the precision the latency sum is kept in."""
    if pattern != "uniform":
        raise ValueError(f"no reference for traffic pattern {pattern!r}")
    net = Network(fab, sw, mode, capacity=max(1, int(rate * fab.n_endpoints
                                                     * cycles * 1.2)))
    E, N = net.E, net.N
    draw = getattr(net.mode, "draw", None)
    coins, dsts, route = open_loop_draws(
        seed, cycles, E, N, rate,
        None if draw is None else lambda k, e, n: draw(k, e, n, sw))
    src_r = net.ep_router
    per = {k: np.zeros(cycles, np.int64) for k in
           ("injected", "delivered", "src_backlog", "dropped", "in_flight")}
    per["latency"] = np.zeros(cycles, np.float64)
    eid = np.arange(E)
    for c in range(cycles):
        occ = net.occupancy()
        want = coins[c] & (net.scount < sw.q_src)
        dropped = int((coins[c] & (net.scount >= sw.q_src)).sum())
        d = dsts[c].astype(np.int64)
        dst_r = src_r[np.where(d >= eid, d + 1, d)]
        inter, phase = net.route(src_r, dst_r, occ, route[c])
        net.inject(want, dst_r, inter, phase, c)
        lat = [latency_dtype(0)]
        got = [0]

        def on_eject(ids, c=c):
            got[0] += len(ids)
            lat[0] = latency_dtype(
                lat[0] + latency_dtype(int((c - net.pk.born[ids] + 1).sum())))

        net.switch(c, on_eject)
        per["injected"][c] = int(want.sum())
        per["delivered"][c] = got[0]
        per["latency"][c] = float(lat[0])
        per["src_backlog"][c] = int(net.scount.sum())
        per["dropped"][c] = dropped
        per["in_flight"][c] = int(net.ncount.sum() + net.scount.sum())
    meas = slice(warmup, cycles)
    delivered_m = int(per["delivered"][meas].sum())
    summary = dict(
        accepted_load=delivered_m / ((cycles - warmup) * E),
        avg_latency=float(per["latency"][meas].sum() / max(delivered_m, 1)),
        delivered=int(per["delivered"].sum()),
        injected=int(per["injected"].sum()),
        dropped_at_source=int(per["dropped"].sum()),
        src_occupancy=float(per["src_backlog"][meas].mean() / E))
    return {"per_cycle": per, "summary": summary}


def collective(kind: str, args: dict) -> dict:
    """Messages of a collective (`collectives/<kind>.py`): its rank
    count, and the src/dst ranks, sizes, dependencies [M, D] (-1 none)
    and phases of its messages."""
    return by_name("collectives", kind).messages(**args)


def place(fab, n_ranks: int, placement: str) -> np.ndarray:
    """Endpoint of each rank: `spread` deals ranks round-robin over the
    routers that hold endpoints, first endpoints first."""
    if placement != "spread":
        raise ValueError(f"no reference for placement {placement!r}")
    routers = np.unique(fab.ep_router)
    i = np.arange(n_ranks)
    return fab.ep_at[routers[i % len(routers)], i // len(routers)]


def closed_loop(fab, sw: Switch, *, kind: str, args: dict,
                placement: str, mode: str, chunk: int,
                max_cycles: int, stale_deps: int = 0) -> dict:
    """Dependency-triggered run of a collective, in chunks of `chunk`
    cycles up to `max_cycles`, stopping at the first chunk end where
    every message is done.

    Each cycle every endpoint injects one flit of its lowest-numbered
    message whose dependencies are all delivered and that has flits
    left, routed by the mode with no route draws.  `stale_deps` > 0
    reads the done state that many cycles late (the control that breaks
    the dependency guarantee)."""
    wl = collective(kind, args)
    ep_of_rank = place(fab, wl["n_ranks"], placement)
    src_ep, dst_ep = ep_of_rank[wl["src"]], ep_of_rank[wl["dst"]]
    size, dep = wl["size"], wl["dep"]
    M = len(size)
    net = Network(fab, sw, mode, capacity=int(size.sum()) + 1)
    E = net.E
    # messages of each sending endpoint, ascending id
    senders, col = np.unique(src_ep, return_inverse=True)
    counts = np.bincount(col, minlength=len(senders))
    by_ep = np.full((len(senders), max(1, counts.max())), -1, np.int64)
    order = np.lexsort((np.arange(M), col))
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    by_ep[col[order], np.arange(M) - starts[col[order]]] = order
    dst_r = net.ep_router[dst_ep]

    sent = np.zeros(M, np.int64)
    flits_del = np.zeros(M, np.int64)
    start = np.full(M, BIG, np.int64)
    done_at = np.full(M, BIG, np.int64)
    history = [np.zeros(M, bool)] * (stale_deps + 1)
    per_dlv = []
    cycle, completed = 0, False
    while cycle < max_cycles:
        for c in range(cycle, cycle + chunk):
            done = flits_del >= size
            history = history[1:] + [done]
            seen = history[0] if stale_deps else done
            dep_ok = np.where(dep >= 0, seen[np.maximum(dep, 0)], True).all(1)
            sendable = dep_ok & (sent < size)
            cand = (by_ep >= 0) & sendable[np.maximum(by_ep, 0)]
            has = np.zeros(E, bool)
            has[senders] = cand.any(axis=1)
            pick = np.zeros(E, np.int64)
            pick[senders] = np.where(
                has[senders],
                by_ep[np.arange(len(senders)), cand.argmax(axis=1)], 0)
            want = has & (net.scount < sw.q_src)
            inter, phase = net.route(net.ep_router, dst_r[pick],
                                     net.occupancy(), None)
            net.inject(want, dst_r[pick], inter, phase, c, msg=pick)
            m = pick[want]
            sent[m] += 1
            start[m] = np.minimum(start[m], c)
            got = [0]

            def on_eject(ids):
                np.add.at(flits_del, net.pk.msg[ids], 1)
                got[0] += len(ids)

            net.switch(c, on_eject)
            now = (flits_del >= size) & (done_at == BIG)
            done_at[now] = c + 1
            per_dlv.append(got[0])
        cycle += chunk
        if (flits_del >= size).all():
            completed = True
            break
    cycles_run = cycle
    per_dlv = np.array(per_dlv, np.int64)
    if completed:
        cycles_run = int(done_at.max())
        per_dlv = per_dlv[:cycles_run]
    return dict(
        completed=completed,
        makespan=float(done_at.max()) if completed else float("inf"),
        cycles_run=cycles_run,
        flits_injected=int(sent.sum()), flits_delivered=int(flits_del.sum()),
        msg_size=size, msg_phase=wl["phase"], msg_sent=sent,
        msg_delivered=flits_del,
        msg_start=np.where(start < BIG, start, -1),
        msg_done=np.where(done_at < BIG, done_at, -1),
        per_cycle_delivered=per_dlv, ep_of_rank=ep_of_rank)
