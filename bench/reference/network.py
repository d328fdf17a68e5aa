"""Plain reference of the flit network: input-queued routers, one cycle
at a time, in numpy.

It follows the switch as the simulator documents it (DESIGN.md §5,
engine.py's module docstring), written from that description alone:

- single-flit packets; V virtual channels per input port, each a FIFO
  of Qn slots; the VC of a hop is min(hops so far, V - 1);
- every endpoint owns a source FIFO of Qs slots;
- per cycle, in this order: the credit view (the depth summed over the
  VCs of the input queue each output port feeds), injection at the tail
  of the source FIFOs, then W rounds of allocation over the first W
  packets of every FIFO (the lookahead window), then link traversal and
  dequeue;
- allocation round w looks at window slot w of each FIFO not yet
  granted this cycle.  Packets at their destination router ask to eject;
  up to p ejections per router per cycle, ranked over the network
  queues in request order rotated to start at column (cycle mod P*V),
  with the source queues ranked after them on even cycles and before
  them on odd ones.  Other packets ask for the output port the mode
  gives at this hop (toward the Valiant intermediate until they reach
  it) and are eligible when the downstream FIFO had a free slot at the
  start of the cycle; each output port grants the eligible request with
  the lowest rotating priority (global queue id + 7919*cycle + 131*w)
  mod R, one packet per port per cycle;
- a granted packet leaves its FIFO from the middle if need be (order
  of the rest kept) and joins the tail of the downstream FIFO of its
  VC; its hop count rises by one, and it enters its second phase on
  reaching its intermediate.
- the routing mode is a file of its own (`modes/<mode>.py`): it picks
  each new packet's intermediate at injection and may pick the output
  port at each hop (the minimal first port otherwise);
- a router's source queues are those of its endpoints, in id order; a
  router without endpoints has none.

Random draws are made with jax.random from the run's seed, split per
cycle into (next key, injection, destination, route) keys as the
simulator's documented seeding does, so both sides see the same coins.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from . import by_name

PRIO_CYCLE, PRIO_ROUND = 7919, 131
UNUSED_PORT_OCC = 1 << 30  # credit view of a port with no link
HOPS_MAX = 63


@dataclasses.dataclass(frozen=True)
class Switch:
    vcs: int
    q_net: int
    q_src: int
    lookahead: int
    n_val_candidates: int


class Packets:
    """Packet table: one row per packet ever injected."""

    def __init__(self, capacity: int):
        self.dst = np.zeros(capacity, np.int64)     # destination router
        self.inter = np.zeros(capacity, np.int64)   # Valiant intermediate
        self.born = np.zeros(capacity, np.int64)    # injection cycle
        self.hops = np.zeros(capacity, np.int64)
        self.phase = np.zeros(capacity, np.int64)   # 1 = heading to dst
        self.msg = np.zeros(capacity, np.int64)     # closed-loop message
        self.n = 0

    def add(self, dst, inter, born, phase, msg=None) -> np.ndarray:
        k = len(dst)
        ids = np.arange(self.n, self.n + k)
        if self.n + k > len(self.dst):
            grow = max(k, len(self.dst))
            for f in ("dst", "inter", "born", "hops", "phase", "msg"):
                setattr(self, f, np.concatenate(
                    [getattr(self, f), np.zeros(grow, np.int64)]))
        self.dst[ids], self.inter[ids], self.born[ids] = dst, inter, born
        self.hops[ids], self.phase[ids] = 0, phase
        self.msg[ids] = 0 if msg is None else msg
        self.n += k
        return ids


class Network:
    """Queues of packet ids and one `cycle` of the switch pipeline."""

    def __init__(self, fab, sw: Switch, mode: str, capacity: int = 1 << 16):
        self.fab, self.sw = fab, sw
        self.mode = by_name("modes", mode)
        N, P, V = fab.n_routers, fab.n_ports, sw.vcs
        self.N, self.P, self.V, self.E = N, P, V, fab.n_endpoints
        self.nq = np.full((N, P, V, sw.q_net), -1, np.int64)
        self.ncount = np.zeros((N, P, V), np.int64)
        self.sq = np.full((self.E, sw.q_src), -1, np.int64)
        self.scount = np.zeros(self.E, np.int64)
        self.pk = Packets(capacity)
        self.ep_router = fab.ep_router
        self.NQ = N * P * V
        self.R = self.NQ + self.E

    # -- credit view -------------------------------------------------------
    def occupancy(self) -> np.ndarray:
        f = self.fab
        occ = self.ncount[np.maximum(f.nbr, 0), np.maximum(f.rev, 0),
                          :].sum(axis=-1)                           # [N, P]
        return np.where(f.nbr >= 0, occ, UNUSED_PORT_OCC)

    # -- routing -----------------------------------------------------------
    def route(self, src_r, dst_r, occ, draws):
        """(inter, phase) of new packets, by the mode's injection hook."""
        return self.mode.route(self, src_r, dst_r, occ, draws)

    def hop(self, r, tgt, occ):
        """Output port at router `r` toward `tgt` (-1 at the target)."""
        hop = getattr(self.mode, "hop", None)
        if hop is None:
            return self.fab.port_toward[r, tgt]
        return hop(self, r, tgt, occ)

    def inject(self, want, dst_r, inter, phase, cycle, msg=None):
        e = np.nonzero(want)[0]
        ids = self.pk.add(dst_r[e], inter[e], cycle, phase[e],
                          None if msg is None else msg[e])
        self.sq[e, self.scount[e]] = ids
        self.scount[e] += 1

    # -- one cycle of allocation, traversal and dequeue ----------------------
    def switch(self, cycle: int, on_eject):
        """Allocate, move and dequeue.  `on_eject(ids)` is called once per
        round with the packets ejected in that round."""
        sw, f, pk = self.sw, self.fab, self.pk
        N, P, V, W, E = self.N, self.P, self.V, sw.lookahead, self.E
        PV, p = P * V, f.p
        K = PV + p
        Qn = sw.q_net

        # requests of router r: its P*V network queues, then the source
        # queues of its endpoint slots (`ep_at`, -1: no endpoint, never
        # occupied); window slot w of each
        ncount0 = self.ncount.copy()                       # cycle start
        occ = self.occupancy()
        ep_at = f.ep_at
        has_ep = ep_at >= 0
        e_at = np.maximum(ep_at, 0)
        cnt = np.concatenate([ncount0.reshape(N, PV),
                              np.where(has_ep, self.scount[e_at], 0)],
                             axis=1)                        # [N, K]
        pad = max(0, W - Qn)
        win = np.concatenate([
            np.pad(self.nq[..., :W], ((0, 0),) * 3 + ((0, pad),),
                   constant_values=-1).reshape(N, PV, W),
            np.where(has_ep[..., None], self.sq[e_at, :W], -1)],
            axis=1)                                         # [N, K, W] ids
        # desires of the occupied window slots (the rest are never asked)
        vr, vk, vw = np.nonzero(cnt[:, :, None] > np.arange(W))
        ids = win[vr, vk, vw]
        dst, phase = pk.dst[ids], pk.phase[ids]
        tgt = np.where(phase == 1, dst, pk.inter[ids])
        eject = np.zeros((N, K, W), bool)
        eject[vr, vk, vw] = (dst == vr) & (phase == 1)
        out = np.full((N, K, W), -1)
        out[vr, vk, vw] = self.hop(vr, tgt, occ)            # -1 at the target
        vc = np.zeros((N, K, W), np.int64)
        vc[vr, vk, vw] = np.minimum(pk.hops[ids], V - 1)
        o = np.maximum(out[vr, vk, vw], 0)
        down = ncount0[f.nbr[vr, o], f.rev[vr, o], vc[vr, vk, vw]]
        space = np.zeros((N, K, W), bool)
        space[vr, vk, vw] = (out[vr, vk, vw] >= 0) & (down < Qn)

        qid = np.concatenate([
            np.arange(N)[:, None] * PV + np.arange(PV)[None, :],
            self.NQ + ep_at], axis=1)                       # [N, K]
        rot0 = (qid + cycle * PRIO_CYCLE) % self.R
        free = np.ones((N, K), bool)
        chan_free = np.ones((N, P), bool)
        budget = np.full(N, p)
        granted = np.full((N, K), -1)        # slot granted to each request
        win_req = np.full((N, P), -1)        # request each port carries
        s_rot = cycle % PV
        net_first = cycle % 2 == 0
        rows = np.arange(N)

        for w in range(W):
            valid = free & (cnt > w)
            ej = valid & eject[:, :, w]
            # ejection ranks: network queues in rotated order, endpoints
            # after them (even cycles) or before them (odd cycles)
            ej_n = np.roll(ej[:, :PV], -s_rot, axis=1)
            rank_n = np.roll(np.cumsum(ej_n, axis=1) - ej_n, s_rot, axis=1)
            ej_s = ej[:, PV:]
            rank_s = np.cumsum(ej_s, axis=1) - ej_s
            n_n = ej_n.sum(axis=1, keepdims=True)
            n_s = ej_s.sum(axis=1, keepdims=True)
            if net_first:
                rank = np.concatenate([rank_n, rank_s + n_n], axis=1)
            else:
                rank = np.concatenate([rank_n + n_s, rank_s], axis=1)
            g_ej = ej & (rank < budget[:, None])
            budget = budget - g_ej.sum(axis=1)
            # port arbitration: lowest rotating priority per output port
            elig = valid & ~eject[:, :, w] & space[:, :, w]
            elig &= chan_free[rows[:, None], np.maximum(out[:, :, w], 0)]
            r_i, k_i = np.nonzero(elig)
            port = out[r_i, k_i, w]
            prio = (rot0[r_i, k_i] + w * PRIO_ROUND) % self.R
            order = np.lexsort((prio, port, r_i))
            r_i, k_i, port = r_i[order], k_i[order], port[order]
            first = np.ones(len(r_i), bool)
            first[1:] = (r_i[1:] != r_i[:-1]) | (port[1:] != port[:-1])
            r_w, k_w, port_w = r_i[first], k_i[first], port[first]
            chan_free[r_w, port_w] = False
            win_req[r_w, port_w] = k_w
            granted[r_w, k_w] = w
            gr, gk = np.nonzero(g_ej)
            granted[gr, gk] = w
            free[gr, gk] = False
            free[r_w, k_w] = False
            on_eject(win[gr, gk, w])

        # ---- link traversal: the packet each port carries moves to the
        # tail of the downstream FIFO of its VC
        r_c, o_c = np.nonzero(win_req >= 0)
        k_c = win_req[r_c, o_c]
        w_c = granted[r_c, k_c]
        moving = win[r_c, k_c, w_c]
        vc_c = vc[r_c, k_c, w_c]
        nr, nport = f.nbr[r_c, o_c], f.rev[r_c, o_c]
        pk.hops[moving] = np.minimum(pk.hops[moving] + 1, HOPS_MAX)
        pk.phase[moving] |= (nr == pk.inter[moving])

        # ---- dequeue every granted packet (network and source FIFOs)
        gr, gk = np.nonzero(granted >= 0)
        slot = granted[gr, gk]
        net = gk < PV
        self._remove(self.nq.reshape(N * PV, Qn), self.ncount.reshape(-1),
                     gr[net] * PV + gk[net], slot[net])
        self._remove(self.sq, self.scount,
                     ep_at[gr[~net], gk[~net] - PV], slot[~net])
        # arrivals after the dequeue, at the new tail
        tail = self.ncount[nr, nport, vc_c]
        self.nq[nr, nport, vc_c, tail] = moving
        self.ncount[nr, nport, vc_c] += 1

    @staticmethod
    def _remove(fifo, count, rows, slot):
        """Take slot `slot` out of FIFO `rows`; later packets move up."""
        if len(rows) == 0:
            return
        depth = int(count[rows].max())                 # occupied prefix
        j = np.arange(depth)
        take = np.minimum(j[None, :] + (j[None, :] >= slot[:, None]),
                          depth - 1)
        moved = fifo[rows[:, None], take]
        moved[j[None, :] >= (count[rows] - 1)[:, None]] = -1
        fifo[rows, :depth] = moved
        count[rows] -= 1
