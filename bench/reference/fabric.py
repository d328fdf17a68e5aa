"""Plain reference fabrics: the graph, its ports and its minimal routes.

Built from the published constructions alone, with nothing taken from
the simulator under test.  Each family is a file of its own,
`families/<family>.py`, giving the router adjacency and the router of
each endpoint; a configuration's `topology` entry names the family and
its parameters.

Ports of a router are its neighbours in ascending id order; a router
with fewer neighbours than the widest one has its last ports unused
(-1).  The minimal next hop toward a target is the lowest-numbered port
whose neighbour lies one hop closer.  Endpoints are numbered router by
router, and a router may hold none.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from . import by_name


@dataclasses.dataclass
class Fabric:
    nbr: np.ndarray          # [N, P] neighbour on each port (-1 unused)
    rev: np.ndarray          # [N, P] the port at that neighbour leading back
    dist: np.ndarray         # [N, N] hops
    port_toward: np.ndarray  # [N, N] first port of the minimal route (-1 self)
    ep_router: np.ndarray    # [E] router of each endpoint, ascending
    ep_at: np.ndarray        # [N, p] endpoint on each router slot (-1 none)

    @property
    def n_routers(self) -> int:
        return self.nbr.shape[0]

    @property
    def n_ports(self) -> int:
        return self.nbr.shape[1]

    @property
    def n_endpoints(self) -> int:
        return len(self.ep_router)

    @property
    def p(self) -> int:
        """Endpoint slots per router: the most endpoints on one router."""
        return self.ep_at.shape[1]


def fabric(adj: np.ndarray, ep_router: np.ndarray) -> Fabric:
    """Ports, hop distances and minimal first ports of a healthy graph."""
    n = adj.shape[0]
    ep_router = np.asarray(ep_router, dtype=np.int64)
    if (np.diff(ep_router) < 0).any():
        raise ValueError("endpoints must be numbered router by router")
    P = int(adj.sum(axis=1).max())
    nbr = np.full((n, P), -1, dtype=np.int64)
    for r in range(n):
        nb = np.nonzero(adj[r])[0]                                 # sorted
        nbr[r, :len(nb)] = nb
    live = nbr >= 0
    port_of = np.full((n, n), -1, dtype=np.int64)
    rr, oo = np.nonzero(live)
    port_of[rr, nbr[rr, oo]] = oo
    rev = np.where(live, port_of[np.maximum(nbr, 0), np.arange(n)[:, None]],
                   -1)
    # breadth-first hop distances, one frontier expansion per hop
    dist = np.full((n, n), -1, dtype=np.int64)
    np.fill_diagonal(dist, 0)
    frontier = np.eye(n, dtype=bool)
    a32 = adj.astype(np.float32)
    hop = 0
    while frontier.any():
        hop += 1
        reach = (frontier.astype(np.float32) @ a32) > 0
        frontier = reach & (dist < 0)
        dist[frontier] = hop
    if (dist < 0).any():
        raise ValueError("disconnected fabric")
    closer = live[:, :, None] & (dist[np.maximum(nbr, 0), :]
                                 == (dist[:, None, :] - 1))        # [N, P, N]
    port_toward = np.where(closer.any(axis=1), closer.argmax(axis=1), -1)
    # endpoint slots: router r's endpoints in id order, then -1
    count = np.bincount(ep_router, minlength=n)
    first = np.concatenate([[0], np.cumsum(count)[:-1]])
    slot = np.arange(max(1, int(count.max())))
    ep_at = np.where(slot[None, :] < count[:, None],
                     first[:, None] + slot[None, :], -1)
    return Fabric(nbr=nbr, rev=rev, dist=dist, port_toward=port_toward,
                  ep_router=ep_router, ep_at=ep_at)


def build(topology: dict) -> Fabric:
    """The fabric a configuration's `topology` entry names:
    `families/<family>.py` built with the entry's other keys."""
    params = {k: v for k, v in topology.items() if k != "family"}
    return fabric(*by_name("families", topology["family"]).build(**params))
