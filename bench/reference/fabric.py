"""Plain reference fabrics: the graph, its ports and its minimal routes.

Built from the published constructions alone, with nothing taken from
the simulator under test:

- Slim Fly MMS graph (Besta and Hoefler, arXiv:1912.08968 §II-B) for a
  prime q = 4w + delta, delta in {+1, -1}: routers (s, a, b) in
  {0,1} x F_q x F_q, numbered s*q^2 + a*q + b;
  (0,x,y) ~ (0,x,y') iff y - y' in X, (1,m,c) ~ (1,m,c') iff
  c - c' in X', (0,x,y) ~ (1,m,c) iff y = m*x + c.  With xi the
  smallest primitive element: delta = +1 takes X = even powers of xi
  and X' = odd powers; delta = -1 takes X = {+-xi^(2i)} and
  X' = {+-xi^(2i+1)} for 0 <= i < w.  Endpoints per router:
  p = ceil(k' N / (2N - k' - 2)).
- Balanced Dragonfly (Kim et al., ISCA 2008): a = 2h routers per
  group, p = h endpoints per router, g = a*h + 1 groups; router
  grp*a + r; each group a clique; the global link between groups
  u < v at offset d = v - u leaves u from router (d-1)//h and enters v
  at router (g-1-d)//h.

Ports of a router are its neighbours in ascending id order.  The
minimal next hop toward a target is the lowest-numbered port whose
neighbour lies one hop closer.  Endpoints hang p to a router, in
router order.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Fabric:
    nbr: np.ndarray          # [N, P] neighbour on each port
    rev: np.ndarray          # [N, P] the port at that neighbour leading back
    dist: np.ndarray         # [N, N] hops
    port_toward: np.ndarray  # [N, N] first port of the minimal route (-1 self)
    p: int                   # endpoints per router

    @property
    def n_routers(self) -> int:
        return self.nbr.shape[0]

    @property
    def n_ports(self) -> int:
        return self.nbr.shape[1]

    @property
    def n_endpoints(self) -> int:
        return self.n_routers * self.p

    @property
    def ep_router(self) -> np.ndarray:
        return np.repeat(np.arange(self.n_routers), self.p)


def _is_prime(q: int) -> bool:
    return q >= 2 and all(q % d for d in range(2, int(q ** 0.5) + 1))


def slimfly_adjacency(q: int) -> tuple:
    """(adjacency [2q^2, 2q^2] bool, p) of the MMS graph for prime q."""
    if not _is_prime(q) or q % 4 not in (1, 3):
        raise ValueError(f"reference Slim Fly needs a prime q = 4w +- 1: {q}")
    delta = 1 if q % 4 == 1 else -1
    xi = next(x for x in range(2, q)
              if len({pow(x, e, q) for e in range(1, q)}) == q - 1)
    if delta == 1:
        X = {pow(xi, 2 * i, q) for i in range((q - 1) // 2)}
        Xp = {pow(xi, 2 * i + 1, q) for i in range((q - 1) // 2)}
    else:
        w = (q + 1) // 4
        X = {s * pow(xi, 2 * i, q) % q for i in range(w) for s in (1, -1)}
        Xp = {s * pow(xi, 2 * i + 1, q) % q for i in range(w)
              for s in (1, -1)}
    n = 2 * q * q
    adj = np.zeros((n, n), dtype=bool)
    for a in range(q):
        for y in range(q):
            for y2 in range(q):
                if (y - y2) % q in X:
                    adj[a * q + y, a * q + y2] = True
                if (y - y2) % q in Xp:
                    adj[q * q + a * q + y, q * q + a * q + y2] = True
    for m in range(q):
        for x in range(q):
            for c in range(q):
                u, v = x * q + (m * x + c) % q, q * q + m * q + c
                adj[u, v] = adj[v, u] = True
    kprime = (3 * q - delta) // 2
    if not (adj.sum(axis=1) == kprime).all():
        raise AssertionError(f"MMS q={q}: degree is not k'={kprime}")
    p = -(-kprime * n // (2 * n - kprime - 2))
    return adj, p


def dragonfly_adjacency(h: int) -> tuple:
    """(adjacency bool, p) of the balanced Dragonfly with h global links."""
    a, p = 2 * h, h
    g = a * h + 1
    n = a * g
    adj = np.zeros((n, n), dtype=bool)
    for grp in range(g):
        adj[grp * a:(grp + 1) * a, grp * a:(grp + 1) * a] = True
    for u in range(g):
        for d in range(1, g):
            v = (u + d) % g
            if u < v:
                ru, rv = u * a + (d - 1) // h, v * a + (g - 1 - d) // h
                adj[ru, rv] = adj[rv, ru] = True
    np.fill_diagonal(adj, False)
    return adj, p


def fabric(adj: np.ndarray, p: int) -> Fabric:
    """Ports, hop distances and minimal first ports of a healthy graph."""
    n = adj.shape[0]
    deg = adj.sum(axis=1)
    P = int(deg.max())
    if not (deg == P).all():
        raise ValueError("the reference handles regular fabrics only")
    nbr = np.stack([np.nonzero(adj[r])[0] for r in range(n)])     # sorted
    port_of = np.full((n, n), -1, dtype=np.int64)
    port_of[np.arange(n)[:, None], nbr] = np.arange(P)[None, :]
    rev = port_of[nbr, np.arange(n)[:, None]]
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
    closer = dist[nbr, :] == (dist[:, None, :] - 1)               # [N, P, N]
    port_toward = np.where(closer.any(axis=1), closer.argmax(axis=1), -1)
    return Fabric(nbr=nbr, rev=rev, dist=dist, port_toward=port_toward, p=p)


def build(topology: dict) -> Fabric:
    """The fabric a configuration's `topology` entry names."""
    family = topology["family"]
    if family == "slimfly":
        return fabric(*slimfly_adjacency(int(topology["q"])))
    if family == "dragonfly":
        return fabric(*dragonfly_adjacency(int(topology["h"])))
    raise ValueError(f"no reference fabric for {family!r}")
