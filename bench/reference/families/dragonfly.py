"""Balanced Dragonfly (Kim et al., ISCA 2008): a = 2h routers per
group, p = h endpoints per router, g = a*h + 1 groups; router
grp*a + r; each group a clique; the global link between groups
u < v at offset d = v - u leaves u from router (d-1)//h and enters v
at router (g-1-d)//h."""

from __future__ import annotations

import numpy as np


def build(h: int) -> tuple:
    """(adjacency bool, ep_router) of the balanced Dragonfly with h
    global links per router."""
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
    return adj, np.repeat(np.arange(n), p)
