"""Slim Fly MMS graph (Besta and Hoefler, arXiv:1912.08968 §II-B) for a
prime q = 4w + delta, delta in {+1, -1}: routers (s, a, b) in
{0,1} x F_q x F_q, numbered s*q^2 + a*q + b;
(0,x,y) ~ (0,x,y') iff y - y' in X, (1,m,c) ~ (1,m,c') iff
c - c' in X', (0,x,y) ~ (1,m,c) iff y = m*x + c.  With xi the
smallest primitive element: delta = +1 takes X = even powers of xi
and X' = odd powers; delta = -1 takes X = {+-xi^(2i)} and
X' = {+-xi^(2i+1)} for 0 <= i < w.  Endpoints per router:
p = ceil(k' N / (2N - k' - 2)), on every router."""

from __future__ import annotations

import numpy as np


def _is_prime(q: int) -> bool:
    return q >= 2 and all(q % d for d in range(2, int(q ** 0.5) + 1))


def build(q: int) -> tuple:
    """(adjacency [2q^2, 2q^2] bool, ep_router) of the MMS graph."""
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
    return adj, np.repeat(np.arange(n), p)
