"""UGAL-L (Kim et al., ISCA 2008, as the paper's §IV-C takes it): at
injection, score the minimal route against C random Valiant
intermediates by hops times the depth of the first output queue, and
keep the minimal route on ties.  Candidates equal to the source or the
destination router move up by one, then by two (mod N)."""

import numpy as np

OCC_CAP = 1 << 20          # occupancy cap in UGAL scores


def draw(key, n_ep, n_routers, sw):
    """C Valiant candidates per endpoint from the cycle's route key."""
    import jax

    return jax.random.randint(key, (n_ep, sw.n_val_candidates), 0,
                              n_routers)


def route(net, src_r, dst_r, occ, cands):
    if cands is None:
        raise ValueError("UGAL-L needs the open loop's Valiant candidates")
    N, f = net.N, net.fab
    c = cands.astype(np.int64)
    for bump in (1, 2):
        bad = (c == src_r[:, None]) | (c == dst_r[:, None])
        c = np.where(bad, (c + bump) % N, c)

    def first_occ(s, t):
        o = f.port_toward[s, t]
        return np.where(o >= 0,
                        np.minimum(occ[s, np.maximum(o, 0)], OCC_CAP), 0)

    score_min = f.dist[src_r, dst_r] * first_occ(src_r, dst_r)
    s2 = np.broadcast_to(src_r[:, None], c.shape)
    score_val = ((f.dist[s2, c] + f.dist[c, dst_r[:, None]])
                 * first_occ(s2, c))
    scores = np.concatenate([score_min[:, None], score_val], axis=1)
    best = scores.argmin(axis=1)                 # first minimum: MIN on ties
    inter = np.where(best == 0, dst_r,
                     c[np.arange(len(c)), np.maximum(best - 1, 0)])
    return inter, (best == 0).astype(np.int64)
