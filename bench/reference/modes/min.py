"""MIN: every packet takes its minimal route to its destination."""

import numpy as np


def route(net, src_r, dst_r, occ, draws):
    return dst_r.copy(), np.ones_like(dst_r)
