"""Ring all-reduce (NCCL's ring): 2(k-1) steps; at step s rank r sends
one chunk to rank r+1, once the chunk it received at step s-1 from rank
r-1 is delivered.  The first k-1 steps are the reduce-scatter (phase
0), the rest the all-gather (phase 1)."""

import numpy as np


def messages(n_ranks: int, chunk_flits: int) -> dict:
    k = n_ranks
    src, dst, dep, phase = [], [], [], []
    for s in range(2 * (k - 1)):                 # reduce-scatter, gather
        for r in range(k):
            src.append(r)
            dst.append((r + 1) % k)
            dep.append(-1 if s == 0 else (s - 1) * k + (r - 1) % k)
            phase.append(0 if s < k - 1 else 1)
    return dict(n_ranks=k, src=np.array(src), dst=np.array(dst),
                size=np.full(len(src), chunk_flits, np.int64),
                dep=np.array(dep)[:, None], phase=np.array(phase))
