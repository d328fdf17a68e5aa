"""Roofline share of the `alloc_rounds` kernel: the least time its
logical bytes take at the chip's HBM peak, over its measured time.

The kernel does no matrix work, so its roofline is bytes over HBM
bandwidth (819 GB/s on a TPU v5e, `bench/peaks.json`).  The bytes are
the logical int32 inputs and outputs of one cycle's allocation, each
counted once, at the cell's logical sizes, not the arrays as the code
pads them: a change of layout leaves the count as it is."""

from bench import peaks, trace


def logical_bytes(s: dict) -> int:
    """Bytes one cycle's allocation must read and write, one lane."""
    N, PV, PE, W, P = s["N"], s["P"] * s["V"], s["PE"], s["W"], s["P"]
    inputs = (3 * N * PV * W          # desired port, eject flag, space
              + N * PV                # network queue depths
              + 3 * N * PE * W        # the same for the source queues
              + N * PE                # source queue depths
              + N + 1)                # endpoint block per router, cycle
    outputs = (2 * N * PV             # granted slot, channel and eject
               + 2 * N * PE
               + N * P)               # winning request per output port
    return 4 * (inputs + outputs)


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    seconds, _ = t.op_seconds(trace.KERNELS["alloc_rounds"])
    if seconds <= 0:
        return None
    s = ctx["sizes"]
    # one kernel call per simulated cycle and lane
    cycles = sum(c["work"] for c in ctx["window"]["calls"]) // s["N"]
    least = (cycles * logical_bytes(s)
             / peaks.peak(ctx["device"]["kind"], "hbm_bytes_per_s"))
    return 100.0 * least / seconds
