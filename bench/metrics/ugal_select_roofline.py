"""Roofline share of the `ugal_select` kernel: the least time its
logical bytes take at the chip's HBM peak, over its measured time.

No matrix work, so bytes over HBM bandwidth (`bench/peaks.json`).  The
bytes are the logical int32 inputs and output of one cycle's UGAL
choice, each counted once: the minimal and Valiant path lengths and
occupancies of every endpoint's new packet, and the choice."""

from bench import peaks, trace


def logical_bytes(s: dict) -> int:
    """Bytes one cycle's UGAL choice must read and write, one lane."""
    E, C = s["E"], s["C"]
    return 4 * (2 * E                 # minimal path length and occupancy
                + 2 * E * C           # the same for C Valiant candidates
                + E)                  # the choice


def read(ctx):
    t = ctx["trace"]
    s = ctx["sizes"]
    if t is None or not s["ugal"]:
        return None
    seconds, _ = t.op_seconds(trace.KERNELS["ugal_select"])
    if seconds <= 0:
        return None
    # one kernel call per simulated cycle and lane
    cycles = sum(c["work"] for c in ctx["window"]["calls"]) // s["N"]
    least = (cycles * logical_bytes(s)
             / peaks.peak(ctx["device"]["kind"], "hbm_bytes_per_s"))
    return 100.0 * least / seconds
