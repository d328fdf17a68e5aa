"""One module per collective, named by a mix's `collective`.  Each has
`messages(**args) -> dict(n_ranks, src, dst, size, dep, phase)`: the
number of ranks, and the source and destination rank, size in flits,
dependencies ([M, D] message ids, -1 for none; a dependency has a lower
id) and phase of every message, in message-id order."""
