"""One module per routing mode, named by a mix's `mode`.  Each has

- `route(net, src_r, dst_r, occ, draws) -> (inter, phase)`: the path
  choice of new packets at injection (`draws` is this cycle's `draw`
  output, None where the mode or the engine draws nothing);
- optionally `draw(key, n_ep, n_routers, sw)`: what the mode draws from
  the cycle's route key, with jax.random, as the simulator does;
- optionally `hop(net, r, tgt, occ) -> port`: the output port of a
  packet at router `r` heading to router `tgt`, -1 where `r == tgt`;
  without it the minimal first port `port_toward` is taken.

`occ` is the credit view at the start of the cycle (`Network.occupancy`).
"""
