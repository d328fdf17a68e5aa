"""One module per fabric family, named by a configuration's
`topology.family`.  Each has `build(**params) -> (adj, ep_router)`:
the router adjacency ([N, N] bool) and the router of each endpoint,
endpoints numbered router by router."""
