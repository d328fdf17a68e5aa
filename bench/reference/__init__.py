"""Plain reference of the flit simulator, independent of `repro`:
the fabrics (`fabric`), the switch pipeline (`network`) and the runs
the benchmark checks (`runs`).  It imports nothing of the program."""
