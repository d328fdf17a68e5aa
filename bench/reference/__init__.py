"""Plain reference of the flit simulator, independent of `repro`:
the fabrics (`fabric`), the switch pipeline (`network`) and the runs
the benchmark checks (`runs`).  It imports nothing of the program.

What varies from cell to cell is found by name, one file each:
a fabric family in `families/<family>.py`, a routing mode in
`modes/<mode>.py` and a collective in `collectives/<kind>.py`."""

import importlib


def by_name(kind: str, name: str):
    """The module `<kind>/<name>.py` of this package (kind is
    `families`, `modes` or `collectives`)."""
    path = f"{__name__}.{kind}.{name}"
    try:
        return importlib.import_module(path)
    except ModuleNotFoundError as e:
        if e.name != path:
            raise
        raise ValueError(f"the reference has no {kind} file {name!r} "
                         f"({kind}/{name}.py)") from None
