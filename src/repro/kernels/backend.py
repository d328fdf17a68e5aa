"""Where the Pallas kernels run.

Every kernel of this package passes ``interpret=interpret_mode()`` to
``pl.pallas_call``.  The kernels are interpreted on the CPU backend
only, which runs the tests bit-accurately without a chip; any other
backend lowers them through Mosaic, so a backend that cannot compile a
kernel fails loudly instead of silently interpreting it.
"""

from __future__ import annotations

import jax

__all__ = ["interpret_mode"]


def interpret_mode() -> bool:
    """True when the default backend is the CPU."""
    return jax.default_backend() == "cpu"
