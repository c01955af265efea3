"""How every Pallas kernel op picks its execution mode.

Kernel ops take `interpret: bool | None = None`.  None resolves from the
backend at call time: compiled when `jax.default_backend() == "tpu"`,
Pallas interpret mode (the kernel bodies executed on CPU) otherwise.  No
environment variable or import-time state can make a chip run
interpreted.  An explicit `interpret=True/False` always wins, so tests
can pin a mode.
"""

from __future__ import annotations

from typing import Optional

import jax


def default_interpret() -> bool:
    """The backend's Pallas execution mode: False (compiled) on a TPU
    backend, True (interpret) on any other."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Resolve an op's `interpret` argument: None defers to the backend;
    an explicit boolean wins."""
    return default_interpret() if interpret is None else bool(interpret)
