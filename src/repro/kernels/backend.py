"""How a Pallas kernel runs: interpreted on the CPU backend, compiled on
every other backend (the TPU).  There is no way to interpret on a chip."""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """`None` picks from the default backend; True/False force a mode (the
    compile tests force False to compile for a described, absent TPU)."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret
