"""Where a Pallas kernel runs: compiled by Mosaic on a TPU, in the Pallas
interpreter on the CPU (which has no Mosaic backend)."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` follows the backend: interpret only on the CPU.  An explicit
    ``True``/``False`` wins (tests force the interpreter; compile checks for
    a described chip force Mosaic while the process itself is on the CPU)."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret
