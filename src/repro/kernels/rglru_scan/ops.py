"""Model-facing wrapper for the RG-LRU scan kernel."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.rglru_scan.kernel import rglru_scan_blocked


def rglru_scan(a, b, *, bt: int = 128, bc: int = 256,
               interpret: Optional[bool] = None):
    """a,b: (B,S,C) gates/inputs (f32) -> recurrence output h (B,S,C)."""
    return rglru_scan_blocked(a.astype(jnp.float32),
                              b.astype(jnp.float32),
                              bt=bt, bc=bc, interpret=interpret)
