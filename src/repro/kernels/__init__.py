"""Pallas TPU kernels with jnp oracles.

``enable_flash_attention()`` plugs the Pallas kernel into the model's
attention path (``models.attention.set_attention_impl``); on CPU it runs in
interpret mode, on TPU it compiles to real Mosaic kernels
(``repro.kernels.platform.resolve_interpret``).
"""
from typing import Optional

from repro.kernels.flash_attention import flash_attention  # noqa: F401
from repro.kernels.rglru_scan import rglru_scan  # noqa: F401
from repro.kernels.rwkv6_chunk import wkv6  # noqa: F401


def enable_flash_attention(interpret: Optional[bool] = None, bq: int = 128,
                           bk: int = 128):
    from repro.models.attention import set_attention_impl

    def impl(q, k, v, *, window, softcap, scale):
        return flash_attention(q, k, v, window=window, softcap=softcap,
                               scale=scale, bq=bq, bk=bk,
                               interpret=interpret)

    set_attention_impl(impl)


def disable_flash_attention():
    from repro.models.attention import set_attention_impl
    set_attention_impl(None)
