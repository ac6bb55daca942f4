"""Jitted public wrappers for the Pallas kernels.

``interpret=None`` (the default) defers to
:func:`repro.kernels.default_interpret`: compiled on a TPU, interpreted on
any other backend.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from . import default_interpret
from .buzen import buzen_pallas
from .decode_attention import decode_attention_pallas
from .flash_attention import flash_attention_pallas
from .fused_update import fused_async_update as _fused_update


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None):
    interp = default_interpret() if interpret is None else interpret
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interp)


@partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(q, k_cache, v_cache, length, *, block_s: int = 256,
                     interpret: Optional[bool] = None):
    interp = default_interpret() if interpret is None else interpret
    return decode_attention_pallas(q, k_cache, v_cache, length,
                                   block_s=block_s, interpret=interp)


@partial(jax.jit, static_argnames=("m_max", "interpret"))
def buzen_log_Z(log_rho, log_gamma_total, m_max: int,
                interpret: Optional[bool] = None):
    interp = default_interpret() if interpret is None else interpret
    return buzen_pallas(log_rho, log_gamma_total, m_max, interpret=interp)


@partial(jax.jit, static_argnames=("interpret",))
def fused_async_update(params, grads, scale,
                       interpret: Optional[bool] = None):
    interp = default_interpret() if interpret is None else interpret
    return _fused_update(params, grads, scale, interpret=interp)
