"""Jitted wrapper: Pallas on TPU, interpret on the CPU (validation)."""
from __future__ import annotations

import functools

import jax

from repro.kernels import default_interpret
from repro.kernels.flash_attention.kernel import flash_attention_pallas


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "attn_softcap", "block_q", "block_kv", "seq_len",
    "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    attn_softcap: float = 0.0, block_q: int = 128,
                    block_kv: int = 128, seq_len: int | None = None,
                    interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, attn_softcap=attn_softcap,
        block_q=block_q, block_kv=block_kv, seq_len=seq_len,
        interpret=interpret)
