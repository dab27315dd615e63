"""Jitted wrapper for the decode-attention kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels import default_interpret
from repro.kernels.decode_attention.kernel import decode_attention_pallas


@functools.partial(jax.jit, static_argnames=(
    "block_kv", "attn_softcap", "window", "interpret"))
def decode_attention(q, k_cache, v_cache, valid_len, *, block_kv: int = 512,
                     attn_softcap: float = 0.0, window: int = 0,
                     interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    return decode_attention_pallas(
        q, k_cache, v_cache, valid_len, block_kv=block_kv,
        attn_softcap=attn_softcap, window=window, interpret=interpret)
