"""Jitted SSD wrapper: Pallas chunk kernel + JAX inter-chunk recurrence."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.ssd_scan.kernel import ssd_chunk_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, A, Bm, Cm, *, chunk: int = 64, interpret: bool | None = None):
    """Chunked SSD with the Pallas intra-chunk kernel.

    x: (B,S,H,P); dt: (B,S,H); A: (H,); Bm/Cm: (B,S,N).
    Returns (y (B,S,H,P), final state (B,H,P,N)).
    """
    if interpret is None:
        interpret = default_interpret()
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    L = min(chunk, S)
    assert S % L == 0
    C = S // L

    a = (dt * A).reshape(B, C, L, H)
    cum = jnp.cumsum(a, axis=2)
    total = cum[:, :, -1]                                     # (B,C,H)
    xr = x.reshape(B, C, L, H, P)
    dtr = dt.reshape(B, C, L, H)
    Br = Bm.reshape(B, C, L, N)
    Cr = Cm.reshape(B, C, L, N)

    y_intra, Sc = ssd_chunk_pallas(xr, dtr, cum, Br, Cr, interpret=interpret)

    def step(st, inp):
        Sc_c, tot_c = inp
        out_st = st
        st_new = st * jnp.exp(tot_c)[:, :, None, None] + Sc_c
        return st_new, out_st

    st0 = jnp.zeros((B, H, P, N), jnp.float32)
    st_final, st_in = jax.lax.scan(
        step, st0, (jnp.moveaxis(Sc, 1, 0), jnp.moveaxis(total, 1, 0)))
    st_in = jnp.moveaxis(st_in, 0, 1)                         # (B,C,H,P,N)

    y_inter = jnp.einsum("bcin,bchpn,bcih->bcihp", Cr, st_in, jnp.exp(cum))
    y = (y_intra + y_inter).reshape(B, S, H, P)
    return y, st_final
