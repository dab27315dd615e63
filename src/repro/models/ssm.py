"""Mamba2 (SSD — state-space duality) block: chunked train/prefill path and
O(1)-state decode recurrence.

Chunked algorithm (Dao & Gu, arXiv:2405.21060 §6): sequence split into chunks
of length L; intra-chunk term is a small quadratic attention-like matmul with
decay mask; inter-chunk term flows through a scan over per-chunk states.
All SSM math in float32. The intra-chunk matmul is the Pallas target
(kernels/ssd_scan); this module is the production XLA path.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import Builder, rms_norm


def ssm_dims(cfg):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    return d_inner, n_heads, s.head_dim, s.state_dim


def ssm_params(b: Builder, cfg):
    d = cfg.d_model
    d_inner, H, Pd, N = ssm_dims(cfg)
    W = cfg.ssm.conv_width
    return {
        "wz": b.p((d, H, Pd), ("embed", "ssm_heads", "head_dim")),
        "wx": b.p((d, H, Pd), ("embed", "ssm_heads", "head_dim")),
        "wB": b.p((d, N), ("embed", "ssm_state")),
        "wC": b.p((d, N), ("embed", "ssm_state")),
        "wdt": b.p((d, H), ("embed", "ssm_heads")),
        "conv_x": b.p((W, H, Pd), ("conv", "ssm_heads", "head_dim"),
                      init="uniform", scale=1.0 / math.sqrt(W)),
        "conv_B": b.p((W, N), ("conv", "ssm_state"),
                      init="uniform", scale=1.0 / math.sqrt(W)),
        "conv_C": b.p((W, N), ("conv", "ssm_state"),
                      init="uniform", scale=1.0 / math.sqrt(W)),
        "A_log": b.p((H,), ("ssm_heads",), init="zeros"),
        "dt_bias": b.p((H,), ("ssm_heads",), init="zeros"),
        "D": b.p((H,), ("ssm_heads",), init="ones"),
        "gate_norm": b.p((H, Pd), ("ssm_heads", "head_dim"), init="ones"),
        "w_out": b.p((H, Pd, d), ("ssm_heads", "head_dim", "embed")),
    }


def _causal_conv(x, w):
    """x: (B,S,C...), w: (W,C...) depthwise causal conv along S."""
    W = w.shape[0]
    pad = jnp.pad(x, [(0, 0), (W - 1, 0)] + [(0, 0)] * (x.ndim - 2))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    S = x.shape[1]
    for i in range(W):
        out = out + pad[:, i:i + S].astype(jnp.float32) * w[i].astype(jnp.float32)
    return out.astype(x.dtype)


_IN_PROJ = ("wz", "wx", "wB", "wC", "wdt")


def ssm_serve_params(p):
    """One SSM block's params in their served form.

    The five input projections ``wz``, ``wx`` (d, H, Pd), ``wB``, ``wC``
    (d, N) and ``wdt`` (d, H) become one output-major ``in_proj`` of shape
    (2·H·Pd + 2N + H, d), rows in the order z, x, B, C, dt, as the
    published Mamba2 checkpoint stores ``in_proj.weight``. Leading (layer)
    axes are kept; every other leaf is unchanged. Stored (d, H, Pd), the
    64-wide minor dimension is padded to 128 lanes on a TPU and every
    decode step relays the stack out for its d-minor dot; output-major
    with d minor it is read as it is stored.
    """
    d = p["wB"].shape[-2]
    lead = p["wB"].shape[:-2]
    cols = jnp.concatenate(
        [p[k].reshape(*lead, d, -1) for k in _IN_PROJ], axis=-1)
    out = {k: v for k, v in p.items() if k not in _IN_PROJ}
    out["in_proj"] = jnp.swapaxes(cols, -1, -2)
    return out


def _project(p, u, cfg, ctx):
    """u: (B,S,d) -> z,x,(B,S,H,P), Bm,Cm (B,S,N), dt (B,S,H) pre-activation.

    Reads either form of the block's params: the published five
    projections (``ssm_params``) or the served ``in_proj``
    (``ssm_serve_params``)."""
    if "in_proj" in p:
        _, H, Pd, N = ssm_dims(cfg)
        zxbcdt = jnp.einsum("bsd,nd->bsn", u, p["in_proj"])
        z, x, Bm, Cm, dt = jnp.split(
            zxbcdt, [H * Pd, 2 * H * Pd, 2 * H * Pd + N, 2 * H * Pd + 2 * N],
            axis=-1)
        z = z.reshape(*z.shape[:-1], H, Pd)
        x = x.reshape(*x.shape[:-1], H, Pd)
    else:
        z = jnp.einsum("bsd,dhp->bshp", u, p["wz"])
        x = jnp.einsum("bsd,dhp->bshp", u, p["wx"])
        Bm = jnp.einsum("bsd,dn->bsn", u, p["wB"])
        Cm = jnp.einsum("bsd,dn->bsn", u, p["wC"])
        dt = jnp.einsum("bsd,dh->bsh", u, p["wdt"])
    # seq gathered inside the SSM block (SP); heads are the sharded dim
    x = ctx.constrain(x, "act_batch", None, "act_heads", None)
    z = ctx.constrain(z, "act_batch", None, "act_heads", None)
    return z, x, Bm, Cm, dt


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int):
    """Chunked SSD. x:(B,S,H,P) f32, dt:(B,S,H) f32 (post-softplus),
    A:(H,) f32 (negative), Bm/Cm:(B,S,N) f32. Returns y:(B,S,H,P) f32 and
    final state (B,H,P,N)."""
    B_, S, H, Pd = x.shape
    N = Bm.shape[-1]
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    C_ = S // L

    a = dt * A                                   # (B,S,H) log-decay, <= 0
    xr = x.reshape(B_, C_, L, H, Pd)
    dtr = dt.reshape(B_, C_, L, H)
    ar = a.reshape(B_, C_, L, H)
    Br = Bm.reshape(B_, C_, L, N)
    Cr = Cm.reshape(B_, C_, L, N)

    cum = jnp.cumsum(ar, axis=2)                 # inclusive (B,C,L,H)
    total = cum[:, :, -1]                        # (B,C,H)

    # ---- intra-chunk (quadratic within chunk, causal + decay mask) ----
    G = jnp.einsum("bcin,bcjn->bcij", Cr, Br)    # (B,C,L,L)
    dec = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # (B,C,i,j,H)
    ii = jnp.arange(L)
    causal = ii[:, None] >= ii[None, :]
    dec = jnp.where(causal[None, None, :, :, None], dec, -jnp.inf)
    Wt = G[..., None] * jnp.exp(dec) * dtr[:, :, None, :, :]   # (B,C,i,j,H)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", Wt, xr)

    # ---- per-chunk end states ----
    dec_end = jnp.exp(total[:, :, None, :] - cum)          # (B,C,L,H)
    Sc = jnp.einsum("bclh,bcln,bclhp->bchpn", dtr * dec_end, Br, xr)

    # ---- inter-chunk scan ----
    def step(st, inp):
        Sc_c, tot_c = inp                        # (B,H,P,N), (B,H)
        out_st = st                              # state entering this chunk
        st_new = st * jnp.exp(tot_c)[:, :, None, None] + Sc_c
        return st_new, out_st

    st0 = jnp.zeros((B_, H, Pd, N), jnp.float32)
    Sc_t = jnp.moveaxis(Sc, 1, 0)
    tot_t = jnp.moveaxis(total, 1, 0)
    st_final, st_in = jax.lax.scan(step, st0, (Sc_t, tot_t))
    st_in = jnp.moveaxis(st_in, 0, 1)            # (B,C,H,P,N) state at chunk start

    y_inter = jnp.einsum("bcin,bchpn,bcih->bcihp",
                         Cr, st_in, jnp.exp(cum))
    y = (y_intra + y_inter).reshape(B_, S, H, Pd)
    return y, st_final


def _conv_tail(x_raw, width: int):
    """Last (width-1) pre-conv inputs along S, left-padded with zeros."""
    B = x_raw.shape[0]
    S = x_raw.shape[1]
    W = width - 1
    pad = max(0, W - S)
    tail = x_raw[:, max(0, S - W):]
    if pad:
        widths = [(0, 0), (pad, 0)] + [(0, 0)] * (x_raw.ndim - 2)
        tail = jnp.pad(tail, widths)
    return tail.astype(jnp.float32)


def ssm_block(p, u, cfg, ctx, *, return_state: bool = False):
    """Full mamba2 block forward (train/prefill). u: (B,S,d) -> (B,S,d).

    With return_state=True also returns the decode state after the last
    position (SSD running state + causal-conv input tails).
    """
    s = cfg.ssm
    z, x, Bm, Cm, dt = _project(p, u, cfg, ctx)
    x_raw, B_raw, C_raw = x, Bm, Cm
    x = jax.nn.silu(_causal_conv(x, p["conv_x"]))
    Bm = jax.nn.silu(_causal_conv(Bm, p["conv_B"]))
    Cm = jax.nn.silu(_causal_conv(Cm, p["conv_C"]))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    dt = jnp.clip(dt, s.dt_min, s.dt_max)
    y, st_final = ssd_chunked(x.astype(jnp.float32), dt, A,
                              Bm.astype(jnp.float32), Cm.astype(jnp.float32),
                              s.chunk_size)
    y = y + p["D"].astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)
    y = y.astype(u.dtype) * jax.nn.silu(z)
    y = rms_norm(y, p["gate_norm"], cfg.norm_eps)
    out = jnp.einsum("bshp,hpd->bsd", y, p["w_out"])
    out = ctx.constrain(out, "act_batch", "act_seq", "act_embed")
    if not return_state:
        return out
    W = s.conv_width
    state = {"ssd": st_final,
             "conv_x": _conv_tail(x_raw, W),
             "conv_B": _conv_tail(B_raw, W),
             "conv_C": _conv_tail(C_raw, W)}
    return out, state


# ---------------------------------------------------------------------------
# Decode (single step): O(1) state recurrence
# ---------------------------------------------------------------------------

def ssm_init_state(cfg, batch):
    d_inner, H, Pd, N = ssm_dims(cfg)
    W = cfg.ssm.conv_width
    return {
        "ssd": jnp.zeros((batch, H, Pd, N), jnp.float32),
        "conv_x": jnp.zeros((batch, W - 1, H, Pd), jnp.float32),
        "conv_B": jnp.zeros((batch, W - 1, N), jnp.float32),
        "conv_C": jnp.zeros((batch, W - 1, N), jnp.float32),
    }


def ssm_state_axes(cfg):
    from repro.distributed.sharding import axes
    return {
        "ssd": axes("cache_batch", "ssm_heads", None, None),
        "conv_x": axes("cache_batch", None, "ssm_heads", None),
        "conv_B": axes("cache_batch", None, None),
        "conv_C": axes("cache_batch", None, None),
    }


def _conv_step(cache, xt, w):
    """cache: (B,W-1,C...), xt: (B,C...) -> (out (B,C...), new cache)."""
    hist = jnp.concatenate([cache, xt[:, None].astype(cache.dtype)], axis=1)
    out = jnp.einsum("bw...,w...->b...", hist.astype(jnp.float32),
                     w.astype(jnp.float32))
    return out, hist[:, 1:]


def ssm_block_decode(p, u, state, cfg, ctx):
    """u: (B,1,d) single token. Returns (out (B,1,d), new state)."""
    s = cfg.ssm
    # named scopes: a profiler trace's ops name the block's part they ran
    with jax.named_scope("mamba2.in_proj"):
        z, x, Bm, Cm, dt = _project(p, u, cfg, ctx)
    with jax.named_scope("mamba2.conv"):
        x1, cx = _conv_step(state["conv_x"], x[:, 0], p["conv_x"])
        B1, cB = _conv_step(state["conv_B"], Bm[:, 0], p["conv_B"])
        C1, cC = _conv_step(state["conv_C"], Cm[:, 0], p["conv_C"])
        x1, B1, C1 = jax.nn.silu(x1), jax.nn.silu(B1), jax.nn.silu(C1)

    with jax.named_scope("mamba2.ssd"):
        A = -jnp.exp(p["A_log"].astype(jnp.float32))
        dt1 = jax.nn.softplus(dt[:, 0].astype(jnp.float32)
                              + p["dt_bias"].astype(jnp.float32))
        dt1 = jnp.clip(dt1, s.dt_min, s.dt_max)             # (B,H)
        decay = jnp.exp(dt1 * A)                            # (B,H)
        st = state["ssd"]
        st = (st * decay[:, :, None, None]
              + jnp.einsum("bh,bhp,bn->bhpn", dt1, x1, B1))
        y = jnp.einsum("bn,bhpn->bhp", C1, st)
        y = y + p["D"].astype(jnp.float32)[None, :, None] * x1
    with jax.named_scope("mamba2.out_proj"):
        y = (y.astype(u.dtype) * jax.nn.silu(z[:, 0]))
        y = rms_norm(y, p["gate_norm"], cfg.norm_eps)
        out = jnp.einsum("bhp,hpd->bd", y, p["w_out"])[:, None]
    new_state = {"ssd": st, "conv_x": cx, "conv_B": cB, "conv_C": cC}
    return ctx.constrain(out, "act_batch", "act_seq", "act_embed"), new_state
