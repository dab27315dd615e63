"""Attention: GQA projections + exact blockwise flash (XLA path) + decode.

Two XLA implementations (both numerically exact):

* ``flash_xla`` — blockwise flash attention as a ``lax.scan`` over the STATIC
  list of valid (q_block, kv_block) pairs. Causal/local sparsity is exploited
  structurally (invalid block pairs never appear in the HLO), so
  ``cost_analysis`` FLOPs ≈ useful FLOPs and peak memory is O(S·block), which
  is what lets prefill_32k compile inside 16 GB/chip.
* ``masked_full_xla`` — naive full-score attention; kept as the control arm
  for the §Perf experiment quantifying the blockwise win (and as the oracle
  for small shapes).

Decode attention supports KV caches whose *sequence* dim is sharded over mesh
axes (decode_32k: 'model'; long_500k: ('data','model')) via a shard_map
flash-decoding merge: per-shard partial (max, sumexp, pv) + tiny psum. The
GPU paper's analogue layer is `kernels/flash_attention` (Pallas, TPU target).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.layers import Builder, softcap

_shard_map = functools.partial(jax.shard_map, check_vma=False)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def attn_params(b: Builder, d_model: int, n_heads: int, n_kv: int,
                head_dim: int, qkv_bias: bool):
    p = {
        "wq": b.p((d_model, n_heads, head_dim), ("embed", "heads", "head_dim")),
        "wk": b.p((d_model, n_kv, head_dim), ("embed", "kv_heads", "head_dim")),
        "wv": b.p((d_model, n_kv, head_dim), ("embed", "kv_heads", "head_dim")),
        "wo": b.p((n_heads, head_dim, d_model), ("heads", "head_dim", "embed")),
    }
    if qkv_bias:
        p["bq"] = b.p((n_heads, head_dim), ("heads", "head_dim"), init="zeros")
        p["bk"] = b.p((n_kv, head_dim), ("kv_heads", "head_dim"), init="zeros")
        p["bv"] = b.p((n_kv, head_dim), ("kv_heads", "head_dim"), init="zeros")
    return p


def qkv_project(p, x, ctx):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    # seq gathered here (Megatron-SP): heads are the sharded dim inside attn
    q = ctx.constrain(q, "act_batch", None, "act_heads", None)
    return q, k, v


def out_project(p, o, ctx):
    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return ctx.constrain(y, "act_batch", "act_seq", "act_embed")


# ---------------------------------------------------------------------------
# Static block-pair schedule
# ---------------------------------------------------------------------------

def block_pairs(num_q: int, num_kv: int, causal: bool,
                window_blocks: Optional[int]) -> np.ndarray:
    """Valid (q_block, kv_block) pairs. window_blocks in units of kv blocks."""
    pairs = []
    for qi in range(num_q):
        hi = min(qi, num_kv - 1) if causal else num_kv - 1
        lo = 0 if window_blocks is None else max(0, qi - window_blocks)
        for kj in range(lo, hi + 1):
            pairs.append((qi, kj))
    return np.asarray(pairs, dtype=np.int32)


def _pad_to_block(x, block, axis):
    s = x.shape[axis]
    pad = (-s) % block
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# Exact blockwise flash attention (XLA path)
#
# Module-level custom_vjp with hashable statics: the backward replays block
# pairs and recomputes p (flash backward). Defining the custom_vjp inside the
# traced caller leaks the pair-constant under jax.checkpoint; keeping it at
# module level with statics in nondiff_argnums avoids that entirely.
# ---------------------------------------------------------------------------

import dataclasses as _dc
from typing import Any as _Any

_NEG = jnp.float32(-1e30)

# Calibration hook (launch/dryrun.py): XLA cost_analysis counts a scan body
# ONCE regardless of trip count; unrolling the pair scans during the
# cost-calibration compiles makes attention FLOPs visible. Never set in
# production paths.
UNROLL_PAIR_SCAN = False


def _scan(body, init, xs):
    unroll = len(xs) if UNROLL_PAIR_SCAN else 1
    return jax.lax.scan(body, init, xs, unroll=unroll)


@_dc.dataclass(frozen=True)
class _FlashStatics:
    causal: bool
    window: int
    attn_softcap: float
    block_q: int
    block_kv: int
    real_len: int
    groups: int
    scale: float
    sh_stats: _Any = None    # NamedSharding for (Tq,B,Hq,bq) or None
    sh_acc: _Any = None      # (Tq,B,Hq,bq,D)
    sh_q: _Any = None        # (Tq,B,bq,Hq,D)
    sh_kv: _Any = None       # (Tkv,B,bk,Hkv,D)


def _wsc(x, sh):
    if sh is None:
        return x
    return jax.lax.with_sharding_constraint(x, sh)


def _pairs_for(st: _FlashStatics, Tq: int, Tkv: int):
    wb = None
    if st.window > 0:
        wb = max(1, math.ceil(st.window / st.block_kv))
    return jnp.asarray(block_pairs(Tq, Tkv, st.causal, wb))


def _block_mask(st, qi, kj):
    qpos = qi * st.block_q + jnp.arange(st.block_q)
    kpos = kj * st.block_kv + jnp.arange(st.block_kv)
    mask = kpos[None, :] < st.real_len
    if st.causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if st.window > 0:
        mask &= qpos[:, None] - kpos[None, :] < st.window
    return mask


def _block_scores(st, qblk, kblk, qi, kj):
    z = jnp.einsum("bqhd,bkhd->bhqk", qblk, kblk,
                   preferred_element_type=jnp.float32) * st.scale
    s = softcap(z, st.attn_softcap)
    mask = _block_mask(st, qi, kj)
    return jnp.where(mask[None, None], s, _NEG), z, mask


def _expand(st, blk):
    return jnp.repeat(blk, st.groups, axis=2) if st.groups > 1 else blk


def _flash_fwd_impl(qb, kb, vb, st: _FlashStatics):
    Tq, B, bq, Hq, D = qb.shape
    Tkv = kb.shape[0]
    pairs = _pairs_for(st, Tq, Tkv)
    m0 = _wsc(jnp.full((Tq, B, Hq, bq), _NEG, jnp.float32), st.sh_stats)
    l0 = _wsc(jnp.zeros((Tq, B, Hq, bq), jnp.float32), st.sh_stats)
    a0 = _wsc(jnp.zeros((Tq, B, Hq, bq, D), jnp.float32), st.sh_acc)

    def step(carry, pair):
        m, l, acc = carry
        qi, kj = pair[0], pair[1]
        qblk = jax.lax.dynamic_index_in_dim(qb, qi, 0, keepdims=False)
        kblk = _expand(st, jax.lax.dynamic_index_in_dim(kb, kj, 0,
                                                        keepdims=False))
        vblk = _expand(st, jax.lax.dynamic_index_in_dim(vb, kj, 0,
                                                        keepdims=False))
        s, _, _ = _block_scores(st, qblk, kblk, qi, kj)
        m_blk = jnp.max(s, axis=-1)
        m_old = jax.lax.dynamic_index_in_dim(m, qi, 0, keepdims=False)
        l_old = jax.lax.dynamic_index_in_dim(l, qi, 0, keepdims=False)
        a_old = jax.lax.dynamic_index_in_dim(acc, qi, 0, keepdims=False)
        m_new = jnp.maximum(m_old, m_blk)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_old - m_new)
        l_new = corr * l_old + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vblk.dtype), vblk,
                        preferred_element_type=jnp.float32)
        a_new = corr[..., None] * a_old + pv
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, qi, 0)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, qi, 0)
        acc = jax.lax.dynamic_update_index_in_dim(acc, a_new, qi, 0)
        return (m, l, acc), None

    (m, l, acc), _ = _scan(step, (m0, l0, a0), pairs)
    out = acc / jnp.maximum(l, 1e-30)[..., None]            # (Tq,B,H,bq,D)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))                # (Tq,B,H,bq)
    return out, lse


def _flash_bwd_impl(st: _FlashStatics, res, dout):
    qb, kb, vb, out, lse = res
    Tq, B, bq, Hq, D = qb.shape
    Tkv, _, bk, Hkv, _ = kb.shape
    G = st.groups
    pairs = _pairs_for(st, Tq, Tkv)
    delta = jnp.sum(dout * out, axis=-1)                    # (Tq,B,H,bq)
    dq0 = _wsc(jnp.zeros(qb.shape, jnp.float32), st.sh_q)
    dk0 = _wsc(jnp.zeros(kb.shape, jnp.float32), st.sh_kv)
    dv0 = _wsc(jnp.zeros(vb.shape, jnp.float32), st.sh_kv)

    def bstep(carry, pair):
        dq, dk, dv = carry
        qi, kj = pair[0], pair[1]
        qblk = jax.lax.dynamic_index_in_dim(qb, qi, 0, keepdims=False)
        kblk = _expand(st, jax.lax.dynamic_index_in_dim(kb, kj, 0,
                                                        keepdims=False))
        vblk = _expand(st, jax.lax.dynamic_index_in_dim(vb, kj, 0,
                                                        keepdims=False))
        do = jax.lax.dynamic_index_in_dim(dout, qi, 0, keepdims=False)
        lse_i = jax.lax.dynamic_index_in_dim(lse, qi, 0, keepdims=False)
        dlt_i = jax.lax.dynamic_index_in_dim(delta, qi, 0, keepdims=False)
        s, z, mask = _block_scores(st, qblk, kblk, qi, kj)
        p = jnp.exp(s - lse_i[..., None])                   # (B,H,bq,bk)
        dvb = jnp.einsum("bhqk,bhqd->bkhd", p, do,
                         preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhqd,bkhd->bhqk", do, vblk.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        ds = p * (dp - dlt_i[..., None])
        if st.attn_softcap > 0:
            t = jnp.tanh(z / st.attn_softcap)
            ds = ds * (1.0 - jnp.square(t))
        ds = jnp.where(mask[None, None], ds, 0.0) * st.scale
        dqb = jnp.einsum("bhqk,bkhd->bqhd", ds, kblk.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        dkb = jnp.einsum("bhqk,bqhd->bkhd", ds, qblk.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        if G > 1:
            dvb = dvb.reshape(B, bk, Hkv, G, D).sum(axis=3)
            dkb = dkb.reshape(B, bk, Hkv, G, D).sum(axis=3)
        dq_old = jax.lax.dynamic_index_in_dim(dq, qi, 0, keepdims=False)
        dk_old = jax.lax.dynamic_index_in_dim(dk, kj, 0, keepdims=False)
        dv_old = jax.lax.dynamic_index_in_dim(dv, kj, 0, keepdims=False)
        dq = jax.lax.dynamic_update_index_in_dim(dq, dq_old + dqb, qi, 0)
        dk = jax.lax.dynamic_update_index_in_dim(dk, dk_old + dkb, kj, 0)
        dv = jax.lax.dynamic_update_index_in_dim(dv, dv_old + dvb, kj, 0)
        return (dq, dk, dv), None

    (dq, dk, dv), _ = _scan(bstep, (dq0, dk0, dv0), pairs)
    return dq.astype(qb.dtype), dk.astype(kb.dtype), dv.astype(vb.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_core(qb, kb, vb, st: _FlashStatics):
    return _flash_fwd_impl(qb, kb, vb, st)[0]


def _flash_core_f(qb, kb, vb, st):
    out, lse = _flash_fwd_impl(qb, kb, vb, st)
    return out, (qb, kb, vb, out, lse)


def _flash_core_b(st, res, dout):
    return _flash_bwd_impl(st, res, dout)


_flash_core.defvjp(_flash_core_f, _flash_core_b)


def flash_xla(q, k, v, *, causal: bool, window: int = 0,
              attn_softcap: float = 0.0, block_q: int = 512,
              block_kv: int = 512, seq_len: Optional[int] = None,
              ctx=None):
    """q: (B,S,Hq,D) — Hq shardable; k,v: (B,S,Hkv,D) — heads replicated.

    Returns (B,S,Hq,D). Exact (renormalized blockwise softmax, f32 stats).
    custom_vjp: the backward replays block pairs and recomputes p — without
    it, autodiff through the pair scan saves every step's (bq,bk) prob
    matrix (measured: 23.8 GiB/device for whisper train_4k; 1.5 GiB after).
    """
    B, S, Hq, D = q.shape
    Skv0 = k.shape[1]
    Hkv = k.shape[2]
    G = Hq // Hkv
    real_len = Skv0 if seq_len is None else seq_len
    block_q = min(block_q, S)
    block_kv = min(block_kv, Skv0)
    if causal:
        assert S == Skv0, "causal flash requires equal q/kv lengths"

    qp = _pad_to_block(q, block_q, 1)
    kp = _pad_to_block(k, block_kv, 1)
    vp = _pad_to_block(v, block_kv, 1)
    Sq, Skv = qp.shape[1], kp.shape[1]
    Tq, Tkv = Sq // block_q, Skv // block_kv

    def _sh(ax_names, shape):
        if ctx is None or ctx.mesh is None:
            return None
        from repro.distributed.sharding import Axes
        return ctx.sharding_for(Axes(ax_names), shape)

    st = _FlashStatics(
        causal=causal, window=int(window or 0), attn_softcap=attn_softcap,
        block_q=block_q, block_kv=block_kv, real_len=real_len, groups=G,
        scale=1.0 / math.sqrt(D),
        sh_stats=_sh((None, "act_batch", "act_heads", None),
                     (Tq, B, Hq, block_q)),
        sh_acc=_sh((None, "act_batch", "act_heads", None, None),
                   (Tq, B, Hq, block_q, D)),
        sh_q=_sh((None, "act_batch", None, "act_heads", None),
                 (Tq, B, block_q, Hq, D)),
        sh_kv=_sh((None, "act_batch", None, None, None),
                  (Tkv, B, block_kv, Hkv, D)),
    )

    # (Tq, B, bq, H, D) block-major layouts
    qb = jnp.moveaxis(qp.reshape(B, Tq, block_q, Hq, D), 1, 0)
    kb = jnp.moveaxis(kp.reshape(B, Tkv, block_kv, Hkv, D), 1, 0)
    vb = jnp.moveaxis(vp.reshape(B, Tkv, block_kv, Hkv, D), 1, 0)
    qb = _wsc(qb, st.sh_q)
    kb = _wsc(kb, st.sh_kv)
    vb = _wsc(vb, st.sh_kv)

    out = _flash_core(qb, kb, vb, st)                      # (Tq,B,H,bq,D)
    out = jnp.transpose(out, (1, 0, 3, 2, 4))              # (B,Tq,bq,H,D)
    out = out.reshape(B, Sq, Hq, D)[:, :S]
    return out.astype(q.dtype)


def masked_full_xla(q, k, v, *, causal: bool, window: int = 0,
                    attn_softcap: float = 0.0, seq_len: Optional[int] = None,
                    ctx=None):
    """Naive O(S^2)-memory attention (oracle / §Perf control arm)."""
    B, S, Hq, D = q.shape
    Skv = k.shape[1]
    G = Hq // k.shape[2]
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    if ctx is not None:
        s = ctx.constrain(s, "act_batch", "act_heads")
    s = softcap(s, attn_softcap)
    qpos = jnp.arange(S)
    kpos = jnp.arange(Skv)
    mask = jnp.ones((S, Skv), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window and window > 0:
        mask &= qpos[:, None] - kpos[None, :] < window
    if seq_len is not None:
        mask &= kpos[None, :] < seq_len
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return out.astype(q.dtype)


def pad_heads_for_tp(q, Hkv: int, ctx) -> tuple:
    """Pad q-heads to the next multiple of the model-axis size that is also
    a multiple of Hkv (GQA grouping stays integral). Without this, archs
    whose head count doesn't divide the mesh (llama4: 40 on 16) fall back to
    REPLICATED attention activations/compute — 16x waste vs <=1.2x padding
    waste. Padded heads produce zeros that are sliced off."""
    Hq = q.shape[2]
    ms = ctx.model_axis_size if ctx is not None else 1
    if ms <= 1 or Hq % ms == 0:
        return q, Hq
    cand = ((Hq + ms - 1) // ms) * ms
    while cand % Hkv:
        cand += ms
    pad = cand - Hq
    q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return q, Hq


def attention(q, k, v, cfg, ctx, *, causal: bool, window: int = 0):
    """Dispatch on cfg.attn_backend ('xla' | 'masked' | 'pallas' | 'auto')."""
    backend = cfg.attn_backend
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    q, Hq_orig = pad_heads_for_tp(q, k.shape[2], ctx)
    if backend == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(
            q, k, v, causal=causal, window=window,
            attn_softcap=cfg.attn_softcap)
    elif backend == "masked":
        out = masked_full_xla(q, k, v, causal=causal, window=window,
                              attn_softcap=cfg.attn_softcap, ctx=ctx)
    else:
        out = flash_xla(q, k, v, causal=causal, window=window,
                        attn_softcap=cfg.attn_softcap,
                        block_q=cfg.attn_chunk, block_kv=cfg.attn_chunk,
                        ctx=ctx)
    return out[:, :, :Hq_orig]


# ---------------------------------------------------------------------------
# Decode attention (one new token vs cache), optionally seq-sharded
# ---------------------------------------------------------------------------

def decode_attention_local(q, k_cache, v_cache, valid_len, *,
                           attn_softcap: float = 0.0, window: int = 0):
    """Unsharded reference decode attention.

    q: (B,1,Hq,D); caches: (B,Smax,Hkv,D); valid_len: (B,) — number of valid
    cache positions INCLUDING the just-written token.
    """
    B, Smax, Hkv, D = k_cache.shape
    Hq = q.shape[2]
    G = Hq // Hkv
    if G > 1:
        k_cache = jnp.repeat(k_cache, G, axis=2)
        v_cache = jnp.repeat(v_cache, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    s = softcap(s, attn_softcap)
    pos = jnp.arange(Smax)
    mask = pos[None, :] < valid_len[:, None]              # (B,Smax)
    if window and window > 0:
        mask &= pos[None, :] >= (valid_len[:, None] - window)
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_cache.dtype), v_cache)
    return out.astype(q.dtype)


def decode_attention_sharded(q, k_cache, v_cache, valid_len, ctx, *,
                             attn_softcap: float = 0.0, window: int = 0):
    """Flash-decoding over a KV cache whose seq dim is sharded on mesh axes.

    Per-shard partial (max, sumexp, weighted V) then psum-merge — the shard
    never materializes non-local KV. Batch stays sharded on 'data' unless
    'data' is a cache-seq axis (long_500k, B=1).
    """
    mesh = ctx.mesh
    seq_axes = ctx.rules["cache_seq"]
    if mesh is None or seq_axes is None:
        return decode_attention_local(q, k_cache, v_cache, valid_len,
                                      attn_softcap=attn_softcap, window=window)
    if isinstance(seq_axes, str):
        seq_axes = (seq_axes,)
    batch_axis = ctx.rules["cache_batch"]
    bspec = batch_axis if batch_axis is not None else None

    q_spec = P(bspec, None, None, None)
    c_spec = P(bspec, seq_axes if len(seq_axes) > 1 else seq_axes[0], None, None)
    len_spec = P(bspec)

    def local_fn(qs, ks, vs, vl):
        B, S_loc, Hkv, D = ks.shape
        Hq = qs.shape[2]
        G = Hq // Hkv
        # global offset of this shard's cache slice
        idx = 0
        for ax in seq_axes:
            idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        offset = idx * S_loc
        kx, vx = ks, vs
        if G > 1:
            kx = jnp.repeat(kx, G, axis=2)
            vx = jnp.repeat(vx, G, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", qs, kx,
                       preferred_element_type=jnp.float32) / math.sqrt(D)
        s = softcap(s, attn_softcap)
        pos = offset + jnp.arange(S_loc)
        mask = pos[None, :] < vl[:, None]
        if window and window > 0:
            mask &= pos[None, :] >= (vl[:, None] - window)
        s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
        m_loc = jnp.max(s, axis=-1)                        # (B,H,1)
        m_safe = jnp.where(jnp.isfinite(m_loc), m_loc, 0.0)
        p = jnp.where(jnp.isfinite(m_loc)[..., None],
                      jnp.exp(s - m_safe[..., None]), 0.0)
        l_loc = jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vx.dtype), vx,
                        preferred_element_type=jnp.float32)
        # merge across seq shards
        m_glob = jax.lax.pmax(m_loc, seq_axes)
        m_glob_safe = jnp.where(jnp.isfinite(m_glob), m_glob, 0.0)
        corr = jnp.where(jnp.isfinite(m_loc),
                         jnp.exp(m_loc - m_glob_safe), 0.0)
        l_glob = jax.lax.psum(corr * l_loc, seq_axes)
        o_glob = jax.lax.psum(corr[..., None] * pv, seq_axes)
        out = o_glob / jnp.maximum(l_glob, 1e-30)[..., None]
        return jnp.swapaxes(out, 1, 2).astype(qs.dtype)   # (B,1,H,D)

    return _shard_map(
        local_fn, mesh=mesh,
        in_specs=(q_spec, c_spec, c_spec, len_spec),
        out_specs=q_spec,
    )(q, k_cache, v_cache, valid_len)


def cache_update_sharded(k_cache, v_cache, k_new, v_new, positions, ctx):
    """Write (B,1,Hkv,D) new K/V at per-sequence positions into a cache whose
    seq dim may be sharded: predicated local update inside shard_map."""
    mesh = ctx.mesh
    seq_axes = ctx.rules["cache_seq"]
    if mesh is None or seq_axes is None:
        def upd(c, n, p):
            return jax.vmap(
                lambda cb, nb, pb: jax.lax.dynamic_update_slice(
                    cb, nb, (pb, 0, 0)))(c, n, p)
        return upd(k_cache, k_new, positions), upd(v_cache, v_new, positions)
    if isinstance(seq_axes, str):
        seq_axes = (seq_axes,)
    batch_axis = ctx.rules["cache_batch"]
    bspec = batch_axis if batch_axis is not None else None
    c_spec = P(bspec, seq_axes if len(seq_axes) > 1 else seq_axes[0], None, None)
    n_spec = P(bspec, None, None, None)
    p_spec = P(bspec)

    def local_fn(kc, vc, kn, vn, pos):
        S_loc = kc.shape[1]
        idx = 0
        for ax in seq_axes:
            idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        offset = idx * S_loc
        local_pos = jnp.clip(pos - offset, 0, S_loc - 1)
        owns = (pos >= offset) & (pos < offset + S_loc)    # (B,)

        def upd(c, n):
            updated = jax.vmap(
                lambda cb, nb, pb: jax.lax.dynamic_update_slice(
                    cb, nb.astype(cb.dtype), (pb, 0, 0)))(c, n, local_pos)
            return jnp.where(owns[:, None, None, None], updated, c)
        return upd(kc, kn), upd(vc, vn)

    return _shard_map(
        local_fn, mesh=mesh,
        in_specs=(c_spec, c_spec, n_spec, n_spec, p_spec),
        out_specs=(c_spec, c_spec),
    )(k_cache, v_cache, k_new, v_new, positions)
