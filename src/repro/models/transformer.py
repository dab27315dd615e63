"""Decoder-only transformer assembly (dense / vlm / moe / ssm families).

Layers are organized into *periods* (the repeating unit: e.g. gemma2 =
[local-attn block, global-attn block], llama4 = [dense block, MoE block]) and
scanned with stacked params — HLO stays small enough to SPMD-compile 80-layer
models for 512 devices on the CPU dry-run host.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (Builder, mlp_apply, mlp_params, rms_norm)


# ---------------------------------------------------------------------------
# Period spec
# ---------------------------------------------------------------------------

def period_spec(cfg) -> list[tuple[str, dict]]:
    if cfg.family == "ssm":
        return [("ssm", {})]
    if cfg.family == "moe":
        if cfg.moe.interleave == 2:
            return [("attn_mlp", {}), ("attn_moe", {})]
        assert cfg.moe.interleave == 1
        return [("attn_moe", {})]
    if cfg.local_global_interleave == 2:
        return [("attn_mlp", {"local": True}), ("attn_mlp", {"local": False})]
    return [("attn_mlp", {})]


def num_periods(cfg) -> int:
    spec = period_spec(cfg)
    assert cfg.num_layers % len(spec) == 0, (cfg.name, cfg.num_layers, len(spec))
    return cfg.num_layers // len(spec)


# ---------------------------------------------------------------------------
# One composite layer
# ---------------------------------------------------------------------------

def layer_params(b: Builder, cfg, kind: str):
    d = cfg.d_model
    p: dict[str, Any] = {}
    if kind == "ssm":
        p["ln"] = b.p((d,), ("embed",), init="ones")
        p["ssm"] = ssm_mod.ssm_params(b, cfg)
        return p
    p["ln_attn"] = b.p((d,), ("embed",), init="ones")
    p["attn"] = attn.attn_params(b, d, cfg.num_heads, cfg.num_kv_heads,
                                 cfg.resolved_head_dim, cfg.qkv_bias)
    p["ln_mlp"] = b.p((d,), ("embed",), init="ones")
    if cfg.sandwich_norm:
        p["ln_attn_post"] = b.p((d,), ("embed",), init="ones")
        p["ln_mlp_post"] = b.p((d,), ("embed",), init="ones")
    if kind == "attn_moe":
        p["moe"] = moe_mod.moe_params(b, cfg)
    else:
        p["mlp"] = mlp_params(b, d, cfg.d_ff, cfg.gated_mlp)
    return p


def _attn_sub(p, x, cfg, ctx, *, local: bool, mode: str, pos,
              cache=None, valid_len=None):
    """Attention sub-block. Returns (out, new_cache)."""
    from repro.models.layers import apply_rope
    # NOTE §Perf: explicit block-entry seq-gather constraints were tried in
    # two variants (post-norm h, pre-norm x) and REFUTED: +40% flops resp.
    # 5x memory vs letting the SPMD partitioner place the SP transitions.
    h = rms_norm(x, p["ln_attn"], cfg.norm_eps)
    q, k, v = attn.qkv_project(p["attn"], h, ctx)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    window = cfg.local_window if local else 0
    new_cache = None
    if mode == "decode":
        # write into cache at absolute positions, then flash-decode
        kc, vc = cache["k"], cache["v"]
        kc, vc = attn.cache_update_sharded(kc, vc, k, v, pos[:, 0], ctx)
        o = attn.decode_attention_sharded(
            q, kc, vc, valid_len, ctx,
            attn_softcap=cfg.attn_softcap, window=window)
        new_cache = {"k": kc, "v": vc}
    else:
        o = attn.attention(q, k, v, cfg, ctx, causal=True, window=window)
        if mode == "prefill":
            new_cache = {"k": k, "v": v}
    o = attn.out_project(p["attn"], o, ctx)
    if cfg.sandwich_norm:
        o = rms_norm(o, p["ln_attn_post"], cfg.norm_eps)
    return x + o, new_cache


def _ffn_sub(p, x, cfg, ctx, kind: str, group_mode: str):
    h = rms_norm(x, p["ln_mlp"], cfg.norm_eps)
    aux = {}
    if kind == "attn_moe":
        o, aux = moe_mod.moe_apply(p["moe"], h, cfg, ctx, group_mode)
    else:
        o = mlp_apply(p["mlp"], h, cfg.mlp_act, cfg.gated_mlp, ctx)
    if cfg.sandwich_norm:
        o = rms_norm(o, p["ln_mlp_post"], cfg.norm_eps)
    return x + o, aux


def layer_apply(p, x, cfg, ctx, kind: str, opts: dict, *, mode: str, pos,
                cache=None, valid_len=None):
    """Returns (x, aux, new_cache)."""
    if kind == "ssm":
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        if mode == "decode":
            o, new_state = ssm_mod.ssm_block_decode(p["ssm"], h, cache, cfg, ctx)
            return x + o, {}, new_state
        if mode == "prefill":
            o, state = ssm_mod.ssm_block(p["ssm"], h, cfg, ctx,
                                         return_state=True)
            return x + o, {}, state
        o = ssm_mod.ssm_block(p["ssm"], h, cfg, ctx)
        return x + o, {}, None
    local = bool(opts.get("local", False))
    x, new_cache = _attn_sub(p, x, cfg, ctx, local=local, mode=mode, pos=pos,
                             cache=cache, valid_len=valid_len)
    group_mode = "global" if mode == "decode" else "local"
    x, aux = _ffn_sub(p, x, cfg, ctx, kind, group_mode)
    return x, aux, new_cache


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------

def remat_wrap(body, cfg):
    """Apply the configured activation-checkpoint policy to a scan body."""
    if not cfg.remat:
        return body
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if cfg.remat_policy == "none":
        return body
    return jax.checkpoint(body)        # "full": recompute everything


def stack_params(b: Builder, cfg):
    spec = period_spec(cfg)
    n = num_periods(cfg)
    return {f"blk{i}": b.stack(n, lambda bb, k=kind: layer_params(bb, cfg, k))
            for i, (kind, _) in enumerate(spec)}


def _merge_aux(acc, aux):
    for k, v in aux.items():
        acc[k] = acc.get(k, 0.0) + v
    return acc


def forward_stack(params, x, cfg, ctx, *, mode: str, pos,
                  caches=None, valid_len=None):
    """Scan the layer stack.

    mode='train': returns (x, aux)
    mode='prefill': returns (x, aux, caches) — caches[f'blk{i}'] stacked (P,...)
    mode='decode': caches required; returns (x, aux, new_caches)
    """
    spec = period_spec(cfg)
    aux_keys = ["moe_lb", "moe_z"] if cfg.family == "moe" else []

    def body(carry, xs):
        x, aux_acc = carry
        new_caches = []
        for i, (kind, opts) in enumerate(spec):
            cache_i = xs[1][i] if mode == "decode" else None
            x, aux, nc = layer_apply(
                xs[0][f"blk{i}"], x, cfg, ctx, kind, opts, mode=mode, pos=pos,
                cache=cache_i, valid_len=valid_len)
            for k in aux_keys:
                aux_acc = dict(aux_acc)
                aux_acc[k] = aux_acc[k] + aux.get(k, 0.0)
            new_caches.append(nc)
        ys = tuple(new_caches) if mode in ("prefill", "decode") else None
        return (x, aux_acc), ys

    if mode == "train":
        body = remat_wrap(body, cfg)

    aux0 = {k: jnp.zeros((), jnp.float32) for k in aux_keys}
    unroll = num_periods(cfg) if cfg.scan_unroll else 1
    if mode == "decode":
        # Two cache routes, chosen by layer kind:
        # - attention k/v ride in the CARRY with per-layer dynamic in-place
        #   slice updates: a step writes one position of a large cache, and
        #   passing them through scan xs/ys makes XLA materialize
        #   full-cache copies (measured: 32.6 GiB temp on qwen2-72b
        #   decode_32k via xs/ys vs O(1 layer slice) carried)
        # - SSM state rides as per-layer xs/ys: a step rewrites every
        #   element of it, so reading the stack once and writing it once is
        #   its floor; carried, XLA copied the whole stacked state twice
        #   per layer (in and out of the in-place update)
        streamed = [f"blk{i}" for i, (kind, _) in enumerate(spec)
                    if kind == "ssm"]
        carried = {k: c for k, c in caches.items() if k not in streamed}

        def dbody(carry, xs):
            x, aux_acc, cc = carry
            lp, li, sc = xs
            cc = dict(cc)
            new_sc = {}
            for i, (kind, opts) in enumerate(spec):
                key = f"blk{i}"
                if key in sc:
                    cache_i = sc[key]
                else:
                    cache_i = jax.tree.map(
                        lambda c: jax.lax.dynamic_index_in_dim(
                            c, li, 0, keepdims=False), cc[key])
                x, aux, nc = layer_apply(
                    lp[key], x, cfg, ctx, kind, opts, mode=mode,
                    pos=pos, cache=cache_i, valid_len=valid_len)
                if key in sc:
                    new_sc[key] = nc
                else:
                    cc[key] = jax.tree.map(
                        lambda c, n: jax.lax.dynamic_update_index_in_dim(
                            c, n.astype(c.dtype), li, 0), cc[key], nc)
            return (x, aux_acc, cc), new_sc

        idxs = jnp.arange(num_periods(cfg))
        (x, aux, new_caches), new_streamed = jax.lax.scan(
            dbody, (x, aux0, carried),
            (params, idxs, {k: caches[k] for k in streamed}), unroll=unroll)
        return x, aux, {**new_caches, **new_streamed}
    (x, aux), ys = jax.lax.scan(body, (x, aux0), (params,), unroll=unroll)
    if mode == "prefill":
        new_caches = {f"blk{i}": ys[i] for i in range(len(spec))}
        return x, aux, new_caches
    return x, aux


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def init_caches(cfg, batch: int, max_seq: int):
    """Decode caches for the layer stack, grouped by period element."""
    spec = period_spec(cfg)
    n = num_periods(cfg)
    hk, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    caches = {}
    for i, (kind, _) in enumerate(spec):
        if kind == "ssm":
            st = ssm_mod.ssm_init_state(cfg, batch)
            caches[f"blk{i}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n,) + a.shape).copy(), st)
        else:
            dt = jnp.dtype(cfg.dtype)
            caches[f"blk{i}"] = {
                "k": jnp.zeros((n, batch, max_seq, hk, dh), dt),
                "v": jnp.zeros((n, batch, max_seq, hk, dh), dt),
            }
    return caches


def cache_axes(cfg):
    from repro.distributed.sharding import Axes, axes
    spec = period_spec(cfg)
    out = {}
    for i, (kind, _) in enumerate(spec):
        if kind == "ssm":
            st = ssm_mod.ssm_state_axes(cfg)
            out[f"blk{i}"] = jax.tree.map(
                lambda a: axes("layers", *a.names), st,
                is_leaf=lambda x: isinstance(x, Axes))
        else:
            ca = axes("layers", "cache_batch", "cache_seq", "cache_heads", None)
            out[f"blk{i}"] = {"k": ca, "v": ca}
    return out
