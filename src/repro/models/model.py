"""Unified model bundle: one API over all 10 architectures.

``build(cfg, ctx)`` returns a ``Model`` whose methods are pure functions
suitable for jit/pjit:

* ``init(rng) -> params``; ``param_axes() -> logical-axes tree``
* ``loss(params, batch) -> (scalar, metrics)``               (train_step body)
* ``prefill(params, batch, max_seq) -> (logits, caches)``
* ``decode_step(params, caches, tokens, positions) -> (logits, caches)``
* ``init_caches(batch, max_seq)``; ``cache_axes()``
* ``input_specs(shape) -> (batch SDS tree, batch axes tree)``  (dry-run)
* ``serve_params(params) -> params``: a private device copy of ``params``
  in the served form, laid out once when a serving engine is built

``decode_step`` and ``prefill`` take either form of the params: the tree
``init`` returns (the published layout, which ``loss`` trains) or the one
``serve_params`` returns, in which each SSM block's five input projections
are one output-major ``in_proj`` (``ssm.ssm_serve_params``).
"""
from __future__ import annotations

import math
import warnings
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec
from repro.distributed.sharding import Axes, ShardCtx, axes
from repro.models import encdec as encdec_mod
from repro.models import hybrid as hybrid_mod
from repro.models import ssm as ssm_mod
from repro.models import transformer as tfm
from repro.models.layers import (Builder, embed_lookup, embed_params,
                                 rms_norm, sinusoidal_positions, unembed)


# ---------------------------------------------------------------------------
# Chunked cross-entropy (bounds logit materialization to (B, chunk, V))
# ---------------------------------------------------------------------------

def chunked_ce(hidden, targets, mask, embed_p, cfg, ctx):
    """hidden: (B,S,d) — predicts targets (B,S) at the same index.

    Returns (sum_ce, sum_mask, sum_correct) as f32 scalars.
    """
    B, S, d = hidden.shape
    chunk = min(cfg.loss_chunk, S)
    pad = (-S) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    T = hidden.shape[1] // chunk
    hc = jnp.moveaxis(hidden.reshape(B, T, chunk, d), 1, 0)
    tc = jnp.moveaxis(targets.reshape(B, T, chunk), 1, 0)
    mc = jnp.moveaxis(mask.reshape(B, T, chunk), 1, 0)
    # per-chunk seq gathered for the unembed matmul; vocab TP handles memory
    hc = ctx.constrain(hc, None, "act_batch", None, "act_embed")
    tc = ctx.constrain(tc, None, "act_batch", None)
    mc = ctx.constrain(mc, None, "act_batch", None)

    def body(carry, xs):
        ce_sum, n_sum, acc_sum = carry
        h, t, m = xs
        logits = unembed(embed_p, h, cfg.tie_embeddings, cfg.logit_softcap, ctx)
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        true = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        ce = (lse - true) * m
        pred = jnp.argmax(logits, axis=-1)
        acc = jnp.sum((pred == t) * m)
        return (ce_sum + jnp.sum(ce), n_sum + jnp.sum(m), acc_sum + acc), None

    body = jax.checkpoint(body)
    z = jnp.zeros((), jnp.float32)
    (ce_sum, n_sum, acc_sum), _ = jax.lax.scan(body, (z, z, z), (hc, tc, mc))
    return ce_sum, n_sum, acc_sum


# ---------------------------------------------------------------------------
# Model bundle
# ---------------------------------------------------------------------------

@dataclass
class Model:
    cfg: ModelConfig
    ctx: ShardCtx
    init: Callable
    param_axes: Callable
    loss: Callable
    prefill: Callable
    decode_step: Callable
    init_caches: Callable
    cache_axes: Callable
    input_specs: Callable
    serve_params: Callable


def _serve_tree(tree):
    """``tree`` with every SSM block's params (a layer's ``"ssm"`` entry)
    in the served form."""
    if isinstance(tree, dict):
        return {k: ssm_mod.ssm_serve_params(v) if k == "ssm"
                else _serve_tree(v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_serve_tree(t) for t in tree)
    return tree


def build(cfg: ModelConfig, ctx: ShardCtx | None = None) -> Model:
    cfg.validate()
    ctx = ctx or ShardCtx.single()
    dtype = jnp.dtype(cfg.dtype)

    # -- params ------------------------------------------------------------
    def build_params(b: Builder):
        p = {"embed": embed_params(b, cfg.padded_vocab, cfg.d_model,
                                   cfg.tie_embeddings),
             "final_norm": b.p((cfg.d_model,), ("embed",), init="ones")}
        if cfg.family == "hybrid":
            p["stack"] = hybrid_mod.hybrid_params(b, cfg)
        elif cfg.family == "encdec":
            p["stack"] = encdec_mod.encdec_params(b, cfg)
        else:
            p["stack"] = tfm.stack_params(b, cfg)
        return p

    def init(rng):
        return build_params(Builder("init", rng, jnp.dtype(cfg.param_dtype)))

    def param_axes():
        return build_params(Builder("axes"))

    # -- embedding helpers ---------------------------------------------------
    def _embed(p, tokens):
        x = embed_lookup(p["embed"], tokens, cfg.d_model).astype(dtype)
        if cfg.scale_embeddings:
            x = x * math.sqrt(cfg.d_model)
        return x

    def _prefix(p, batch):
        """VLM: prepend precomputed patch embeddings."""
        x = _embed(p, batch["tokens"])
        if cfg.family == "vlm":
            vis = batch["vision_embeds"].astype(dtype)
            x = jnp.concatenate([vis, x], axis=1)
        return x

    # -- backbone dispatch ---------------------------------------------------
    def _backbone(p, x, *, mode, pos, caches=None, valid_len=None,
                  enc_out=None):
        if cfg.family == "hybrid":
            return hybrid_mod.hybrid_forward(
                p["stack"], x, cfg, ctx, mode=mode, pos=pos, caches=caches,
                valid_len=valid_len)
        if cfg.family == "encdec":
            out = encdec_mod.decoder_forward(
                p["stack"], x, enc_out, cfg, ctx, mode=mode, pos=pos,
                caches=caches, valid_len=valid_len)
            if mode == "train":
                return out[0], {}
            return out[0], {}, out[1]
        return tfm.forward_stack(p["stack"], x, cfg, ctx, mode=mode, pos=pos,
                                 caches=caches, valid_len=valid_len)

    # -- loss (train) --------------------------------------------------------
    def loss(params, batch):
        tokens = batch["tokens"]                     # (B,S)
        B, S = tokens.shape
        tokens = ctx.constrain(tokens, "act_batch", "act_seq")
        enc_out = None
        if cfg.family == "encdec":
            enc_out = encdec_mod.encode(params["stack"], batch["frames"],
                                        cfg, ctx)
            x = _embed(params, tokens)
            x = x + sinusoidal_positions(S, cfg.d_model)[None].astype(dtype)
        else:
            x = _prefix(params, batch)
        Sx = x.shape[1]
        pos = jnp.broadcast_to(jnp.arange(Sx)[None], (B, Sx))
        x = ctx.constrain(x, "act_batch", "act_seq", "act_embed")
        x, aux = _backbone(params, x, mode="train", pos=pos, enc_out=enc_out)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        # next-token prediction on the text region
        off = Sx - S                                  # vision prefix length
        h = x[:, off:, :][:, :-1, :]
        targets = tokens[:, 1:]
        mask = jnp.ones_like(targets, jnp.float32)
        ce_sum, n_sum, acc_sum = chunked_ce(h, targets, mask,
                                            params["embed"], cfg, ctx)
        ce = ce_sum / jnp.maximum(n_sum, 1.0)
        total = ce
        metrics = {"ce": ce, "acc": acc_sum / jnp.maximum(n_sum, 1.0)}
        for k, v in aux.items():
            total = total + v
            metrics[k] = v
        metrics["loss"] = total
        return total, metrics

    # -- prefill ---------------------------------------------------------------
    def prefill(params, batch, max_seq: int):
        """Run the prompt; returns (last-position logits, caches padded to
        max_seq)."""
        tokens = batch["tokens"]
        B, S = tokens.shape
        enc_out = None
        if cfg.family == "encdec":
            enc_out = encdec_mod.encode(params["stack"], batch["frames"],
                                        cfg, ctx)
            x = _embed(params, tokens)
            x = x + sinusoidal_positions(S, cfg.d_model)[None].astype(dtype)
        else:
            x = _prefix(params, batch)
        Sx = x.shape[1]
        pos = jnp.broadcast_to(jnp.arange(Sx)[None], (B, Sx))
        x, _, caches = _backbone(params, x, mode="prefill", pos=pos,
                                 enc_out=enc_out)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed(params["embed"], x[:, -1:, :], cfg.tie_embeddings,
                         cfg.logit_softcap, ctx)
        caches = _pad_prefill_caches(caches, max_seq)
        return logits, caches

    def _pad_prefill_caches(caches, max_seq):
        def pad(leaf):
            if leaf is None:
                return None
            # attn caches have seq at axis=2 of (P,B,S,H,D); ssm states don't
            # pass through here (they are already fixed-size)
            return leaf
        # attn kv from prefill are (P,B,S,H,D) — pad seq dim to max_seq.
        # 'cross' caches (encdec) are full-length already: never pad them.
        def fix(tree):
            if isinstance(tree, dict) and set(tree) == {"k", "v"}:
                k, v = tree["k"], tree["v"]
                if k.ndim == 5 and k.shape[2] < max_seq:
                    padw = [(0, 0)] * 5
                    padw[2] = (0, max_seq - k.shape[2])
                    return {"k": jnp.pad(k, padw), "v": jnp.pad(v, padw)}
                return tree
            if isinstance(tree, dict):
                return {kk: (vv if kk == "cross" else fix(vv))
                        for kk, vv in tree.items()}
            if isinstance(tree, (list, tuple)):
                return type(tree)(fix(t) for t in tree)
            return tree
        return fix(caches)

    # -- decode ---------------------------------------------------------------
    def decode_step(params, caches, tokens, positions):
        """tokens: (B,1) int32; positions: (B,) write index of this token.
        Returns (logits (B,1,V), new caches)."""
        B = tokens.shape[0]
        x = _embed(params, tokens)
        if cfg.family == "encdec":
            from repro.models.layers import sinusoidal_at
            x = x + sinusoidal_at(positions, cfg.d_model
                                  ).astype(dtype)[:, None]
        pos2 = positions[:, None]                     # (B,1)
        valid_len = positions + 1
        out = _backbone(params, x, mode="decode", pos=pos2, caches=caches,
                        valid_len=valid_len)
        x, _, new_caches = out
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        with jax.named_scope("lm_head"):
            logits = unembed(params["embed"], x, cfg.tie_embeddings,
                             cfg.logit_softcap, ctx)
        return logits, new_caches

    # -- caches ----------------------------------------------------------------
    def init_caches(batch: int, max_seq: int):
        if cfg.family == "hybrid":
            return hybrid_mod.hybrid_init_caches(cfg, batch, max_seq)
        if cfg.family == "encdec":
            return encdec_mod.encdec_init_caches(cfg, batch, max_seq)
        return tfm.init_caches(cfg, batch, max_seq)

    def cache_axes():
        if cfg.family == "hybrid":
            return hybrid_mod.hybrid_cache_axes(cfg)
        if cfg.family == "encdec":
            return encdec_mod.encdec_cache_axes(cfg)
        return tfm.cache_axes(cfg)

    # -- dry-run input specs ----------------------------------------------------
    def input_specs(shape: ShapeSpec):
        B, S = shape.global_batch, shape.seq_len
        ti = jnp.int32
        if shape.kind == "train":
            batch = {"tokens": jax.ShapeDtypeStruct((B, S), ti)}
            ax = {"tokens": axes("act_batch", "act_seq")}
        elif shape.kind == "prefill":
            batch = {"tokens": jax.ShapeDtypeStruct((B, S), ti)}
            ax = {"tokens": axes("act_batch", "act_seq")}
        else:  # decode: one new token
            batch = {"tokens": jax.ShapeDtypeStruct((B, 1), ti),
                     "positions": jax.ShapeDtypeStruct((B,), ti)}
            ax = {"tokens": axes("cache_batch", None),
                  "positions": axes("cache_batch")}
        if cfg.family == "vlm" and shape.kind != "decode":
            batch["vision_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.vision_tokens, cfg.d_model), jnp.dtype(cfg.dtype))
            ax["vision_embeds"] = axes("act_batch", None, "act_embed")
        if cfg.family == "encdec" and shape.kind != "decode":
            batch["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_frames, cfg.d_model), jnp.float32)
            ax["frames"] = axes("act_batch", None, "act_embed")
        return batch, ax

    # -- served params -------------------------------------------------------
    serve_jit = jax.jit(_serve_tree, donate_argnums=0)

    def serve_params(params):
        """A private device copy of ``params`` (host or device arrays) in
        the served form: the caller's buffers stay valid, and the layout
        is converted on the device in one call."""
        params = jax.tree.map(jnp.array, params)
        if cfg.family in ("ssm", "hybrid"):
            with warnings.catch_warnings():
                # the replaced projections are donated with the rest of
                # the copy; no output has their shapes
                warnings.filterwarnings(
                    "ignore", "Some donated buffers were not usable")
                params = serve_jit(params)
        return params

    return Model(cfg=cfg, ctx=ctx, init=init, param_axes=param_axes,
                 loss=loss, prefill=prefill, decode_step=decode_step,
                 init_caches=init_caches, cache_axes=cache_axes,
                 input_specs=input_specs, serve_params=serve_params)
