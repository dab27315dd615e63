"""The decode layer scan's two cache routes against a plain loop.

``transformer.forward_stack`` in decode mode passes each layer kind's
cache by one of two routes: SSM state as per-layer scan xs/ys (a step
rewrites all of it), attention k/v in the carry with an in-place update
at the layer's index (a step writes one position). Either way a step must
give what a plain Python loop over the layers gives."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed import ShardCtx
from repro.models import build
from repro.models import ssm as ssm_mod
from repro.models import transformer as tfm
from repro.models.layers import embed_lookup, rms_norm, unembed


def _plain_decode(cfg, ctx, params, caches, tokens, positions):
    """decode_step as a Python loop over the stacked layers."""
    x = embed_lookup(params["embed"], tokens, cfg.d_model).astype(cfg.dtype)
    if cfg.scale_embeddings:
        x = x * math.sqrt(cfg.d_model)
    new = {k: [] for k in caches}
    for l in range(tfm.num_periods(cfg)):
        for i, (kind, opts) in enumerate(tfm.period_spec(cfg)):
            key = f"blk{i}"
            p = jax.tree.map(lambda a: a[l], params["stack"][key])
            cache = jax.tree.map(lambda a: a[l], caches[key])
            if kind == "ssm":
                h = rms_norm(x, p["ln"], cfg.norm_eps)
                o, nc = ssm_mod.ssm_block_decode(p["ssm"], h, cache, cfg, ctx)
                x = x + o
            else:
                x, _, nc = tfm.layer_apply(
                    p, x, cfg, ctx, kind, opts, mode="decode",
                    pos=positions[:, None], cache=cache,
                    valid_len=positions + 1)
            new[key].append(nc)
    new = {k: jax.tree.map(lambda *ls: jnp.stack(ls), *v)
           for k, v in new.items()}
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(params["embed"], x, cfg.tie_embeddings,
                   cfg.logit_softcap, ctx), new


def _scan_routes(model, params, caches, tokens, positions):
    """(carried, streamed): the caches' leaf shapes found among the layer
    scan's carry outputs and among its stacked ys."""
    jaxpr = jax.make_jaxpr(model.decode_step)(params, caches, tokens,
                                              positions)
    n = tfm.num_periods(model.cfg)
    scans = [e for e in jaxpr.eqns
             if e.primitive.name == "scan" and e.params["length"] == n]
    assert len(scans) == 1
    eqn = scans[0]
    outs = [tuple(v.aval.shape) for v in eqn.outvars]
    k = eqn.params["num_carry"]
    return set(outs[:k]), set(outs[k:])


@pytest.mark.parametrize("arch,batch,route", [
    ("mamba2-780m", 1, "xs_ys"),
    ("mamba2-780m", 8, "xs_ys"),
    ("llama3-8b", 2, "carry"),
])
def test_decode_step_equals_a_plain_layer_loop(arch, batch, route):
    cfg = get_config(arch).reduced()
    ctx = ShardCtx.single(kind="decode")
    model = build(cfg, ctx)
    params = model.init(jax.random.key(0))
    caches = model.init_caches(batch, 16)
    leaves, treedef = jax.tree.flatten(caches)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    caches = jax.tree.unflatten(treedef, [
        jax.random.normal(k, a.shape, a.dtype) for k, a in zip(keys, leaves)])
    tokens = jnp.arange(batch, dtype=jnp.int32)[:, None] * 7 % cfg.vocab_size
    positions = jnp.arange(batch, dtype=jnp.int32) + 3

    carried, streamed = _scan_routes(model, params, caches, tokens,
                                     positions)
    shapes = {tuple(a.shape) for a in leaves}
    if route == "xs_ys":
        assert shapes <= streamed and not shapes & carried
    else:
        assert shapes <= carried and not shapes & streamed

    logits, new = jax.jit(model.decode_step)(params, caches, tokens,
                                             positions)
    ref_logits, ref_new = jax.jit(
        lambda *a: _plain_decode(cfg, ctx, *a))(params, caches, tokens,
                                                positions)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    assert jax.tree.structure(new) == jax.tree.structure(ref_new)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(ref_new)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
