"""The served form of the params against the published one.

``Model.serve_params`` lays each SSM block's five input projections out as
one output-major ``in_proj`` when a serving engine is built; ``decode_step``
and ``prefill`` read either form and must give the same logits and caches.
The tree ``init`` returns stays the published layout the benchmark builds
its weights in."""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed import ShardCtx
from repro.models import build

ROOT = Path(__file__).resolve().parents[1]

# the same products summed in another order: a few roundings of the dtype
TOL = {"float32": 1e-5, "bfloat16": 2 ** -5}


def _model(arch, dtype="float32"):
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype=dtype,
                              param_dtype=dtype)
    return build(cfg, ShardCtx.single(kind="decode"))


def _random_caches(model, batch, max_seq):
    leaves, treedef = jax.tree.flatten(model.init_caches(batch, max_seq))
    keys = jax.random.split(jax.random.key(1), len(leaves))
    return jax.tree.unflatten(treedef, [
        jax.random.normal(k, a.shape, a.dtype) for k, a in zip(keys, leaves)])


def _assert_close(got, want, dtype):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=TOL[dtype], atol=TOL[dtype])


def _by_path(tree):
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_serve_params_fuses_the_input_projection():
    """Rows z, x, B, C, dt of ``in_proj`` are the published projections
    transposed, layer by layer; every other leaf is unchanged and the
    caller's buffers stay valid."""
    model = _model("mamba2-780m")
    params = model.init(jax.random.key(0))
    served = _by_path(model.serve_params(params))
    assert not any(a.is_deleted() for a in jax.tree.leaves(params))
    published = _by_path(params)
    ssm = "['stack']['blk0']['ssm']"
    proj = [np.asarray(published.pop(f"{ssm}['{k}']"))
            for k in ("wz", "wx", "wB", "wC", "wdt")]
    L, d = proj[0].shape[:2]
    want = np.concatenate([w.reshape(L, d, -1) for w in proj], -1)
    np.testing.assert_array_equal(np.asarray(served.pop(f"{ssm}['in_proj']")),
                                  want.transpose(0, 2, 1))
    assert served.keys() == published.keys()
    for k, v in served.items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(published[k]))


def test_serve_params_leaves_an_attention_tree_unchanged():
    model = _model("llama3-8b")
    params = model.init(jax.random.key(0))
    served = model.serve_params(params)
    assert jax.tree.structure(served) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(served), jax.tree.leaves(params)):
        assert a is not b
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("arch", ["mamba2-780m", "zamba2-7b"])
def test_decode_step_reads_either_form(arch, dtype):
    model = _model(arch, dtype)
    params = model.init(jax.random.key(0))
    served = model.serve_params(params)
    caches = _random_caches(model, 3, 32)
    tokens = jnp.array([[1], [5], [7]], jnp.int32)
    positions = jnp.array([3, 4, 5], jnp.int32)
    step = jax.jit(model.decode_step)
    _assert_close(step(served, caches, tokens, positions),
                  step(params, caches, tokens, positions), dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("arch", ["mamba2-780m", "zamba2-7b"])
def test_prefill_reads_either_form(arch, dtype):
    model = _model(arch, dtype)
    params = model.init(jax.random.key(0))
    served = model.serve_params(params)
    batch = {"tokens": jnp.arange(32, dtype=jnp.int32).reshape(2, 16) * 11
             % model.cfg.vocab_size}
    prefill = jax.jit(lambda p, b: model.prefill(p, b, 32))
    _assert_close(prefill(served, batch), prefill(params, batch), dtype)


def test_init_tree_is_the_benchmark_weight_layout():
    """``model.init`` keeps the published layout, leaf for leaf, that the
    benchmark builds its weights in (``bench/run.py``'s check_layout)."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench.configs import mamba2
    from bench.run import check_layout
    c = json.loads((ROOT / "bench/configs/mamba2-780m.json").read_text())
    model = build(mamba2.program_config(c))
    check_layout(model, mamba2.weight_specs(c), jnp.dtype(c["dtype"]))
