"""Compile the main path's kernels and step programs for a described TPU
v5e chip (no chip attached): what the chip's compiler refuses fails here,
at no chip time.

The topology is described inside the module fixture only — never at import
— so every pytest-xdist worker collects the same tests and only the worker
running this file loads the TPU compiler. The persistent compilation cache
is off around these compiles: an entry written for a described chip cannot
be read back without one.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import mailbox as mb
from repro.core.persistent import PersistentRuntime
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.persistent import kernel as K


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    cc.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("profile", [False, True])
@pytest.mark.parametrize("queue_len", [8, 64])
def test_drain_kernel_compiles(one_chip, profile, queue_len):
    """The drain megakernel as MegaRuntime launches it (one cluster),
    compiled — not interpreted — with its scalar words in SMEM."""
    args = [_sds(one_chip, (1, mb.QCTRL_WIDTH), jnp.int32),
            _sds(one_chip, (1, queue_len, mb.DESC_WIDTH), jnp.int32),
            _sds(one_chip, (1, 8, K.TILE, K.TILE), jnp.float32),
            _sds(one_chip, (1, 1), jnp.float32)]
    if profile:
        args.append(_sds(one_chip, (1, 1), jnp.int32))
    fn = functools.partial(K.persistent_drain_pallas, profile=profile)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    """The model path's Pallas kernel at a real width: 32 query heads on
    8 KV heads, head_dim 128, 2048 tokens, bf16."""
    q = _sds(one_chip, (1, 2048, 32, 128), jnp.bfloat16)
    kv = _sds(one_chip, (1, 2048, 8, 128), jnp.bfloat16)
    compiled = jax.jit(flash_attention_pallas).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_scan_multi_step_compiles(one_chip):
    """The scan path's multi-step program (one doorbell drains a whole
    descriptor ring) with a trivial work fn, donated as on the chip."""
    def bump(state, desc):
        x = state["x"] + desc[mb.W_ARG0].astype(jnp.float32)
        return {"x": x}, jnp.sum(x)[None]

    rt = PersistentRuntime([("bump", bump)],
                           result_template=jnp.zeros((1,), jnp.float32),
                           max_steps=8)
    state = {"x": _sds(one_chip, (8, 128), jnp.float32)}
    carries = (_sds(one_chip, (), jnp.int32),)
    ring = _sds(one_chip, (8, mb.DESC_WIDTH), jnp.int32)
    compiled = jax.jit(rt._lk_multi_step, donate_argnums=(0, 1)).lower(
        state, carries, ring).compile()
    # the donated state is updated in place
    assert compiled.memory_analysis().alias_size_in_bytes > 0
