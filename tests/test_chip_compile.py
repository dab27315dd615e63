"""Compile the main path's kernels and step programs for a described TPU
v5e chip (no chip attached): what the chip's compiler refuses fails here,
at no chip time.

The topology is described inside the module fixture only — never at import
— so every pytest-xdist worker collects the same tests and only the worker
running this file loads the TPU compiler. The persistent compilation cache
is off around these compiles: an entry written for a described chip cannot
be read back without one.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import mailbox as mb
from repro.core import op_scopes
from repro.core.persistent import PersistentRuntime
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.persistent import kernel as K
from repro.models import build
from repro.serving import ServingEngine


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


def _computations(hlo: str) -> dict[str, list[str]]:
    comps, name = {}, None
    for line in hlo.splitlines():
        m = op_scopes._COMPUTATION.match(line)
        if m and not line.startswith(" "):
            name = m.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _reached(comps: dict[str, list[str]], root: str) -> set[str]:
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps.get(name, ()):
            for ref in op_scopes._CALLS.findall(line):
                todo += op_scopes._names(ref)
    return seen


def _loop_copies(hlo: str, scope: str, shape: str) -> dict[str, int]:
    """{loop op_name: copies of ``shape`` in its body} for every while
    loop whose op_name holds ``scope``, the loops it calls included."""
    comps = _computations(hlo)
    copy = re.compile(r"=\s*" + re.escape(shape) + r"\{[^}]*\}\s+copy\(")
    out = {}
    for lines in comps.values():
        for line in lines:
            op = re.search(r'op_name="([^"]*)"', line)
            if " while(" not in line or not op or scope not in op.group(1):
                continue
            body = re.search(r"\bbody=%?([\w.\-]+)", line).group(1)
            out[op.group(1)] = sum(
                bool(copy.search(ln)) for c in _reached(comps, body)
                for ln in comps.get(c, ()))
    return out


@pytest.fixture(scope="module")
def mamba2_step(one_chip):
    """(compiled HLO text, served SSM params) of the serving step program
    of a 2-layer, 8-slot mamba2-780m as ``ServingEngine`` builds it and
    the runtime compiles it, state donated."""
    cfg = dataclasses.replace(get_config("mamba2-780m"), num_layers=2)
    model = build(cfg)
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                          jax.eval_shape(model.init, jax.random.key(0)))
    engine = ServingEngine(model, params, max_batch=8, max_seq=256,
                           chunked_prefill=True)
    rt = engine.rt
    ssm = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       rt.state["params"]["stack"]["blk0"]["ssm"])
    state, carries = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype), (rt.state, rt._carries))
    desc = _sds(one_chip, (mb.DESC_WIDTH,), jnp.int32)
    hlo = jax.jit(rt._lk_step, donate_argnums=(0, 1)).lower(
        state, carries, desc).compile().as_text()
    engine.dispose()
    return hlo, ssm


def _hlo_shape(dtype, dims) -> str:
    short = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(dtype).name]
    return f"{short}[{','.join(map(str, dims))}]"


def test_decode_layer_loop_does_not_copy_the_ssm_state(mamba2_step):
    """No loop of the decode or prefill work function copies the stacked
    SSD state (a carried stack was copied in and out of the decode layer
    loop on every layer)."""
    hlo, _ = mamba2_step
    decode = _loop_copies(hlo, "lk.op.decode", "f32[2,8,48,64,128]")
    assert any(k.endswith("lk.op.decode/while") for k in decode), decode
    assert not any(decode.values()), decode
    prefill = _loop_copies(hlo, "lk.op.prefill", "f32[2,1,48,64,128]")
    assert prefill and not any(prefill.values()), prefill


_OPERANDS = re.compile(r"=\s*(?:\([^=]*?\)|\S+)\s+[\w\-]+\(([^)]*)\)")
# an instruction's name and its (first) array shape
_DEFINES = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\(?(\w+\[[\d,]*\])")


def test_step_program_copies_no_ssm_weight(mamba2_step):
    """The engine serves the Mamba2 input projection output-major
    (``in_proj``, d minor): no copy in the step program has the shape of
    an SSM weight or of one layer's slice of it (stored (d, H, Pd), the
    stacked wz/wx were relaid out on every decode step and copied back on
    every launch), and prefill's batch-1 ``mamba2.in_proj`` reads no
    operand laid out (..., H, Pd) (the 64-wide rows padded to 128 lanes)."""
    hlo, ssm = mamba2_step
    assert "in_proj" in ssm and "wz" not in ssm, sorted(ssm)
    weights = set()
    for a in jax.tree.leaves(ssm):
        weights |= {_hlo_shape(a.dtype, a.shape),
                    _hlo_shape(a.dtype, a.shape[1:]),
                    _hlo_shape(a.dtype, (1,) + a.shape[1:])}
    copies = re.findall(r"=\s*(\w+\[[\d,]*\])\{[^}]*\}\s+copy\(", hlo)
    assert copies, "no copy found: the HLO format changed"
    assert not weights & set(copies), sorted(weights & set(copies))

    shape_of = {}
    for line in hlo.splitlines():
        m = _DEFINES.match(line)
        if m:
            shape_of[m.group(1)] = m.group(2)
    in_proj = [line for line in hlo.splitlines()
               if "lk.op.prefill" in line and "mamba2.in_proj" in line]
    assert in_proj
    read = {shape_of[name]
            for line in in_proj if (m := _OPERANDS.search(line))
            for name in re.findall(r"%([\w.\-]+)", m.group(1))}
    heads = re.compile(r"bf16\[(?:\d+,)*48,64\]")
    assert read and not [s for s in read if heads.fullmatch(s)], sorted(read)
