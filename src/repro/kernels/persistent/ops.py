"""Jitted wrappers + queue-building helpers for the persistent executor,
plus ``tile_work_table()`` — the SCAN-path twin of the drain megakernel's
opcode table (same op semantics, chunk contract, and result values), which
is what makes megakernel/scan equivalence testable token-for-token."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mailbox as mb
from repro.core.mailbox import (DESC_WIDTH, THREAD_NOP, THREAD_WORK, W_ARG0,
                                W_ARG1, W_OPCODE, W_STATUS)
from repro.kernels import default_interpret
from repro.kernels.persistent import kernel as K


def build_queue(programs: list[list[tuple]], queue_len: int) -> np.ndarray:
    """programs[c] = list of (opcode, arg0, arg1) for cluster c; padded with
    NOP descriptors to queue_len."""
    C = len(programs)
    q = np.zeros((C, queue_len, DESC_WIDTH), np.int32)
    q[:, :, W_STATUS] = THREAD_NOP
    for c, prog in enumerate(programs):
        assert len(prog) <= queue_len
        for i, (op, a0, a1) in enumerate(prog):
            q[c, i, W_STATUS] = THREAD_WORK + i
            q[c, i, W_OPCODE] = op
            q[c, i, W_ARG0] = a0
            q[c, i, W_ARG1] = a1
    return q


@functools.partial(jax.jit, static_argnames=("interpret",))
def persistent_execute(queue, workspace, *, interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    return K.persistent_execute_pallas(queue, workspace, interpret=interpret)


def mlp_program(nbuf_in: int = 0) -> list[tuple]:
    """A two-layer tile-MLP as a descriptor program:
    t3 += t0@t1; relu t3; t4 += t3@t2 — the 'finer-grained kernels' demo."""
    return [
        (K.OP_MATMUL, *(lambda p: (p[0], p[1]))(K.pack_args(3, 0, 1))),
        (K.OP_RELU, K.pack_args(3, 3)[0], 0),
        (K.OP_MATMUL, *K.pack_args(4, 3, 2)),
    ]


@functools.partial(jax.jit, static_argnames=("interpret",))
def persistent_drain(ctrl, queue, workspace, carry, *,
                     interpret: bool | None = None):
    """Jitted drain launch (``MegaRuntime``'s compiled fast path)."""
    if interpret is None:
        interpret = default_interpret()
    return K.persistent_drain_pallas(ctrl, queue, workspace, carry,
                                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def persistent_drain_prof(ctrl, queue, workspace, carry, tick, *,
                          interpret: bool | None = None):
    """Jitted flight-recorder drain launch: the bare drain's outputs plus
    ``(prof, tick')`` profile rows (see ``core.mailbox`` PROF_* words)."""
    if interpret is None:
        interpret = default_interpret()
    return K.persistent_drain_pallas(ctrl, queue, workspace, carry, tick,
                                     profile=True, interpret=interpret)


# -- scan-path twin of the drain kernel's opcode table ----------------------

TILE_OP_NAMES = ("nop", "matmul", "add", "scale", "relu", "copy", "reduce")

TILE_RESULT_TEMPLATE = jnp.zeros((1,), jnp.float32)


def tile_state(nbuf: int = 8, seed: int | None = None) -> dict:
    """The tile-op state tree: ``{"ws": (nbuf, TILE, TILE) f32}`` —
    zeros, or small random normals when ``seed`` is given."""
    if seed is None:
        ws = np.zeros((nbuf, K.TILE, K.TILE), np.float32)
    else:
        rng = np.random.default_rng(seed)
        ws = rng.standard_normal((nbuf, K.TILE, K.TILE)).astype(np.float32)
        ws *= 0.1        # keep repeated matmul chains numerically tame
    return {"ws": jnp.asarray(ws)}


def tile_work_table() -> list[tuple]:
    """The drain megakernel's opcode table as chunk-aware SCAN-path work
    fns: ``fn(state, carry, desc) -> (state, carry, result, done)`` over
    ``state = {"ws": (nbuf, TILE, TILE) f32}``, in kernel opcode order
    (``TILE_OP_NAMES``). Op semantics, result values ([sum of the written
    tile], [carry] for reduce, [0] for nop) and the uniform per-chunk done
    test match ``_drain_kernel`` exactly — running one descriptor
    sequence through ``PersistentRuntime`` with this table and through
    ``MegaRuntime`` must produce token-identical results and from_gpu
    records. Entry format is ``(name, fn)`` / ``(name, fn, carry)`` as
    consumed by ``PersistentRuntime`` and ``WorkClass``."""

    def _dst_a(desc):
        packed = desc[mb.W_ARG0]
        return packed // 256, packed % 256

    def _done(desc):
        # the same uniform quantum test the kernel stamps statuses from
        return desc[mb.W_CHUNK] + 1 >= jnp.maximum(desc[mb.W_NCHUNKS], 1)

    def nop_fn(state, carry, desc):
        return state, carry, jnp.zeros((1,), jnp.float32), _done(desc)

    def _tile_fn(compute):
        def fn(state, carry, desc):
            ws = state["ws"]
            dst, a = _dst_a(desc)
            new = compute(ws, a, dst, desc)
            ws = ws.at[dst].set(new)
            return ({"ws": ws}, carry, jnp.sum(new)[None], _done(desc))
        return fn

    matmul_fn = _tile_fn(
        lambda ws, a, dst, desc: ws[dst] + jax.lax.dot_general(
            ws[a], ws[desc[mb.W_ARG1]], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
    add_fn = _tile_fn(
        lambda ws, a, dst, desc: ws[a] + ws[desc[mb.W_ARG1]])
    scale_fn = _tile_fn(
        lambda ws, a, dst, desc: ws[a] * (
            desc[mb.W_ARG1].astype(jnp.float32) / (1 << K.SCALE_SHIFT)))
    relu_fn = _tile_fn(
        lambda ws, a, dst, desc: jnp.maximum(ws[a], 0.0))
    copy_fn = _tile_fn(lambda ws, a, dst, desc: ws[a])

    def reduce_fn(state, carry, desc):
        _dst, a = _dst_a(desc)
        acc = carry + jnp.sum(state["ws"][a])
        return state, acc, acc[None], _done(desc)

    return [
        ("nop", nop_fn),
        ("matmul", matmul_fn),
        ("add", add_fn),
        ("scale", scale_fn),
        ("relu", relu_fn),
        ("copy", copy_fn),
        ("reduce", reduce_fn, jnp.zeros((), jnp.float32)),
    ]
