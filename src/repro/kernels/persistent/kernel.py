"""Persistent work-queue executor megakernel (the paper's core, on TPU).

One ``pl.pallas_call`` whose grid is the cluster count; each program is a
persistent worker pinned to its cluster's workspace (paper: one block per
SM). Instead of spin-waiting on host-coherent memory (impossible on TPU —
DESIGN §2), the worker drains a device-resident descriptor queue: for each
descriptor it switches on the opcode, executes a tile-op on its private
workspace (8 VMEM-resident 128×128 tiles → MXU-aligned), and stamps the
from_GPU mailbox with THREAD_FINISHED + work count. A whole DAG of micro-ops
thus runs under ONE kernel launch — the Trigger-overhead argument of the
paper transposed to per-op launch overhead.

Opcodes: NOP / MATMUL (dst += a@b) / ADD / SCALE (fixed-point arg) / RELU /
COPY. Tiles are f32 (T, T) with T=128.

Two kernels live here:

* ``_executor_kernel`` — the original demo: drains a whole static queue,
  answers ONE from_gpu row per cluster (done count in W_ARG0).
* ``_drain_kernel`` — the dispatch fast path (``MegaRuntime``): the queue
  is paired with a ``QCTRL_WIDTH`` control vector (head / tail / stop /
  drained — see ``core.mailbox``), each work row executes for exactly ONE
  chunk (the per-descriptor quantum) threading a resumable carry, and the
  kernel stamps a PER-ROW from_gpu ack (FINISHED / PREEMPTED / NOP +
  request id + chunk words) byte-identical to the scan path's
  ``_lk_step`` records, so the host's zero-readback retire loop — and the
  dispatcher's chunk-boundary preemption on top of it — consume device-
  stamped words without any per-chunk roundtrip. The aggregate work count
  lands in the control output's ``QC_DRAINED`` word, NOT in the ack rows
  (keeping them token-identical to the scan path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.mailbox import (DESC_WIDTH, P_ACTIVE, P_OPCODE, P_QDEPTH,
                                P_REQID, P_ROW, P_TICK0, P_TICK1, PROF_WIDTH,
                                QC_DRAINED, QC_HEAD, QC_STOP, QC_TAIL,
                                QCTRL_WIDTH, THREAD_FINISHED, THREAD_NOP,
                                THREAD_PREEMPTED, THREAD_WORK, W_ARG0,
                                W_ARG1, W_CHUNK, W_NCHUNKS, W_OPCODE,
                                W_REQID, W_STATUS)

TILE = 128

OP_NOP = 0
OP_MATMUL = 1
OP_ADD = 2
OP_SCALE = 3
OP_RELU = 4
OP_COPY = 5
NUM_OPS = 6

# drain-path extension: a chunk-carrying reduction (carry += sum(ws[a]),
# result = carry) — exercises the resumable-carry thread through both the
# megakernel and the scan path. The legacy executor keeps its 6-op table.
OP_REDUCE = 6
NUM_DRAIN_OPS = 7

# descriptor arg packing for tile ops: arg0 = dst*256 + a, arg1 = b or
# fixed-point scale (<<16)
SCALE_SHIFT = 16


def pack_args(dst: int, a: int, b: int = 0) -> tuple[int, int]:
    return dst * 256 + a, b


def pack_scale(dst: int, a: int, scale: float) -> tuple[int, int]:
    return dst * 256 + a, int(scale * (1 << SCALE_SHIFT))


def _executor_kernel(queue_ref, ws_ref, out_ref, fromgpu_ref):
    """queue: (1, Q, DESC_WIDTH) i32 — this cluster's slice.
    ws/out: (1, NBUF, T, T) f32 workspace (aliased in ops.py).
    fromgpu: (1, DESC_WIDTH) i32."""
    out_ref[...] = ws_ref[...]
    q_len = queue_ref.shape[1]

    def op_nop(desc):
        pass

    def _dst_a(desc):
        packed = desc[W_ARG0]
        return packed // 256, packed % 256

    def op_matmul(desc):
        dst, a = _dst_a(desc)
        b = desc[W_ARG1]
        av = out_ref[0, a]
        bv = out_ref[0, b]
        acc = jax.lax.dot_general(av, bv, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        out_ref[0, dst] = out_ref[0, dst] + acc

    def op_add(desc):
        dst, a = _dst_a(desc)
        b = desc[W_ARG1]
        out_ref[0, dst] = out_ref[0, a] + out_ref[0, b]

    def op_scale(desc):
        dst, a = _dst_a(desc)
        scale = desc[W_ARG1].astype(jnp.float32) / (1 << SCALE_SHIFT)
        out_ref[0, dst] = out_ref[0, a] * scale

    def op_relu(desc):
        dst, a = _dst_a(desc)
        out_ref[0, dst] = jnp.maximum(out_ref[0, a], 0.0)

    def op_copy(desc):
        dst, a = _dst_a(desc)
        out_ref[0, dst] = out_ref[0, a]

    ops = [op_nop, op_matmul, op_add, op_scale, op_relu, op_copy]

    def body(i, done_count):
        desc = queue_ref[0, i]
        status = desc[W_STATUS]
        is_work = status >= THREAD_WORK

        def run():
            opcode = jnp.clip(desc[W_OPCODE], 0, NUM_OPS - 1)
            jax.lax.switch(opcode, ops, desc)

        jax.lax.cond(is_work, run, lambda: None)
        return done_count + is_work.astype(jnp.int32)

    done = jax.lax.fori_loop(0, q_len, body, jnp.int32(0))
    fromgpu_ref[0, :] = jnp.zeros((DESC_WIDTH,), jnp.int32)
    fromgpu_ref[0, W_STATUS] = THREAD_FINISHED
    fromgpu_ref[0, W_ARG0] = done


def persistent_execute_pallas(queue, workspace, *, interpret: bool = False):
    """queue: (C, Q, DESC_WIDTH) i32; workspace: (C, NBUF, T, T) f32.
    Returns (new workspace, from_gpu (C, DESC_WIDTH))."""
    C, Q, W = queue.shape
    _, NBUF, T, _ = workspace.shape
    assert W == DESC_WIDTH and T == TILE

    out, fromgpu = pl.pallas_call(
        _executor_kernel,
        grid=(C,),
        in_specs=[
            pl.BlockSpec((1, Q, W), lambda c: (c, 0, 0)),
            pl.BlockSpec((1, NBUF, T, T), lambda c: (c, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, NBUF, T, T), lambda c: (c, 0, 0, 0)),
            pl.BlockSpec((1, W), lambda c: (c, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(workspace.shape, workspace.dtype),
            jax.ShapeDtypeStruct((C, W), jnp.int32),
        ],
        input_output_aliases={1: 0},
        interpret=interpret,
    )(queue, workspace)
    return out, fromgpu


def _store_row(ref, c, i, width: int, words: dict) -> None:
    """Stamp one ``width``-word record of an SMEM ref word by word (SMEM
    takes scalar stores only): ``words`` maps a word index to its value,
    every other word is written 0 so no word of the output is left
    undefined."""
    for w in range(width):
        ref[c, i, w] = words.get(w, jnp.int32(0))


def _drain_body(ctrl_ref, queue_ref, out_ref, carry_out_ref, ack_ref,
                res_ref, ctrl_out_ref, prof_ref=None, tick_out_ref=None):
    """Shared drain loop of the bare and profiled kernels (out_ref /
    carry_out_ref / tick_out_ref already hold their input copies).

    Layout: the tile workspace ``out_ref`` is this cluster's VMEM block
    ``(1, NBUF, T, T)``; every scalar word (control vector, descriptor
    rows, ack rows, per-row results, carry, tick, profile rows) is a
    whole-array SMEM ref indexed by the cluster ``c = program_id(0)``.
    When ``prof_ref`` is given, each row also stamps a flight-recorder
    profile record (``PROF_WIDTH`` words, see core.mailbox) and
    ``tick_out_ref`` advances the persistent logical-tick counter by one
    per executed row — the ack rows stay byte-identical either way."""
    c = pl.program_id(0)
    head = ctrl_ref[c, QC_HEAD]
    tail = ctrl_ref[c, QC_TAIL]
    stop = ctrl_ref[c, QC_STOP]
    q_len = queue_ref.shape[1]

    def _dst_a(arg0):
        # arg0 = dst*256 + a: floor-div/mod by 256 as shift/mask
        return arg0 >> 8, arg0 & 255

    def _write(i, dst, new):
        out_ref[0, dst] = new
        res_ref[c, i, 0] = jnp.sum(new)

    def op_nop(i, arg0, arg1):
        res_ref[c, i, 0] = jnp.float32(0.0)

    def op_matmul(i, arg0, arg1):
        dst, a = _dst_a(arg0)
        acc = jax.lax.dot_general(out_ref[0, a], out_ref[0, arg1],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        _write(i, dst, out_ref[0, dst] + acc)

    def op_add(i, arg0, arg1):
        dst, a = _dst_a(arg0)
        _write(i, dst, out_ref[0, a] + out_ref[0, arg1])

    def op_scale(i, arg0, arg1):
        dst, a = _dst_a(arg0)
        scale = arg1.astype(jnp.float32) * (1.0 / (1 << SCALE_SHIFT))
        _write(i, dst, out_ref[0, a] * scale)

    def op_relu(i, arg0, arg1):
        dst, a = _dst_a(arg0)
        _write(i, dst, jnp.maximum(out_ref[0, a], 0.0))

    def op_copy(i, arg0, arg1):
        dst, a = _dst_a(arg0)
        _write(i, dst, out_ref[0, a])

    def op_reduce(i, arg0, arg1):
        _dst, a = _dst_a(arg0)
        acc = carry_out_ref[c, 0] + jnp.sum(out_ref[0, a])
        carry_out_ref[c, 0] = acc
        res_ref[c, i, 0] = acc

    ops = [op_nop, op_matmul, op_add, op_scale, op_relu, op_copy,
           op_reduce]

    def body(i, drained):
        status = queue_ref[c, i, W_STATUS]
        opcode = queue_ref[c, i, W_OPCODE]
        reqid = queue_ref[c, i, W_REQID]
        chunk = queue_ref[c, i, W_CHUNK]
        n_chunks = queue_ref[c, i, W_NCHUNKS]
        active = ((i >= head) & (i < tail) & (stop == 0)
                  & (status >= THREAD_WORK))

        def run():
            jax.lax.switch(jnp.clip(opcode, 0, NUM_DRAIN_OPS - 1), ops, i,
                           queue_ref[c, i, W_ARG0], queue_ref[c, i, W_ARG1])

        def skip():
            res_ref[c, i, 0] = jnp.float32(0.0)

        jax.lax.cond(active, run, skip)
        # the per-descriptor quantum: one chunk ran — FINISHED only when
        # it was the item's last, PREEMPTED otherwise (the host requeues
        # the remainder through the normal scheduling lane)
        done = chunk + 1 >= jnp.maximum(n_chunks, 1)
        _store_row(ack_ref, c, i, DESC_WIDTH, {
            W_STATUS: jnp.where(
                active, jnp.where(done, THREAD_FINISHED, THREAD_PREEMPTED),
                THREAD_NOP).astype(jnp.int32),
            W_REQID: reqid, W_CHUNK: chunk, W_NCHUNKS: n_chunks})
        act = active.astype(jnp.int32)
        if prof_ref is not None:
            t0 = tick_out_ref[c, 0]
            tick_out_ref[c, 0] = t0 + act
            _store_row(prof_ref, c, i, PROF_WIDTH, {
                P_TICK0: act * t0, P_TICK1: act * (t0 + 1),
                P_ROW: act * drained,
                # occupancy at pop: ring rows still pending, this one
                # included
                P_QDEPTH: act * (tail - i),
                P_OPCODE: act * opcode, P_REQID: act * reqid,
                P_ACTIVE: act})
        return drained + act

    drained = jax.lax.fori_loop(0, q_len, body, jnp.int32(0))
    for w in range(QCTRL_WIDTH):
        ctrl_out_ref[c, w] = drained if w == QC_DRAINED else ctrl_ref[c, w]


def _drain_kernel(ctrl_ref, queue_ref, ws_ref, carry_ref, out_ref,
                  carry_out_ref, ack_ref, res_ref, ctrl_out_ref):
    """SMEM: ctrl (C, QCTRL_WIDTH) i32; queue (C, Q, DESC_WIDTH) i32;
    carry (C, 1) f32 (aliased) — the resumable reduction accumulator
    threaded across rows AND launches; ack (C, Q, DESC_WIDTH) i32 per-row
    from_gpu records; res (C, Q, 1) f32 per-row results; ctrl_out: ctrl
    with QC_DRAINED stamped. VMEM: ws/out (1, NBUF, T, T) f32 (aliased),
    this cluster's block."""
    c = pl.program_id(0)
    out_ref[...] = ws_ref[...]
    carry_out_ref[c, 0] = carry_ref[c, 0]
    _drain_body(ctrl_ref, queue_ref, out_ref, carry_out_ref, ack_ref,
                res_ref, ctrl_out_ref)


def _drain_kernel_prof(ctrl_ref, queue_ref, ws_ref, carry_ref, tick_ref,
                       out_ref, carry_out_ref, ack_ref, res_ref,
                       ctrl_out_ref, prof_ref, tick_out_ref):
    """The flight-recorder variant of ``_drain_kernel``: same queue drain
    and byte-identical ack rows, plus a ``(C, Q, PROF_WIDTH)`` SMEM
    profile output and a persistent ``(C, 1)`` i32 SMEM logical-tick
    counter (aliased input → output like the carry, so ticks stay
    monotone across launches)."""
    c = pl.program_id(0)
    out_ref[...] = ws_ref[...]
    carry_out_ref[c, 0] = carry_ref[c, 0]
    tick_out_ref[c, 0] = tick_ref[c, 0]
    _drain_body(ctrl_ref, queue_ref, out_ref, carry_out_ref, ack_ref,
                res_ref, ctrl_out_ref, prof_ref=prof_ref,
                tick_out_ref=tick_out_ref)


def persistent_drain_pallas(ctrl, queue, workspace, carry, tick=None, *,
                            profile: bool = False,
                            interpret: bool = False):
    """One drain launch per cluster: execute queue rows ``[head, tail)``
    for one chunk each, device-stamping per-row acks.

    ctrl: (C, QCTRL_WIDTH) i32; queue: (C, Q, DESC_WIDTH) i32;
    workspace: (C, NBUF, T, T) f32; carry: (C, 1) f32.
    Returns (workspace', carry', acks (C, Q, DESC_WIDTH),
    results (C, Q, 1), ctrl').

    The grid walks the clusters in order; the workspace is blocked per
    cluster in VMEM, and every scalar array is one whole-array SMEM
    block that each grid step indexes by its cluster.

    With ``profile=True`` the flight-recorder kernel runs instead:
    ``tick`` (a (C, 1) i32 persistent logical-tick counter) is required,
    and the return gains ``(..., prof (C, Q, PROF_WIDTH), tick')`` —
    ack rows stay byte-identical to the bare path."""
    C, Q, W = queue.shape
    _, NBUF, T, _ = workspace.shape
    assert W == DESC_WIDTH and T == TILE
    assert ctrl.shape == (C, QCTRL_WIDTH)
    assert carry.shape == (C, 1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    tiles = pl.BlockSpec((1, NBUF, T, T), lambda c: (c, 0, 0, 0))
    in_specs = [smem, smem, tiles, smem]
    out_specs = [tiles, smem, smem, smem, smem]
    out_shape = [
        jax.ShapeDtypeStruct(workspace.shape, workspace.dtype),
        jax.ShapeDtypeStruct((C, 1), jnp.float32),
        jax.ShapeDtypeStruct((C, Q, W), jnp.int32),
        jax.ShapeDtypeStruct((C, Q, 1), jnp.float32),
        jax.ShapeDtypeStruct((C, QCTRL_WIDTH), jnp.int32),
    ]
    args = (ctrl, queue, workspace, carry)
    aliases = {2: 0, 3: 1}
    kernel = _drain_kernel
    if profile:
        assert tick is not None and tick.shape == (C, 1)
        in_specs.append(smem)
        out_specs += [smem, smem]
        out_shape += [jax.ShapeDtypeStruct((C, Q, PROF_WIDTH), jnp.int32),
                      jax.ShapeDtypeStruct((C, 1), jnp.int32)]
        args += (tick,)
        aliases[4] = 6
        kernel = _drain_kernel_prof
    return pl.pallas_call(
        kernel,
        grid=(C,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(*args)
