"""MegaRuntime — the dispatcher's megakernel fast path.

Where ``PersistentRuntime`` compiles the work table into an XLA step and
feeds a host-refilled descriptor ring (one ``lax.scan`` doorbell per
batch), the MegaRuntime boots ONE compiled ``pl.pallas_call`` per cluster
— the drain megakernel of ``repro.kernels.persistent`` — whose worker
loops over a device-resident descriptor queue under a ``QCTRL_WIDTH``
control vector (head / tail / stop — see ``core.mailbox``). ``kick()``
appends a whole coalesced batch into the queue buffer via
``trigger_many``; the device executes every row for exactly ONE chunk
(the per-descriptor quantum), threads the resumable reduce carry across
rows AND launches, and stamps per-row ``from_gpu`` words (FINISHED /
PREEMPTED / NOP + request id + chunk progress) that the host's existing
zero-readback retire path — and the dispatcher's chunk-boundary
preemption on top of it — consume without any per-chunk host roundtrip.
The aggregate drained-work count rides the control output's
``QC_DRAINED`` word (``work_drained``), keeping the ack rows
byte-identical to the scan path's ``_lk_step`` records (that identity is
CI-tested in ``tests/test_mega_runtime.py``).

The work table is FIXED: the drain kernel's tile-op opcodes
(``TILE_OP_NAMES`` order — nop / matmul / add / scale / relu / copy /
reduce over ``{"ws": (nbuf, TILE, TILE) f32}``). ``LkSystem``'s
``runtime="mega"`` knob validates registered class names against that
order at boot and falls back per item through the normal ``trigger()``
protocol (a one-row queue) when a caller bypasses ``trigger_many``.
Donation is NOT requested at the jit level — the pallas
``input_output_aliases`` already alias workspace and carry device-side,
and jit-level donation would serialize dispatch on CPU (see
``PersistentRuntime``'s module docstring).
"""
from __future__ import annotations

import functools
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mailbox as mb
from repro.core import persistent as P
from repro.core.persistent import (ExecutableCache, _Block,
                                   _device_key, _PipelinedRuntime,
                                   _tree_key)
from repro.core.telemetry import TraceCollector
from repro.core.telemetry.events import now_us
from repro.core.wcet import WcetTracker
from repro.kernels import default_interpret
from repro.kernels.persistent import kernel as K
from repro.kernels.persistent.ops import TILE_OP_NAMES, tile_work_table

__all__ = ["MegaRuntime", "mega_work_classes", "TILE_OP_NAMES"]


class MegaRuntime(_PipelinedRuntime):
    """One persistent megakernel worker (paper: one block per SM).

    Satisfies ``RuntimeProtocol``: ``trigger``/``trigger_many`` enqueue
    drain launches (async — one compiled call per ``max_steps``-row
    queue), ``ready``/``wait``/``poll`` retire items strictly in issue
    order with one bulk ack readback per launch. ``max_steps`` is the
    device queue capacity Q; ``boot(state)`` takes the tile state tree
    ``{"ws": (nbuf, TILE, TILE) f32}`` (``tile_state()``) and compiles
    the drain ``pallas_call`` once (shared ``exec_cache`` turns recarve
    reboots into dictionary hits). ``interpret=None`` compiles the kernel
    on the TPU and interprets it on the CPU backend only
    (``repro.kernels.default_interpret``); ``interpreted`` records which
    ran. ``device`` places the workspace, carry, queues and the compiled
    kernel on one device (a cluster's chip); None keeps JAX's default.
    """

    def __init__(self, *, tracker: Optional[WcetTracker] = None,
                 max_inflight: int = 2,
                 max_steps: int = 8,
                 telemetry: Optional[TraceCollector] = None,
                 exec_cache: Optional[ExecutableCache] = None,
                 interpret: Optional[bool] = None,
                 profile: Optional[bool] = None,
                 device=None):
        super().__init__(tracker=tracker, max_inflight=max_inflight,
                         telemetry=telemetry, name="mega")
        if max_steps < 1:
            raise ValueError("max_steps must be >= 1")
        self.work_names = list(TILE_OP_NAMES)
        self.max_steps = int(max_steps)
        self._exec_cache = exec_cache
        self._interpret = interpret
        self.interpreted: Optional[bool] = None   # set at boot
        self.device = device
        # flight recorder (None = auto: on exactly when telemetry is
        # attached): boots the profiled drain kernel, whose extra
        # (Q, PROF_WIDTH) output and persistent tick counter join the
        # bulk readback; ack rows stay byte-identical to the bare path
        self._profile = profile
        self._drain = None
        self._ws = None                # (1, NBUF, TILE, TILE) f32
        self._carry = None             # (1, 1) f32 — device-resident
        self._tick = None              # (1, 1) i32 — logical-tick counter
        # control outputs pending readback, FIFO-aligned with _inflight:
        # QC_DRAINED accumulates into work_drained at block retirement
        self._ctrl_pending: deque = deque()
        self.doorbells = 0             # drain launches issued
        self.batched_steps = 0         # descriptors issued through them
        self.work_drained = 0          # device-stamped QC_DRAINED total

    # ------------------------------------------------------------------
    @property
    def booted(self) -> bool:
        return self._drain is not None

    def boot(self, state) -> None:
        """Init phase: compile the drain megakernel and make the tile
        workspace + reduce carry device-resident."""
        with self.tracker.phase("init", "lk.init"):
            ws = jnp.asarray(state["ws"], jnp.float32)
            if ws.ndim != 3 or ws.shape[1:] != (K.TILE, K.TILE):
                raise ValueError(
                    "MegaRuntime state must be {'ws': (nbuf, "
                    f"{K.TILE}, {K.TILE}) f32}}, got ws{ws.shape}")
            dev = self.device
            ws = jax.device_put(ws[None], dev)        # add the cluster dim
            carry = jax.device_put(np.zeros((1, 1), np.float32), dev)
            interpret = self._interpret
            if interpret is None:
                interpret = default_interpret()
            self.interpreted = bool(interpret)
            if self._profile is None:
                self._profile = self.telemetry is not None
            tick0 = jax.device_put(np.zeros((1, 1), np.int32), dev) \
                if self._profile else None
            Q = self.max_steps
            ctrl0 = jax.device_put(np.zeros((1, mb.QCTRL_WIDTH), np.int32),
                                   dev)
            ring0 = jax.device_put(
                np.tile(mb.nop_descriptor(), (1, Q, 1)), dev)

            def compile_drain():
                fn = functools.partial(K.persistent_drain_pallas,
                                       profile=self._profile,
                                       interpret=interpret)
                if self._profile:
                    return jax.jit(fn).lower(
                        ctrl0, ring0, ws, carry, tick0).compile()
                return jax.jit(fn).lower(ctrl0, ring0, ws, carry).compile()

            key = ("mega_drain_prof" if self._profile else "mega_drain",
                   _tree_key(ws), Q, bool(interpret), _device_key(dev),
                   mb.DESC_WIDTH, mb.QCTRL_WIDTH)
            if self._exec_cache is not None:
                self._drain = self._exec_cache.get_or_compile(
                    key, compile_drain)
            else:
                self._drain = compile_drain()
            self._ws = ws
            self._carry = carry
            self._tick = tick0
        self.status = mb.THREAD_NOP

    # ------------------------------------------------------------------
    def trigger(self, desc) -> None:
        """Per-item fallback: a one-row queue through the same drain
        launch (async — returns at enqueue)."""
        self.trigger_many([desc])

    def trigger_many(self, descs) -> int:
        """Append a coalesced batch into the device queue: ONE ring +
        control transfer and ONE compiled drain launch per ``max_steps``
        rows — the device loops the descriptors without any per-chunk
        host roundtrip. Items retire through ``wait()``/``poll()`` in
        issue order; returns the number of descriptors issued."""
        if self._drain is None:
            raise RuntimeError("boot() first")
        descs = list(descs)
        if not descs:
            return 0
        if self.inflight + len(descs) > self.max_inflight:
            raise RuntimeError(
                f"batch of {len(descs)} exceeds pipeline capacity "
                f"(max_inflight={self.max_inflight}, "
                f"inflight={self.inflight})")
        for base in range(0, len(descs), self.max_steps):
            block = descs[base:base + self.max_steps]
            ring = mb.descriptor_ring(block, self.max_steps)
            ctrl = mb.queue_control(tail=len(block))
            with self.tracker.phase("trigger", "lk.trigger",
                                    batch=len(block),
                                    depth=self.inflight + len(block)) as span:
                if span is not None:
                    span.update(P.PersistentRuntime.launch_args(block))
                ctrl_dev, ring_dev = jax.device_put(
                    (ctrl[None], ring[None]), self.device)
                prof = None
                if self._profile:
                    (ws, carry, acks, results, ctrl_out, prof,
                     self._tick) = self._drain(
                        ctrl_dev, ring_dev, self._ws, self._carry,
                        self._tick)
                    prof = prof[0]
                else:
                    ws, carry, acks, results, ctrl_out = self._drain(
                        ctrl_dev, ring_dev, self._ws, self._carry)
                # async dispatch: return as soon as the drain is enqueued
                self._ws = ws
                self._carry = carry
                blk = _Block(results[0], acks[0], len(block), True,
                             prof=prof, t_trigger_us=now_us())
                self._inflight.append(blk)
                self._ctrl_pending.append((blk, ctrl_out))
            self.doorbells += 1
            self.batched_steps += len(block)
            self.steps += len(block)
            self.tracker.record_depth(self.inflight)
        self.status = mb.THREAD_WORKING
        return len(descs)

    def _on_block_retired(self, blk: _Block) -> None:
        """A drain launch fully retired: fold its device-stamped
        QC_DRAINED work count into ``work_drained`` (the launch's outputs
        are already materialized, so this readback is free)."""
        if self._ctrl_pending and self._ctrl_pending[0][0] is blk:
            _, ctrl_out = self._ctrl_pending.popleft()
            self.work_drained += int(
                np.asarray(ctrl_out)[0, mb.QC_DRAINED])

    # ------------------------------------------------------------------
    @property
    def state(self):
        return self._ws

    def dispose(self) -> None:
        """Release device state — O(µs), blocking teardown deferred to
        ``reap_deferred()`` exactly like ``PersistentRuntime``."""
        with self.tracker.phase("dispose", "lk.dispose"):
            held = (self._drain,)
            if self._inflight or self._ws is not None:
                P._DEFERRED_TEARDOWN.append(
                    (list(self._inflight),
                     (self._ws, self._carry, self._tick), held))
            self._inflight.clear()
            self._oldest_ready = False
            self._ctrl_pending.clear()
            self._ws = None
            self._carry = None
            self._tick = None
            self._drain = None
        self.status = mb.THREAD_EXIT
        if len(P._DEFERRED_TEARDOWN) > P._DEFERRED_CAP:
            P.reap_deferred()


def mega_work_classes(**overrides) -> list:
    """``WorkClass`` declarations matching the drain kernel's opcode
    table, in registration order — boot ``LkSystem(runtime="mega")``
    from these, or the default scan runtime from the SAME list (the fns
    are ``tile_work_table()``'s scan-path twins) for an apples-to-apples
    comparison. ``overrides`` maps a class name to WorkClass field
    overrides, e.g. ``reduce={"chunk_us": 50.0}``."""
    from repro.core.system import WorkClass     # local: avoid import cycle
    unknown = set(overrides) - set(TILE_OP_NAMES)
    if unknown:
        raise KeyError(f"unknown tile op(s): {sorted(unknown)}")
    out = []
    for entry in tile_work_table():
        name, fn = entry[0], entry[1]
        carry = entry[2] if len(entry) > 2 else None
        out.append(WorkClass(name, fn=fn, carry=carry,
                             **overrides.get(name, {})))
    return out
