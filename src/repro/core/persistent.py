"""Persistent runtime — the paper's persistent-kernel execution model at the
XLA step granularity.

Boot once (compile + make all heavy state device-resident), then each work
item is dispatched by transferring ONLY a DESC_WIDTH-int32 mailbox vector;
the device program (``lk_step``) switches on the opcode and mutates the
donated state in place. This is the TPU analogue of LK's "spawn one kernel,
then poke mailboxes" (DESIGN §2): Trigger = async dispatch enqueue, Wait =
block_until_ready, exactly the paper's phase split.

The Trigger/Wait split is pipelined: up to ``max_inflight`` steps may be
enqueued before the first is retired, so the host keeps feeding mailboxes
while the device runs (the paper's whole point — async Trigger, separate
Wait). Steps retire strictly in FIFO order; the chain of donated states
gives XLA the data dependence that serializes them on device.

Batched doorbells: ``trigger_many(descs)`` stacks up to ``max_steps``
descriptors into ONE ``(max_steps, DESC_WIDTH)`` device transfer and ONE
compiled call — a ``lax.scan`` over the descriptor ring threads the state
and carries through every step device-side (the true multi-step
persistent loop: the host refills the ring, the device consumes it).
The scan's stacked outputs form the ACK BLOCK: one ``(max_steps,
DESC_WIDTH)`` ``from_gpu`` array materialized with a single readback when
the block's first step is waited on, after which the remaining steps
retire from host memory at deque speed. Unused ring rows are padded with
NOP descriptors (the nop branch of the step — they cost nothing and are
never surfaced).

Donation is BACKEND-AWARE (``donate=None``): on CPU, XLA runs donated
executables synchronously — the enqueue absorbs the whole computation and
the async Trigger/Wait split silently degenerates to run-to-completion
per call (measured: a donated step's "enqueue" costs the full step, a
plain one returns in tens of µs with the compute landing in Wait). Auto
mode therefore donates only on accelerator backends, where donation is
both supported and the memory win is real; pass ``donate=True``/``False``
to force either.

Double-buffered descriptors: a chunked item's NEXT chunk descriptor is
staged device-side (``chunk + 1`` computed by a tiny compiled advance
program) while the current chunk runs, so re-triggering a preempted
remainder costs no fresh host transfer — the staged buffer is consumed
on a key match (``staged_hits`` counts them).

Chunked (resumable) work: the full work-fn contract is

    fn(state, carry, desc) -> (state, carry, result, done)

where ``carry`` is the opcode's PRIVATE resumable scratch (one device-
resident tree per opcode, threaded through every step alongside the
donated state) and ``done`` is a scalar bool — False means "this chunk
finished but the item has more chunks", which the step reports to the
host as ``THREAD_PREEMPTED`` so the dispatcher can requeue the remainder
(``desc`` carries ``chunk``/``n_chunks``). Legacy two-argument fns
``fn(state, desc) -> (state, result)`` are auto-wrapped as always-done
atomic work, so existing work tables keep compiling unchanged. The carry
is CLUSTER-LOCAL scratch: a remainder replayed onto a different cluster
after a failure sees that cluster's (freshly booted) carry, so chunk fns
must either rebuild their progress from ``state`` + the descriptor's
``chunk`` word or keep cross-chunk results in ``state``.
"""
from __future__ import annotations

import inspect
from collections import deque
from typing import Any, Callable, Optional, Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mailbox as mb
from repro.core.telemetry import (EV_CHUNK_RETIRE, EV_RT_RETIRE,
                                  EV_RT_TRIGGER, TraceCollector)
from repro.core.telemetry.events import now_us
from repro.core.wcet import WcetTracker


def _tree_key(tree) -> tuple:
    """Hashable structural fingerprint of a pytree: (treedef, per-leaf
    (shape, dtype)). Two trees with equal keys compile to byte-identical
    executables for the same program — the ExecutableCache's keying
    primitive."""
    leaves, treedef = jax.tree.flatten(tree)
    return (treedef,
            tuple((jnp.shape(leaf), str(jnp.result_type(leaf)))
                  for leaf in leaves))


def _device_key(device) -> Optional[tuple]:
    """Hashable identity of the device a runtime is placed on (None = the
    default device): compiled executables are bound to their device, so
    it is part of every ExecutableCache key."""
    return None if device is None else (device.platform, device.id)


class ExecutableCache:
    """Shared cache of compiled persistent-step executables.

    A recarve boots fresh ``PersistentRuntime``s whose programs are
    IDENTICAL to the ones just disposed — same work fns, same state/carry
    shapes, same donate mode — yet each boot re-pays the full XLA
    lower+compile (~184ms ``lk_init`` in BENCH_7). Compiled executables
    are stateless (the traced program closes over nothing mutable), so
    one cache shared across a fleet turns every post-first boot into a
    dictionary hit. Keys fingerprint everything the trace depends on:
    the ORIGINAL work-fn objects (pre-``_normalize_work_fn``: the
    wrappers are per-runtime closures with fresh ids), the result
    template, the state/carries tree structure + leaf shapes/dtypes, the
    donate flag, the device the runtime is placed on, ``DESC_WIDTH``,
    and — for the multi-step ring variant — ``max_steps``. Runtimes with a
    mesh/shardings bypass the cache (sharded lowering bakes in device
    placement).

    Not thread-safe; callers share it from one dispatch loop
    (``LkSystem`` passes one instance to every runtime it boots).
    """

    def __init__(self):
        self._entries: dict = {}
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._entries)

    def get_or_compile(self, key: tuple, compile_fn: Callable):
        exe = self._entries.get(key)
        if exe is not None:
            self.hits += 1
            return exe
        self.misses += 1
        exe = compile_fn()
        self._entries[key] = exe
        return exe

    def counters(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "entries": len(self._entries)}


# Teardown work handed off by ``dispose()`` — each entry is
# ``(in_flight_blocks, (state, carries))`` whose blocking finalization
# (drain + buffer deletes) runs in ``reap_deferred()`` instead of on the
# dispose hot path. Bounded: past _DEFERRED_CAP entries, dispose reaps
# inline so unreaped teardown can't grow without limit.
_DEFERRED_TEARDOWN: list = []
_DEFERRED_CAP = 16


def reap_deferred() -> int:
    """Finalize every teardown deferred by ``dispose()``: block until the
    disposed runtimes' in-flight steps finish, then delete their device
    buffers. Returns the number of runtimes finalized. Called from
    ``LkSystem.reap()`` (and by dispose itself past the backstop cap);
    safe to call any time, idempotent when nothing is pending."""
    n = 0
    while _DEFERRED_TEARDOWN:
        # the third element holds the runtime's compiled executables:
        # releasing a LAST executable reference runs a multi-ms XLA
        # destructor, so that release lands here (with a shared
        # ExecutableCache the cache still holds them and the drop is free)
        blocks, trees, _executables = _DEFERRED_TEARDOWN.pop()
        for blk in blocks:
            jax.block_until_ready((blk.results, blk.acks, blk.prof))
        for tree in trees:
            if tree is None:
                continue
            for leaf in jax.tree.leaves(tree):
                try:
                    leaf.delete()
                except Exception:   # donated/aliased leaves may be gone
                    pass
        n += 1
    return n


def _normalize_work_fn(fn: Callable) -> Callable:
    """Accept both work-fn generations: the chunk-aware
    ``fn(state, carry, desc) -> (state, carry, result, done)`` passes
    through; a legacy ``fn(state, desc) -> (state, result)`` is wrapped as
    atomic always-done work with a pass-through carry. Classification
    counts REQUIRED positional parameters, so a legacy fn with defaulted
    extras (``fn(state, desc, cfg=CFG)``) stays legacy."""
    try:
        required = sum(
            1 for p in inspect.signature(fn).parameters.values()
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
            and p.default is p.empty)
    except (TypeError, ValueError):     # builtins/partials without sigs
        required = 2
    if required >= 3:
        return fn

    def atomic(state, carry, desc):
        state, result = fn(state, desc)
        return state, carry, result, jnp.asarray(True)

    return atomic


@runtime_checkable
class RuntimeProtocol(Protocol):
    """The contract the Dispatcher requires of a per-cluster runtime.

    ``max_inflight`` is the EXPLICIT pipeline-capacity attribute every
    runtime must declare — the dispatcher reads it directly (no duck-typed
    ``getattr`` fallback), so a runtime that forgets it fails loudly at
    registration instead of silently serializing its cluster.
    ``PersistentRuntime`` implements this; test doubles and any future
    runtime (remote, multi-host, …) must too.
    """

    max_inflight: int

    def trigger(self, desc) -> None: ...        # async enqueue

    def ready(self) -> bool: ...                # oldest step finished?

    def wait(self) -> tuple: ...                # block; (result, from_gpu)


def _tree_ready(tree) -> bool:
    """True when every leaf of an async jax result has materialized."""
    for leaf in jax.tree.leaves(tree):
        is_ready = getattr(leaf, "is_ready", None)
        if is_ready is not None and not is_ready():
            return False
    return True


class _Block:
    """One in-flight pipeline entry: a single step (``n == 1``,
    ``stacked=False``) or a batched multi-step call whose stacked results
    and ack block retire item by item (``idx`` walks the block). The
    device arrays are swapped for host copies at materialization — ONE
    readback per block, however many items it holds.

    ``prof`` optionally carries the flight-recorder profile rows of the
    block's launch (``(n, PROF_WIDTH)`` or ``(PROF_WIDTH,)`` int32, see
    ``core.mailbox``); they join the same bulk readback. ``t_trigger_us``
    anchors the launch's host window for tick calibration."""

    __slots__ = ("results", "acks", "n", "idx", "stacked", "host_acks",
                 "prof", "host_prof", "t_trigger_us")

    def __init__(self, results, acks, n: int, stacked: bool,
                 prof=None, t_trigger_us: int = 0):
        self.results = results
        self.acks = acks
        self.n = n
        self.idx = 0
        self.stacked = stacked
        self.host_acks = None      # set at materialization
        self.prof = prof
        self.host_prof = None
        self.t_trigger_us = t_trigger_us

    @property
    def remaining(self) -> int:
        return self.n - self.idx

    def materialize(self) -> None:
        """Block until the whole block finished; ONE ack readback."""
        if self.host_acks is not None:
            return
        self.results = jax.block_until_ready(self.results)
        self.host_acks = np.asarray(self.acks)
        if self.prof is not None:
            self.host_prof = np.atleast_2d(np.asarray(self.prof))
        if self.stacked:
            # one bulk readback of the stacked results too: per-item
            # device gathers would re-pay a dispatch per retirement
            self.results = jax.tree.map(np.asarray, self.results)

    def pop_item(self) -> tuple:
        """(result, from_gpu) of the next unretired item (materialized)."""
        i = self.idx
        self.idx += 1
        if not self.stacked:
            return self.results, self.host_acks
        return (jax.tree.map(lambda a: a[i], self.results),
                self.host_acks[i])


class _PipelinedRuntime:
    """Pipeline mechanics shared by every device-backed runtime: the
    bounded in-flight deque of ``_Block``s, memoized oldest-ready polling,
    strict-FIFO ``wait()``/``poll()``/``wait_all()`` retirement with ONE
    bulk readback per block, and retire-time telemetry. Subclasses own the
    TRIGGER side — how descriptors reach the device (``PersistentRuntime``
    feeds a host-refilled scan ring; ``repro.core.mega.MegaRuntime`` hands
    the device a whole control-worded queue) — plus the ``booted``
    predicate and the ``_on_block_retired`` hook."""

    def __init__(self, tracker: Optional[WcetTracker] = None,
                 max_inflight: int = 2,
                 telemetry: Optional[TraceCollector] = None,
                 name: str = "lk"):
        if max_inflight < 1:
            raise ValueError("max_inflight must be >= 1")
        self.tracker = tracker or WcetTracker(name)
        self.max_inflight = int(max_inflight)
        self._inflight: deque[_Block] = deque()
        self._oldest_ready = False     # memoized ready() of the oldest block
        self.status = mb.THREAD_INIT
        self.steps = 0
        # runtime-level telemetry: step enqueue/retire instants with the
        # in-flight depth — the device-facing view of the same timeline
        # the dispatcher annotates with scheduling decisions. The cluster
        # id is assigned by whoever registers this runtime (LkSystem).
        self.telemetry = telemetry
        self.telemetry_cluster = -1
        # flight-recorder anchor: host end of the previously retired
        # block — the next block's device ticks are mapped into
        # [max(trigger, here), materialize] so per-cluster device spans
        # never overlap across launches (monotone merged timeline)
        self._last_block_end_us = 0.0
        self.device_spans = 0          # device-stamped spans re-emitted

    @property
    def booted(self) -> bool:
        raise NotImplementedError

    @property
    def inflight(self) -> int:
        """Number of enqueued-but-unretired steps (batch items counted)."""
        return sum(blk.remaining for blk in self._inflight)

    @property
    def can_trigger(self) -> bool:
        return self.booted and self.inflight < self.max_inflight

    def _on_block_retired(self, blk: _Block) -> None:
        """Hook: the oldest block fully retired (subclass bookkeeping)."""

    def ready(self) -> bool:
        """Non-blocking: has the OLDEST in-flight step finished on device?
        The check is memoized — once the oldest block reports ready it
        stays ready until retired, so pump loops that poll ``ready()``
        before every retirement don't re-walk the tree each time."""
        if not self._inflight:
            return False
        if self._oldest_ready:
            return True
        blk = self._inflight[0]
        self._oldest_ready = blk.host_acks is not None or \
            _tree_ready((blk.results, blk.acks, blk.prof))
        return self._oldest_ready

    def _retire_block_profile(self, blk: _Block) -> None:
        """Decode a just-materialized block's flight-recorder rows and
        re-emit them as ``chunk_retire`` spans with ``source=device``.

        Device ticks are LOGICAL (no wall clock exists device-side); the
        per-launch anchor maps them affinely into the block's host window
        ``[max(trigger, previous block end), materialize]``, which keeps
        every cluster's merged device+host timeline monotone."""
        end = float(now_us())
        start = max(float(blk.t_trigger_us), self._last_block_end_us)
        if end < start + 1.0:
            end = start + 1.0
        self._last_block_end_us = end
        prof = blk.host_prof
        if prof is None or self.telemetry is None:
            return
        idxs = np.nonzero(prof[:, mb.P_ACTIVE])[0]
        if idxs.size == 0:
            return
        acks = np.atleast_2d(blk.host_acks)
        t0s = prof[idxs, mb.P_TICK0].astype(np.float64)
        t1s = prof[idxs, mb.P_TICK1].astype(np.float64)
        lo = float(t0s.min())
        scale = (end - start) / max(float(t1s.max()) - lo, 1.0)
        for j, i in enumerate(idxs):
            s = float(start + (t0s[j] - lo) * scale)
            d = float(max((t1s[j] - t0s[j]) * scale, 1.0))
            self.telemetry.emit(
                EV_CHUNK_RETIRE, cluster=self.telemetry_cluster,
                request_id=int(prof[i, mb.P_REQID]),
                opcode=int(prof[i, mb.P_OPCODE]),
                chunk=int(acks[i, mb.W_CHUNK]),
                source="device", start_us=s, dur_us=d,
                tick=int(prof[i, mb.P_TICK0]),
                row=int(prof[i, mb.P_ROW]),
                qdepth=int(prof[i, mb.P_QDEPTH]))
            self.device_spans += 1

    def wait(self):
        """Block until the oldest in-flight step completes; returns
        (result, from_gpu). Steps retire strictly in trigger order. The
        first wait on a batched block materializes the WHOLE ack block
        (one readback); its remaining items then retire host-side."""
        assert self._inflight, "nothing in flight"
        blk = self._inflight[0]
        with self.tracker.phase("wait"):
            first = blk.host_acks is None
            blk.materialize()
            if first:
                self._retire_block_profile(blk)
            result, from_gpu = blk.pop_item()
            if blk.remaining == 0:
                self._inflight.popleft()
                self._oldest_ready = False
                self._on_block_retired(blk)
        self.status = (mb.THREAD_WORKING if self._inflight
                       else int(from_gpu[mb.W_STATUS]))
        if self.telemetry is not None:
            self.telemetry.emit(
                EV_RT_RETIRE, cluster=self.telemetry_cluster,
                request_id=int(from_gpu[mb.W_REQID]),
                chunk=int(from_gpu[mb.W_CHUNK]),
                status=int(from_gpu[mb.W_STATUS]),
                depth=self.inflight)
        return result, from_gpu

    def poll(self):
        """Retire the oldest in-flight step iff it already completed;
        returns (result, from_gpu) or None."""
        if not self.ready():
            return None
        return self.wait()

    def wait_all(self) -> list:
        """Drain the pipeline; returns retired (result, from_gpu) in order."""
        out = []
        while self._inflight:
            out.append(self.wait())
        return out

    def run_sync(self, desc):
        self.trigger(desc)
        return self.wait()


class PersistentRuntime(_PipelinedRuntime):
    """One persistent worker (paper: one SM / one cluster).

    work_fns: list of ``(name, fn)`` or ``(name, fn, carry_template)``.
    ``fn`` is either chunk-aware ``fn(state, carry, desc) -> (state, carry,
    result, done)`` or legacy ``fn(state, desc) -> (state, result)`` (auto-
    wrapped as atomic). All fns must return structurally identical (state,
    result) trees — they are branches of one ``lax.switch``; each opcode's
    carry tree is private (initialized from ``carry_template``, a scalar
    zero when omitted) and device-resident across steps.
    ``result_template`` gives the result structure returned for NOP steps
    (zeros).

    ``max_inflight`` bounds the in-flight pipeline: ``trigger()`` returns at
    enqueue, ``wait()`` (blocking) / ``poll()`` (non-blocking) retire the
    oldest step, ``wait_all()`` drains. ``trigger()`` on a full pipeline
    raises — callers gate on ``can_trigger``. ``trigger_many()`` issues up
    to ``max_steps`` descriptors as ONE batched doorbell (one transfer,
    one compiled multi-step call); its items still retire one at a time
    through ``wait()``/``poll()``, but the whole ack block materializes
    with a single readback. ``donate=None`` donates the state only on
    accelerator backends (donation serializes dispatch on CPU — see the
    module docstring).

    ``staged_cap`` bounds the next-chunk double buffer. Eviction prefers
    entries whose item is NOT in flight any more (finished items drop
    their staged chunks at retirement, so live entries survive interleaved
    multi-item chunking up to the cap); ``staged_hits`` counts re-triggers
    served device-side, ``staged_misses`` counts mid-item re-triggers that
    had to pay a fresh host transfer because their staged entry was
    evicted (or staging is off).

    ``device`` places the state, carries, descriptors and compiled
    programs on one device (a cluster's chip); None keeps JAX's default
    device. A ``mesh`` with ``state_shardings`` places the state by those
    shardings instead.
    """

    def __init__(self, work_fns: Sequence[tuple],
                 result_template: Any,
                 tracker: Optional[WcetTracker] = None,
                 mesh=None,
                 state_shardings=None,
                 donate: Optional[bool] = None,
                 max_inflight: int = 2,
                 max_steps: int = 8,
                 telemetry: Optional[TraceCollector] = None,
                 exec_cache: Optional[ExecutableCache] = None,
                 staged_cap: int = 4,
                 profile: Optional[bool] = None,
                 device=None):
        super().__init__(tracker=tracker, max_inflight=max_inflight,
                         telemetry=telemetry, name="lk")
        if max_steps < 1:
            raise ValueError("max_steps must be >= 1")
        if staged_cap < 0:
            raise ValueError("staged_cap must be >= 0")
        self.work_names = [entry[0] for entry in work_fns]
        # the cache keys on the ORIGINAL fn objects: the normalized
        # wrappers below are per-runtime closures with distinct identities
        self._orig_fns = tuple(entry[1] for entry in work_fns)
        self._fns = [_normalize_work_fn(entry[1]) for entry in work_fns]
        self._carry_templates = [
            entry[2] if len(entry) > 2 else jnp.zeros((), jnp.int32)
            for entry in work_fns]
        self._result_template = result_template
        self.mesh = mesh
        self._state_shardings = state_shardings
        self.device = device
        self._donate = donate
        self._exec_cache = exec_cache
        self._state = None
        self._carries = None
        self.max_steps = int(max_steps)
        self._compiled = None
        self._compiled_multi = None    # lazy: first trigger_many compiles it
        self._advance = None           # compiled device-side chunk advance
        # flight recorder (None = auto: on exactly when telemetry is
        # attached): the profiled step variants thread a persistent
        # logical-tick scalar and return per-step PROF_WIDTH rows that
        # join the block's bulk readback — the bare programs and their
        # ack records are untouched when off
        self._profile = profile
        self._tick = None
        # staged next-chunk descriptors (double buffer): key -> device vec
        self._staged: dict[tuple[int, int], Any] = {}
        self._staged_cap = int(staged_cap)
        # request ids with a LIVE mid-item chunk sequence: these items'
        # staged entries are evicted LAST (dropping the very next chunk of
        # an in-flight item forces a pointless host re-transfer)
        self._live_rids: set[int] = set()
        self.staged_hits = 0           # re-triggers served device-side
        self.staged_misses = 0         # evicted/unstaged mid-item re-triggers
        self.doorbells = 0             # batched trigger_many transfers
        self.batched_steps = 0         # steps issued through doorbells

    # ------------------------------------------------------------------
    def _lk_step(self, state, carries, desc):
        status = desc[mb.W_STATUS]
        opcode = jnp.clip(desc[mb.W_OPCODE], 0, len(self._fns) - 1)
        is_work = status >= mb.THREAD_WORK

        zero_result = jax.tree.map(
            lambda x: jnp.zeros(x.shape, x.dtype), self._result_template)

        def nop_branch(state, carries, desc):
            return state, carries, zero_result, jnp.asarray(True)

        def work_branch(state, carries, desc):
            def branch(i, fn):
                def run(state, carries, desc):
                    state, carry, result, done = fn(state, carries[i], desc)
                    carries = tuple(carry if j == i else c
                                    for j, c in enumerate(carries))
                    return state, carries, result, jnp.asarray(done)
                return run
            return jax.lax.switch(
                opcode, [branch(i, f) for i, f in enumerate(self._fns)],
                state, carries, desc)

        state, carries, result, done = jax.lax.cond(
            is_work, work_branch, nop_branch, state, carries, desc)
        from_gpu = jnp.zeros((mb.DESC_WIDTH,), jnp.int32)
        from_gpu = from_gpu.at[mb.W_STATUS].set(
            jnp.where(is_work,
                      jnp.where(done, mb.THREAD_FINISHED,
                                mb.THREAD_PREEMPTED),
                      mb.THREAD_NOP))
        from_gpu = from_gpu.at[mb.W_REQID].set(desc[mb.W_REQID])
        from_gpu = from_gpu.at[mb.W_CHUNK].set(desc[mb.W_CHUNK])
        from_gpu = from_gpu.at[mb.W_NCHUNKS].set(desc[mb.W_NCHUNKS])
        return state, carries, result, from_gpu

    def _lk_multi_step(self, state, carries, ring):
        """True multi-step persistent loop: one compiled call consumes the
        whole descriptor ring (``(max_steps, DESC_WIDTH)``), threading the
        state and per-opcode carries through every step exactly as the
        host-stepped ``_lk_step`` chain would — token-identical by
        construction (the scan body IS ``_lk_step``). NOP-padded rows run
        the nop branch. Outputs are the stacked results and the ack
        block."""
        def body(sc, desc):
            state, carries = sc
            state, carries, result, from_gpu = self._lk_step(
                state, carries, desc)
            return (state, carries), (result, from_gpu)
        (state, carries), (results, acks) = jax.lax.scan(
            body, (state, carries), ring)
        return state, carries, results, acks

    def _lk_step_prof(self, state, carries, tick, desc,
                      row_idx=0, qdepth=1):
        """``_lk_step`` plus the flight-recorder words: stamps a
        PROF_WIDTH profile row (begin/end tick, per-launch row counter,
        queue occupancy at pop — see ``core.mailbox``) and advances the
        persistent logical-tick scalar by one per work step. The ack
        record is byte-identical to the bare step's."""
        state, carries, result, from_gpu = self._lk_step(
            state, carries, desc)
        act = (desc[mb.W_STATUS] >= mb.THREAD_WORK).astype(jnp.int32)
        prof = jnp.zeros((mb.PROF_WIDTH,), jnp.int32)
        prof = prof.at[mb.P_TICK0].set(act * tick)
        prof = prof.at[mb.P_TICK1].set(act * (tick + 1))
        prof = prof.at[mb.P_ROW].set(act * row_idx)
        prof = prof.at[mb.P_QDEPTH].set(act * qdepth)
        prof = prof.at[mb.P_OPCODE].set(act * desc[mb.W_OPCODE])
        prof = prof.at[mb.P_REQID].set(act * desc[mb.W_REQID])
        prof = prof.at[mb.P_ACTIVE].set(act)
        return state, carries, tick + act, result, from_gpu, prof

    def _lk_multi_step_prof(self, state, carries, tick, ring):
        """Profiled twin of ``_lk_multi_step``: the scan carry also
        threads the tick scalar and a seen-work counter, so each row's
        profile record gets its launch-row index and the ring occupancy
        at pop (total work rows minus work already consumed) — all
        computed device-side."""
        total = jnp.sum(
            (ring[:, mb.W_STATUS] >= mb.THREAD_WORK).astype(jnp.int32))

        def body(sc, desc):
            state, carries, tick, seen = sc
            state, carries, tick, result, from_gpu, prof = \
                self._lk_step_prof(state, carries, tick, desc,
                                   row_idx=seen, qdepth=total - seen)
            seen = seen + (desc[mb.W_STATUS] >=
                           mb.THREAD_WORK).astype(jnp.int32)
            return (state, carries, tick, seen), (result, from_gpu, prof)
        (state, carries, tick, _), (results, acks, profs) = jax.lax.scan(
            body, (state, carries, tick, jnp.int32(0)), ring)
        return state, carries, tick, results, acks, profs

    # ------------------------------------------------------------------
    def _cache_key(self, variant: str, state, carries) -> tuple:
        """ExecutableCache key for this runtime's ``variant`` program.
        Fingerprints everything the traced computation depends on; two
        runtimes with equal keys can share one compiled executable."""
        return (variant, self._orig_fns, _tree_key(self._result_template),
                _tree_key(state), _tree_key(carries), bool(self._donate),
                _device_key(self.device), mb.DESC_WIDTH,
                self.max_steps if variant.startswith("multi") else 0)

    def boot(self, state) -> None:
        """Init phase: compile the persistent step and make state resident.
        With a shared ``exec_cache``, a runtime whose program fingerprint
        was compiled before (same work fns / shapes / donate) skips the
        XLA compile entirely — the warm-reboot path of an elastic
        recarve."""
        with self.tracker.phase("init"):
            if self._donate is None:
                # donation serializes dispatch on CPU (module docstring):
                # auto mode keeps the async Trigger/Wait split alive there
                # and donates only where XLA actually aliases buffers
                self._donate = jax.default_backend() != "cpu"
            kwargs = {}
            if self._donate:
                kwargs["donate_argnums"] = (0, 1)
            desc0 = jax.device_put(mb.nop_descriptor(), self.device)
            if self.mesh is not None and self._state_shardings is not None:
                state = jax.device_put(state, self._state_shardings)
            else:
                state = jax.device_put(state, self.device)
            # COPY the templates before donating: device_put on an array
            # already on device aliases it, and donation would delete the
            # caller's template out from under every other runtime booted
            # from the same object (LkSystem boots one per cluster)
            carries = jax.device_put(tuple(
                jax.tree.map(jnp.array, t) for t in self._carry_templates),
                self.device)
            if self._profile is None:
                self._profile = self.telemetry is not None
            tick0 = jax.device_put(jnp.zeros((), jnp.int32), self.device) \
                if self._profile else None

            def compile_step():
                if self._profile:
                    return jax.jit(self._lk_step_prof, **kwargs).lower(
                        state, carries, tick0, desc0).compile()
                return jax.jit(self._lk_step, **kwargs).lower(
                    state, carries, desc0).compile()

            def compile_advance():
                return jax.jit(
                    lambda d: d.at[mb.W_CHUNK].add(1)).lower(
                        desc0).compile()

            variant = "step_prof" if self._profile else "step"
            if self._exec_cache is not None and self.mesh is None:
                self._compiled = self._exec_cache.get_or_compile(
                    self._cache_key(variant, state, carries), compile_step)
                self._advance = self._exec_cache.get_or_compile(
                    ("advance", _device_key(self.device), mb.DESC_WIDTH),
                    compile_advance)
            else:
                self._compiled = compile_step()
                # the double buffer's device-side descriptor advance
                self._advance = compile_advance()
            self._state = state
            self._carries = carries
            self._tick = tick0
        self.status = mb.THREAD_NOP

    def _ensure_multi(self):
        """Compile the ring variant on first use — booting pays only the
        single-step compile, batch users pay the scan compile once (per
        shared cache when one is attached)."""
        if self._compiled_multi is None:
            kwargs = {}
            if self._donate:
                kwargs["donate_argnums"] = (0, 1)
            ring0 = jax.device_put(
                np.tile(mb.nop_descriptor(), (self.max_steps, 1)),
                self.device)

            def compile_multi():
                if self._profile:
                    return jax.jit(
                        self._lk_multi_step_prof, **kwargs).lower(
                            self._state, self._carries, self._tick,
                            ring0).compile()
                return jax.jit(self._lk_multi_step, **kwargs).lower(
                    self._state, self._carries, ring0).compile()

            variant = "multi_prof" if self._profile else "multi"
            if self._exec_cache is not None and self.mesh is None:
                self._compiled_multi = self._exec_cache.get_or_compile(
                    self._cache_key(variant, self._state, self._carries),
                    compile_multi)
            else:
                self._compiled_multi = compile_multi()
        return self._compiled_multi

    # ------------------------------------------------------------------
    @property
    def booted(self) -> bool:
        return self._compiled is not None

    @staticmethod
    def _desc_fields(desc) -> tuple:
        """(request_id, opcode, chunk, n_chunks, encoded) from either a
        WorkDescriptor or an encoded vector — host-side ints, read ONCE
        (the zero-readback hot path: no repeated numpy conversions)."""
        if isinstance(desc, mb.WorkDescriptor):
            return (desc.request_id, desc.opcode, desc.chunk,
                    desc.n_chunks, None)
        enc = np.asarray(desc)
        return (int(enc[mb.W_REQID]), int(enc[mb.W_OPCODE]),
                int(enc[mb.W_CHUNK]), int(enc[mb.W_NCHUNKS]), enc)

    def _stage_next(self, rid: int, chunk: int, n_chunks: int,
                    dvec) -> None:
        """Double buffer: stage the NEXT chunk's descriptor device-side
        (a compiled ``chunk += 1``) while the current chunk runs, so a
        remainder re-trigger pays no fresh host transfer. Bounded by
        ``staged_cap``; eviction takes non-inflight entries first (a
        finished item's leftovers, a replayed-away remainder) and only
        then the oldest LIVE entry — never the one just staged."""
        if n_chunks <= chunk + 1 or self._staged_cap <= 0:
            return
        just_staged = (rid, chunk + 1)
        self._staged[just_staged] = self._advance(dvec)
        self._live_rids.add(rid)
        while len(self._staged) > self._staged_cap:
            keys = [k for k in self._staged if k != just_staged]
            if not keys:
                break
            stale = [k for k in keys if k[0] not in self._live_rids]
            self._staged.pop(stale[0] if stale else keys[0])

    def trigger(self, desc) -> None:
        """Send one mailbox descriptor (async — returns at enqueue)."""
        if self._compiled is None:
            raise RuntimeError("boot() first")
        if self.inflight >= self.max_inflight:
            raise RuntimeError(
                f"in-flight pipeline full (max_inflight={self.max_inflight});"
                " retire with wait()/poll() first")
        rid, opcode, chunk, n_chunks, enc = self._desc_fields(desc)
        with self.tracker.phase("trigger"):
            dvec = self._staged.pop((rid, chunk), None)
            if dvec is not None:
                self.staged_hits += 1          # device-resident re-trigger
            else:
                if chunk > 0:
                    # a mid-item re-trigger whose staged entry was evicted
                    # (or staging is capped off): the fresh transfer below
                    # is exactly the cost the double buffer exists to hide
                    self.staged_misses += 1
                dvec = jax.device_put(
                    enc if enc is not None else desc.encode(), self.device)
            self._stage_next(rid, chunk, n_chunks, dvec)
            prof = None
            if self._profile:
                (new_state, new_carries, self._tick, result, from_gpu,
                 prof) = self._compiled(
                    self._state, self._carries, self._tick, dvec)
            else:
                new_state, new_carries, result, from_gpu = self._compiled(
                    self._state, self._carries, dvec)
            # async dispatch: we return as soon as the work is enqueued
            self._state = new_state
            self._carries = new_carries
            self._inflight.append(_Block(result, from_gpu, 1, False,
                                         prof=prof,
                                         t_trigger_us=now_us()))
        self.tracker.record_depth(self.inflight)
        if self.telemetry is not None:
            self.telemetry.emit(
                EV_RT_TRIGGER, cluster=self.telemetry_cluster,
                request_id=rid, opcode=opcode, chunk=chunk,
                depth=self.inflight)
        self.status = mb.THREAD_WORKING
        self.steps += 1

    def trigger_many(self, descs) -> int:
        """Batched doorbell: issue N descriptors as ``ceil(N/max_steps)``
        ring transfers + compiled multi-step calls (ONE of each when
        ``N <= max_steps``), instead of N transfers + N dispatches. Items
        retire through ``wait()``/``poll()`` in issue order, exactly as N
        sequential ``trigger()`` calls would; returns N."""
        if self._compiled is None:
            raise RuntimeError("boot() first")
        descs = list(descs)
        if not descs:
            return 0
        if self.inflight + len(descs) > self.max_inflight:
            raise RuntimeError(
                f"batch of {len(descs)} exceeds pipeline capacity "
                f"(max_inflight={self.max_inflight}, "
                f"inflight={self.inflight})")
        fn = self._ensure_multi()
        for base in range(0, len(descs), self.max_steps):
            block = descs[base:base + self.max_steps]
            ring = mb.descriptor_ring(block, self.max_steps)
            with self.tracker.phase("trigger"):
                ring_dev = jax.device_put(ring, self.device)
                profs = None
                if self._profile:
                    (new_state, new_carries, self._tick, results, acks,
                     profs) = fn(self._state, self._carries, self._tick,
                                 ring_dev)
                else:
                    new_state, new_carries, results, acks = fn(
                        self._state, self._carries, ring_dev)
                self._state = new_state
                self._carries = new_carries
                self._inflight.append(
                    _Block(results, acks, len(block), True, prof=profs,
                           t_trigger_us=now_us()))
            self.doorbells += 1
            self.batched_steps += len(block)
            self.steps += len(block)
            self.tracker.record_depth(self.inflight)
            if self.telemetry is not None:
                # one batch-stamped event per doorbell — the hot path
                # reads NOTHING back from the device for telemetry
                rid, opcode, chunk, _, _ = self._desc_fields(block[0])
                self.telemetry.emit(
                    EV_RT_TRIGGER, cluster=self.telemetry_cluster,
                    request_id=rid, opcode=opcode, chunk=chunk,
                    depth=self.inflight, batch=len(block))
        self.status = mb.THREAD_WORKING
        return len(descs)

    def wait(self):
        result, from_gpu = super().wait()
        if self._live_rids and \
                int(from_gpu[mb.W_STATUS]) == mb.THREAD_FINISHED:
            # the item is done: its rid leaves the live set and any
            # still-staged next-chunk entries become eviction fodder
            rid = int(from_gpu[mb.W_REQID])
            if rid in self._live_rids:
                self._live_rids.discard(rid)
                for k in [k for k in self._staged if k[0] == rid]:
                    del self._staged[k]
        return result, from_gpu

    # ------------------------------------------------------------------
    @property
    def state(self):
        return self._state

    def update_state(self, new_state) -> None:
        """Public state replacement (e.g. prefill insertion in serving).

        Safe under async dispatch as long as ``new_state`` is derived from
        ``self.state`` (donated lineage): XLA sequences the derivation after
        every in-flight step that produced it.
        """
        if self._compiled is None:
            raise RuntimeError("boot() first")
        self._state = new_state

    def dispose(self) -> None:
        """Release device state (paper: Dispose phase) — O(µs).

        The BLOCKING half of teardown (draining in-flight steps, deleting
        device buffers leaf by leaf) is handed to the module-level
        deferred list and finalized by :func:`reap_deferred` — typically
        from ``LkSystem.reap()``, off the latency path. Dispose itself
        only detaches: fields null out immediately (``state is None``,
        ``status == THREAD_EXIT`` hold on return, as before), so a live
        recarve's displaced runtimes stop serving in microseconds instead
        of milliseconds. Past ``_DEFERRED_CAP`` unreaped teardowns, the
        reap runs inline as a memory backstop."""
        with self.tracker.phase("dispose"):
            held = (self._compiled, self._compiled_multi, self._advance)
            if self._inflight or self._state is not None \
                    or self._carries is not None \
                    or any(x is not None for x in held):
                _DEFERRED_TEARDOWN.append(
                    (list(self._inflight),
                     (self._state, self._carries, self._tick), held))
            self._inflight.clear()
            self._oldest_ready = False
            self._staged.clear()
            self._live_rids.clear()
            self._state = None
            self._carries = None
            self._tick = None
            self._compiled = None
            self._compiled_multi = None
            self._advance = None
        self.status = mb.THREAD_EXIT
        if len(_DEFERRED_TEARDOWN) > _DEFERRED_CAP:
            reap_deferred()


class TraditionalRuntime:
    """The paper's baseline: every work item pays full launch cost.

    Mirrors a per-call CUDA kernel launch: arguments (including the heavy
    state) are re-staged host→device on every call, and the executable is
    re-dispatched from scratch. Used by benchmarks/bench_dispatch.py as the
    'CUDA Alloc/Spawn/Wait/Dispose' arm.
    """

    def __init__(self, work_fns, result_template,
                 tracker: Optional[WcetTracker] = None):
        # legacy 2-arg fns only: the per-call launch baseline has no
        # persistent carry to thread (any carry template entry is ignored)
        self._fns = {entry[0]: entry[1] for entry in work_fns}
        self._result_template = result_template
        self.tracker = tracker or WcetTracker("traditional")
        self._host_state = None
        self._compiled = {}

    def boot(self, state) -> None:
        with self.tracker.phase("init"):
            # keep state HOST-side (numpy) — re-staged per call, like kernel
            # arguments in the traditional path
            self._host_state = jax.tree.map(np.asarray, state)
            for name, fn in self._fns.items():
                dstate = jax.device_put(self._host_state)
                desc0 = jnp.asarray(mb.nop_descriptor())
                self._compiled[name] = jax.jit(fn).lower(
                    dstate, desc0).compile()
                jax.block_until_ready(dstate)

    def launch(self, name: str, desc):
        if isinstance(desc, mb.WorkDescriptor):
            desc = desc.encode()
        with self.tracker.phase("trigger"):
            dstate = jax.device_put(self._host_state)      # full re-staging
            pending = self._compiled[name](dstate, jnp.asarray(desc))
        with self.tracker.phase("wait"):
            new_state, result = jax.block_until_ready(pending)
        self._host_state = jax.tree.map(np.asarray, new_state)
        return result

    def dispose(self) -> None:
        with self.tracker.phase("dispose"):
            self._host_state = None
            self._compiled = {}
