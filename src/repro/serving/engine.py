"""Persistent serving engine — the paper's execution model applied to LM
inference.

Boot once: weights + KV caches + slot metadata become device-resident state
of a ``PersistentRuntime``. Each decode step is then triggered by a mailbox
descriptor only (DESC_WIDTH int32s) — no weight or cache re-staging — and
runs ONE lockstep decode for all active slots (continuous batching with
static shapes).

The engine is a *client of the shared Dispatcher*: ``decode``, ``insert``,
``prefill`` (when chunked) and ``release`` are opcodes in the runtime's
work table, and every step flows submit → ticket → trigger → retire →
resolve through the dispatcher's EDF queue and mailbox record. Each
submission's ``Ticket`` future carries its own result — the engine never
scans a shared completion list, so a long-running server's dispatcher
memory stays O(completion window).

Staging is PER-SLOT: the prefill→decode handoff area holds one batch-row
per engine slot (prompt, evolving batch-1 caches, first token, length — all
keyed by slot index), so any number of prefills may be outstanding at once
and ``add_request`` returns at SUBMISSION time. The OP_INSERT step that
copies a finished prefill's staging row into the main caches is chained
onto the prefill ticket's ``on_complete`` — no host thread ever blocks on
its own prefill, and decode steps submitted in between overlap it freely
(per-slot staging is what makes the interleaving safe: a decode step
touches only the main caches, a prefill chunk touches only its own staging
row).

Prefill runs host-side by default (one jit per prompt length), staged into
the slot's staging row via the public ``PersistentRuntime.update_state``.
With ``chunked_prefill=True`` the prompt instead runs device-side as a
CHUNKED OP_PREFILL item — ``ceil(L / prefill_chunk_tokens)`` resumable
chunks through the dispatcher, each a preemption point — so a long
prefill no longer occupies its cluster atomically: work already queued
on a SHARED dispatcher (another tenant's decode, another engine, or THIS
engine's own deadline-carrying decode steps) cuts in at every chunk
boundary, the declared ``chunk_us`` collapses admission's blocking term
from "one whole prompt" to one chunk, and budget charging happens per
chunk.

Slot lifecycle is explicit (``kv_cache`` phases): ``add_request`` binds a
slot in phase ``prefill``; the chained insert's resolution flips it to
``decoding`` (and records the first generated token); ``step`` harvests
only ``decoding`` slots, so a decode step that raced ahead of a pending
insert on device can never be misread as that slot's token. OP_RELEASE
deactivates a slot device-side without a host→device state rebuild — the
stream frontend uses it to evict shed streams and to close finished ones
(``step(auto_free=False)`` parks them in phase ``finished`` instead of
freeing host-side immediately).

Phases feed the WcetTracker: Init = boot/compile, Trigger = descriptor
dispatch, Wait = block_until_ready — directly comparable to paper Tables
II/III via benchmarks/bench_dispatch.py. The engine-owned dispatcher and a
stream frontend time their phases (pick, retire, poll, admit, harvest) in
the same tracker, and every phase is a span of a running profiler trace.
"""
from __future__ import annotations

import functools
from collections import deque
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mailbox as mb
from repro.core.dispatcher import Dispatcher, Ticket
from repro.core.persistent import PersistentRuntime
from repro.core.sched import (CRIT_HIGH, CRIT_LOW, BudgetedServerPolicy,
                              ClassSpec, SchedPolicy)
from repro.core.telemetry import EV_ENGINE, TraceCollector
from repro.core.wcet import WcetTracker
from repro.serving.kv_cache import (PH_DECODING, PH_FINISHED, SlotManager,
                                    extract_slot_caches, insert_slot_caches)

OP_DECODE = 0
OP_INSERT = 1
OP_PREFILL = 2          # present only when chunked_prefill=True
# OP_RELEASE is always the LAST opcode in the work table — read it from
# ``engine.op_release`` (2 without chunked prefill, 3 with).

# Decode is the latency-critical class: HIGH criticality (it may shed
# queued LOW work under overload) and — under the budgeted-server policy —
# a guaranteed 80%-bandwidth server, leaving 20% for inserts/background so
# neither side can starve the other.
DECODE_BUDGET_US = 80_000.0
DECODE_PERIOD_US = 100_000.0


class ServingEngine:
    def __init__(self, model, params, *, max_batch: int, max_seq: int,
                 prefill_bucket: int = 64, eos_id: int = -1,
                 tracker: Optional[WcetTracker] = None,
                 dispatcher: Optional[Dispatcher] = None,
                 cluster_id: int = 0, max_inflight: int = 2,
                 max_steps: int = 8,
                 donate: Optional[bool] = None,
                 completion_window: Optional[int] = None,
                 policy: Union[str, SchedPolicy, None] = None,
                 decode_budget_us: float = DECODE_BUDGET_US,
                 decode_period_us: float = DECODE_PERIOD_US,
                 chunked_prefill: bool = False,
                 prefill_chunk_tokens: Optional[int] = None,
                 prefill_chunk_us: Optional[float] = None,
                 telemetry: Optional[TraceCollector] = None):
        if telemetry is not None and dispatcher is not None:
            raise ValueError(
                "telemetry configures the engine-owned dispatcher; attach "
                "the collector to the shared Dispatcher instead "
                "(dispatcher.attach_telemetry)")
        if completion_window is not None:
            if dispatcher is not None:
                raise ValueError(
                    "completion_window configures the engine-owned "
                    "dispatcher; set it on the shared Dispatcher instead")
            if completion_window < 1:
                raise ValueError("completion_window must be >= 1")
        if policy is not None and dispatcher is not None:
            raise ValueError(
                "policy configures the engine-owned dispatcher; set it on "
                "the shared Dispatcher instead")
        self.model = model
        self.cfg = model.cfg
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.prefill_bucket = prefill_bucket
        self.eos_id = eos_id
        self.slots = SlotManager(max_batch)
        self.tracker = tracker or WcetTracker("engine")
        self.cluster = cluster_id
        self.chunked_prefill = bool(chunked_prefill)
        self.prefill_chunk_tokens = int(prefill_chunk_tokens
                                        if prefill_chunk_tokens is not None
                                        else prefill_bucket)
        if self.prefill_chunk_tokens < 1:
            raise ValueError("prefill_chunk_tokens must be >= 1")

        # own a private copy: engine state is donated through every step /
        # insert, which would otherwise invalidate the caller's param
        # buffers. It is the served form, laid out once here for the step
        # programs (no step relays out a weight); converted before any
        # cache is allocated, as the conversion briefly holds both forms
        params = model.serve_params(params)
        caches = model.init_caches(max_batch, max_seq)
        # PER-SLOT prefill→decode handoff area: one staging row per engine
        # slot (batch-1 caches at the slot's batch index, first token,
        # prompt length — plus the staged prompt itself when prefill runs
        # device-side). Any number of prefills can be outstanding at once;
        # OP_INSERT copies row ``slot`` into the main caches on device.
        staging = {
            "caches": model.init_caches(max_batch, max_seq),
            "token": jnp.zeros((max_batch,), jnp.int32),
            "length": jnp.zeros((max_batch,), jnp.int32),
        }
        if self.chunked_prefill:
            # device-side prefill reads its prompt row from state; the host
            # stages it once per request (max_seq int32s — tiny next to
            # the caches it saves re-staging)
            staging["prompt"] = jnp.zeros((max_batch, max_seq), jnp.int32)
        state = {
            "params": params,
            "caches": caches,
            "tokens": jnp.zeros((max_batch, 1), jnp.int32),
            "lengths": jnp.zeros((max_batch,), jnp.int32),
            "active": jnp.zeros((max_batch,), jnp.bool_),
            "staging": staging,
        }

        def decode_fn(state, desc):
            logits, new_caches = model.decode_step(
                state["params"], state["caches"], state["tokens"],
                state["lengths"])
            nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
            act = state["active"]
            tokens = jnp.where(act[:, None], nxt[:, None], state["tokens"])
            lengths = state["lengths"] + act.astype(jnp.int32)
            new_state = dict(state, caches=new_caches, tokens=tokens,
                             lengths=lengths)
            return new_state, nxt

        def insert_fn(state, desc):
            slot = desc[mb.W_ARG0]
            stg = state["staging"]
            small = extract_slot_caches(stg["caches"], slot)
            caches = insert_slot_caches(state["caches"], small, slot)
            tok = jax.lax.dynamic_slice(stg["token"], (slot,), (1,))
            tokens = jax.lax.dynamic_update_slice(
                state["tokens"], tok.reshape(1, 1), (slot, 0))
            length = jax.lax.dynamic_slice(stg["length"], (slot,), (1,))
            lengths = jax.lax.dynamic_update_slice(
                state["lengths"], length, (slot,))
            active = jax.lax.dynamic_update_slice(
                state["active"], jnp.ones((1,), jnp.bool_), (slot,))
            new_state = dict(state, caches=caches, tokens=tokens,
                             lengths=lengths, active=active)
            # the result is the post-insert token column: row ``slot`` is
            # the request's FIRST generated token, so the insert ticket's
            # completion carries it (TTFT measurement, host records)
            return new_state, tokens[:, 0]

        def release_fn(state, desc):
            # deactivate a slot device-side (shed / end-of-stream): decode
            # steps stop writing its row; the slot's caches are left as-is
            # and fully overwritten by the next insert that lands there
            slot = desc[mb.W_ARG0]
            active = jax.lax.dynamic_update_slice(
                state["active"], jnp.zeros((1,), jnp.bool_), (slot,))
            return dict(state, active=active), jnp.zeros(
                (max_batch,), jnp.int32)

        chunk_tokens = self.prefill_chunk_tokens

        def prefill_fn(state, carry, desc):
            # chunk-aware (resumable) prefill against the slot's OWN
            # staging row: chunk k folds tokens [k·chunk_tokens, ...) of
            # the staged prompt row through decode_step on the row's
            # batch-1 caches — mathematically the prompt pass, sliced so
            # more urgent work can preempt between chunks instead of
            # waiting out the whole prompt. Chunk 0 zeroes the row; the
            # running last-sampled token lives in staging["token"][slot],
            # so the remainder is re-triggerable from the descriptor's
            # chunk word alone and other slots' prefills may interleave
            # arbitrarily without clobbering each other.
            stg = state["staging"]
            slot = desc[mb.W_ARG0]
            chunk = desc[mb.W_CHUNK]
            length = desc[mb.W_SEQLEN]
            start = chunk * chunk_tokens
            row = extract_slot_caches(stg["caches"], slot)
            caches0 = jax.tree.map(
                lambda c: jnp.where(chunk == 0, jnp.zeros_like(c), c), row)
            prompt = jax.lax.dynamic_slice_in_dim(
                stg["prompt"], slot, 1, axis=0)[0]
            last0 = jax.lax.dynamic_slice(stg["token"], (slot,), (1,))[0]
            n = jnp.clip(length - start, 0, chunk_tokens)

            def body(i, acc):
                caches, _ = acc
                pos = start + i
                tok = jax.lax.dynamic_slice(prompt, (pos,), (1,))
                logits, caches = model.decode_step(
                    state["params"], caches, tok[:, None],
                    jnp.reshape(pos, (1,)))
                return caches, jnp.argmax(logits[0, 0]).astype(jnp.int32)

            # two tokens per iteration: the layer scan returns the SSM
            # state in a fresh buffer while it still reads the old one, so
            # a one-token loop copies the whole stack back into its carry
            # every token; the second token's state takes the first one's
            # dead input buffer, and the loop carries it with no copy
            def pair(j, acc):
                return body(2 * j + 1, body(2 * j, acc))

            acc = jax.lax.fori_loop(0, n // 2, pair, (caches0, last0))
            caches, last = jax.lax.cond(
                n % 2 == 1, lambda a: body(n - 1, a), lambda a: a, acc)
            done = chunk + 1 >= desc[mb.W_NCHUNKS]
            new_caches = insert_slot_caches(stg["caches"], caches, slot)
            token = jax.lax.dynamic_update_slice(
                stg["token"], last.reshape(1), (slot,))
            lens = jax.lax.dynamic_update_slice(
                stg["length"], length.astype(jnp.int32).reshape(1), (slot,))
            new_stg = dict(stg, caches=new_caches, token=token, length=lens)
            return (dict(state, staging=new_stg), carry,
                    jnp.zeros((max_batch,), jnp.int32), done)

        work_fns = [("decode", decode_fn), ("insert", insert_fn)]
        if self.chunked_prefill:
            work_fns.append(("prefill", prefill_fn,
                             jnp.zeros((), jnp.int32)))
        work_fns.append(("release", release_fn))
        self.op_release = len(work_fns) - 1
        self.rt = PersistentRuntime(
            work_fns,
            result_template=jnp.zeros((max_batch,), jnp.int32),
            tracker=self.tracker, max_inflight=max_inflight,
            max_steps=max_steps, donate=donate,
            telemetry=telemetry)
        if telemetry is not None:
            self.rt.telemetry_cluster = cluster_id
        self.rt.boot(state)

        # decode is HIGH-criticality and (under the server policy) runs in
        # its own bandwidth server; insert/release are best-effort LOW;
        # chunked prefill is LOW and DECLARES its chunk length, which is
        # what collapses its blocking term so decode admission sees one
        # chunk, not one whole prompt
        class_specs = (
            ClassSpec(opcode=OP_DECODE, name="decode", priority=0,
                      criticality=CRIT_HIGH, budget_us=decode_budget_us,
                      period_us=decode_period_us),
            ClassSpec(opcode=OP_INSERT, name="insert", priority=10,
                      criticality=CRIT_LOW),
        )
        if self.chunked_prefill:
            class_specs += (
                ClassSpec(opcode=OP_PREFILL, name="prefill", priority=5,
                          criticality=CRIT_LOW,
                          chunk_us=prefill_chunk_us),)
        class_specs += (
            ClassSpec(opcode=self.op_release, name="release", priority=10,
                      criticality=CRIT_LOW),)
        if dispatcher is None:
            if policy == "server":
                # decode dominates this cluster: budget isolation should
                # throttle it only when insert work competes, never idle
                # the device (work-conserving bandwidth servers)
                policy = BudgetedServerPolicy(work_conserving=True)
            dispatcher = Dispatcher(
                {cluster_id: self.rt},
                completion_window=completion_window
                if completion_window is not None else 1024,
                policy=policy, classes=class_specs,
                telemetry=telemetry, tracker=self.tracker)
        else:
            # raises if cluster_id is taken — silently adopting another
            # engine's runtime would decode against the wrong state
            dispatcher.register(cluster_id, self.rt)
            # the spec table is keyed by opcode ACROSS the dispatcher: on
            # a shared dispatcher the owner's declarations win — only
            # fill in opcodes nobody has declared yet
            for spec in class_specs:
                if dispatcher.policy.spec(spec.opcode) is None:
                    dispatcher.set_class(spec)
        self.dispatcher = dispatcher

        self._stage_jit = jax.jit(self._stage_impl, donate_argnums=(0,))
        self._set_prompt_jit = jax.jit(self._set_prompt_impl,
                                       donate_argnums=(0,))
        self._prefill_jits: dict[int, Any] = {}
        self._step_counter = 0
        # outstanding prefill tickets per slot (stream frontends cancel
        # these when shedding a still-queued prefill)
        self.prefill_tickets: dict[int, Ticket] = {}

    # ------------------------------------------------------------------
    @staticmethod
    def _stage_impl(state, slot_caches, first_token, length, slot):
        stg = state["staging"]
        caches = jax.tree.map(
            lambda big, c: jax.lax.dynamic_update_slice_in_dim(
                big, c.astype(big.dtype), slot, axis=1),
            stg["caches"], slot_caches)
        token = jax.lax.dynamic_update_slice(
            stg["token"], first_token.astype(jnp.int32).reshape(1), (slot,))
        lens = jax.lax.dynamic_update_slice(
            stg["length"], length.astype(jnp.int32).reshape(1), (slot,))
        return dict(state, staging=dict(stg, caches=caches, token=token,
                                        length=lens))

    @staticmethod
    def _set_prompt_impl(state, prompt, slot):
        stg = state["staging"]
        prompts = jax.lax.dynamic_update_slice(
            stg["prompt"], prompt.astype(jnp.int32)[None], (slot, 0))
        return dict(state, staging=dict(stg, prompt=prompts))

    def _prefill(self, batch: dict, length: int):
        # exact-length prefill: one compile per distinct prompt length.
        # (Bucketed prefill with masked pads is a documented production
        # optimization — pads corrupt SSM recurrences unless dt is masked,
        # see DESIGN §9 — so correctness-first here.)
        if length not in self._prefill_jits:
            self._prefill_jits[length] = jax.jit(
                functools.partial(self.model.prefill, max_seq=self.max_seq))
        return self._prefill_jits[length](self.rt.state["params"], batch)

    def _pump_cluster(self) -> list:
        """Run this engine's cluster queue to empty; returns completions."""
        out = []
        d = self.dispatcher
        while d.queue_depth(self.cluster) or d.inflight_depth(self.cluster):
            comp = d.pump(self.cluster)
            if comp is not None:
                out.append(comp)
        return out

    # ------------------------------------------------------------------
    def _submit_insert(self, request_id: int, slot: int,
                       slot_obj) -> Ticket:
        """Submit the staging→main-cache OP_INSERT for ``slot`` and chain
        the host-side bookkeeping onto its resolution: the slot flips to
        phase ``decoding`` and records its first generated token (the
        insert result's row ``slot``). Holding the Slot OBJECT (not the
        index) keeps the callback safe across slot reuse."""
        ticket = self.dispatcher.submit(
            mb.WorkDescriptor(opcode=OP_INSERT, arg0=slot,
                              request_id=request_id),
            cluster=self.cluster, admission=False)
        tc = self.dispatcher.telemetry

        def _on_insert(comp, slot=slot, slot_obj=slot_obj):
            slot_obj.generated.append(int(np.asarray(comp.result)[slot]))
            slot_obj.phase = PH_DECODING
            if tc is not None:
                tc.emit(EV_ENGINE, cluster=self.cluster,
                        request_id=comp.request_id, phase="insert",
                        slot=slot)

        ticket.on_complete(_on_insert)
        return ticket

    def add_request(self, request_id: int, prompt: np.ndarray,
                    max_new_tokens: int = 32,
                    extras: Optional[dict] = None) -> Optional[int]:
        """Prefill a prompt into a free slot. Returns the slot or None.

        NON-BLOCKING: the call returns at submission time. With
        ``chunked_prefill`` the prompt runs DEVICE-side as a chunked
        OP_PREFILL item (``ceil(L / prefill_chunk_tokens)`` resumable
        chunks through the normal dispatcher lane — deadline-carrying
        work preempts it at every chunk boundary) and the OP_INSERT is
        chained onto the prefill ticket's resolution, so this engine's
        own decode steps overlap its own prefills. Prompts that need
        ``extras`` (VLM/enc-dec) fall back to the host prefill path,
        which pays its compute here but still hands off asynchronously.
        The slot is harvestable (phase ``decoding``, first token
        recorded) once the insert resolves — drive the dispatcher via
        ``step()`` / a ticket ``result()``.
        """
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        L = int(prompt.shape[0])
        # the prefill emits the first generated token, so the decode loop
        # contributes max_new_tokens - 1 more
        slot = self.slots.allocate(
            request_id, L, min(L + max_new_tokens - 1, self.max_seq - 1))
        if slot is None:
            return None
        slot_obj = self.slots.slots[slot]
        chunked = self.chunked_prefill and not extras
        tc = self.dispatcher.telemetry   # engine-owned or shared collector
        if tc is not None:
            tc.emit(EV_ENGINE, cluster=self.cluster, request_id=request_id,
                    phase="add_request", slot=slot, prompt_tokens=L,
                    path="chunked" if chunked else "host")
        if chunked:
            buf = np.zeros((self.max_seq,), np.int32)
            buf[:L] = prompt
            self.rt.update_state(self._set_prompt_jit(
                self.rt.state, jnp.asarray(buf),
                jnp.asarray(slot, jnp.int32)))
            n_chunks = -(-L // self.prefill_chunk_tokens)
            ticket = self.dispatcher.submit(
                mb.WorkDescriptor(opcode=OP_PREFILL, arg0=slot, seq_len=L,
                                  request_id=request_id,
                                  n_chunks=n_chunks),
                cluster=self.cluster, admission=False)
            self.prefill_tickets[slot] = ticket

            def _chain(_comp, rid=request_id, slot=slot, slot_obj=slot_obj):
                self.prefill_tickets.pop(slot, None)
                self._submit_insert(rid, slot, slot_obj)

            ticket.on_complete(_chain)
        else:
            batch = {"tokens": jnp.asarray(prompt[None])}
            if extras:
                batch.update({k: jnp.asarray(v)[None]
                              for k, v in extras.items()})
            logits, caches = self._prefill(batch, L)
            first = jnp.argmax(logits[0, -1, :]).astype(jnp.int32)
            self.rt.update_state(self._stage_jit(
                self.rt.state, caches, first, jnp.asarray(L, jnp.int32),
                jnp.asarray(slot, jnp.int32)))
            if tc is not None:
                # the host-fallback admission path, visible in traces:
                # which slot the host prefill bound and that it bypassed
                # the chunked device lane
                tc.emit(EV_ENGINE, cluster=self.cluster,
                        request_id=request_id, phase="host_prefill",
                        slot=slot, path="host", prompt_tokens=L)
            self._submit_insert(request_id, slot, slot_obj)
        return slot

    def release_slot(self, slot: int, request_id: int = -1) -> Ticket:
        """Deactivate ``slot`` device-side (OP_RELEASE): decode steps stop
        writing its row. The HOST record is intentionally untouched — free
        or evict it when the returned ticket resolves, so the slot cannot
        be reallocated while a decode step that predates the release is
        still in flight."""
        return self.dispatcher.submit(
            mb.WorkDescriptor(opcode=self.op_release, arg0=slot,
                              request_id=request_id),
            cluster=self.cluster, admission=False)

    # ------------------------------------------------------------------
    def step(self, deadline_us: int = 0,
             auto_free: bool = True) -> dict[int, int]:
        """One persistent decode step through the dispatcher; returns
        {slot: new_token} for DECODING slots (a slot whose insert has not
        resolved yet produced no real token and is skipped). The step's
        ticket delivers exactly this request's result — no completion-list
        scanning.

        ``deadline_us`` gives the step a real EDF deadline so it preempts
        deadline-free chunked prefills at their next chunk boundary (the
        stream frontend's decode/prefill interleave). ``auto_free=False``
        parks exhausted slots in phase ``finished`` instead of freeing
        them — callers that must release the slot device-side first (the
        frontend) own the free."""
        desc = mb.WorkDescriptor(work_id=self._step_counter % 1024,
                                 opcode=OP_DECODE,
                                 request_id=self._step_counter,
                                 deadline_us=deadline_us)
        self._step_counter += 1
        ticket = self.dispatcher.submit(desc, cluster=self.cluster,
                                        admission=False)
        result = ticket.result()
        with self.tracker.phase("streams.harvest") as span:
            toks = np.asarray(result)
            out = {}
            for i in self.slots.decoding_indices():
                s = self.slots.slots[i]
                t = int(toks[i])
                s.generated.append(t)
                s.length += 1
                out[i] = t
                if t == self.eos_id or s.length >= s.max_len:
                    if auto_free:
                        self.slots.free(i)
                    else:
                        s.phase = PH_FINISHED
            if span is not None:
                span["tokens"] = len(out)
        return out

    # ------------------------------------------------------------------
    def generate(self, prompts: list[np.ndarray], max_new_tokens: int = 16,
                 extras: Optional[list] = None) -> list[list[int]]:
        """Simple driver: admit all (queueing when full), decode until done
        (continuous batching: freed slots are refilled between steps)."""
        queue = deque(enumerate(prompts))
        record: dict[int, Any] = {}

        def admit():
            while queue:
                rid, p = queue[0]
                ex = extras[rid] if extras else None
                slot = self.add_request(rid, p, max_new_tokens, ex)
                if slot is None:
                    return
                # keep a live reference to the Slot object: it survives
                # slot reuse (SlotManager replaces, not mutates, on free)
                record[rid] = self.slots.slots[slot]
                queue.popleft()

        admit()
        while self.slots.any_active or queue:
            self.step()
            admit()
        return [record[r].generated for r in range(len(prompts))]

    def dispose(self):
        self._pump_cluster()        # retire any leftovers before detaching
        if self.cluster in self.dispatcher.runtimes:
            self.dispatcher.unregister(self.cluster)
        self.rt.dispose()
