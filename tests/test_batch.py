"""Batched doorbells: trigger_many ordering equivalence vs sequential
triggers, the multi-step loop's token identity for chunked carries,
mid-batch preempted chunks, dispatcher coalescing, failure replay of an
un-acked batch suffix, the staged double buffer, and batch-stamped
telemetry and trace spans."""
import glob
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import mailbox as mb
from repro.core.dispatcher import Dispatcher
from repro.core.persistent import PersistentRuntime
from repro.core.telemetry import EV_TRIGGER, TraceCollector


def add_fn(state, desc):
    state = dict(state)
    state["x"] = state["x"] + desc[mb.W_ARG0].astype(jnp.float32)
    return state, state["x"].sum()[None]


def chunk_fn(state, carry, desc):
    # resumable: the carry accumulates across chunks; done on final chunk
    carry = carry + desc[mb.W_ARG0]
    done = desc[mb.W_CHUNK] + 1 >= desc[mb.W_NCHUNKS]
    return state, carry, carry.astype(jnp.float32)[None], done


def make_rt(max_inflight=8, max_steps=4, telemetry=None, chunked=False,
            staged_cap=4):
    fns = [("add", add_fn)]
    if chunked:
        fns.append(("chunk", chunk_fn, jnp.zeros((), jnp.int32)))
    rt = PersistentRuntime(fns, result_template=jnp.zeros((1,), jnp.float32),
                           max_inflight=max_inflight, max_steps=max_steps,
                           telemetry=telemetry, staged_cap=staged_cap)
    rt.boot({"x": jnp.zeros((4,), jnp.float32)})
    return rt


# ---------------------------------------------------------------------------
# runtime-level batching semantics
# ---------------------------------------------------------------------------

def _drain_pairs(rt):
    return [(float(res[0]), int(fg[mb.W_REQID]), int(fg[mb.W_STATUS]))
            for res, fg in rt.wait_all()]


def test_trigger_many_matches_sequential():
    """One doorbell of N descriptors retires the exact (result, ack)
    sequence N sequential trigger() calls produce — same state chain,
    same request ids, same statuses."""
    descs = [mb.WorkDescriptor(opcode=0, arg0=i + 1, request_id=50 + i)
             for i in range(6)]
    rt_b = make_rt()
    assert rt_b.trigger_many(descs) == 6
    assert rt_b.inflight == 6
    batched = _drain_pairs(rt_b)
    rt_b.dispose()

    rt_s = make_rt()
    seq = []
    for d in descs:
        rt_s.trigger(d)
        res, fg = rt_s.wait()
        seq.append((float(res[0]), int(fg[mb.W_REQID]),
                    int(fg[mb.W_STATUS])))
    rt_s.dispose()
    assert batched == seq


def test_trigger_many_splits_over_max_steps():
    """N > max_steps issues ceil(N/max_steps) doorbells, still in order."""
    rt = make_rt(max_inflight=16, max_steps=4)
    descs = [mb.WorkDescriptor(opcode=0, arg0=1, request_id=i)
             for i in range(10)]
    rt.trigger_many(descs)
    assert rt.doorbells == 3           # 4 + 4 + 2
    assert rt.batched_steps == 10
    out = _drain_pairs(rt)
    assert [r[1] for r in out] == list(range(10))
    rt.dispose()


def test_trigger_many_mid_batch_preempted_chunk():
    """A non-final chunk in the middle of a batch answers
    THREAD_PREEMPTED on its ack row; its neighbours answer FINISHED —
    the ack block carries per-row statuses."""
    rt = make_rt(chunked=True)
    descs = [
        mb.WorkDescriptor(opcode=0, arg0=1, request_id=0),
        mb.WorkDescriptor(opcode=1, arg0=5, request_id=1, n_chunks=3),
        mb.WorkDescriptor(opcode=0, arg0=1, request_id=2),
    ]
    rt.trigger_many(descs)
    out = _drain_pairs(rt)
    assert [r[1] for r in out] == [0, 1, 2]
    assert out[0][2] == mb.THREAD_FINISHED
    assert out[1][2] == mb.THREAD_PREEMPTED    # chunk 0 of 3: not done
    assert out[2][2] == mb.THREAD_FINISHED
    rt.dispose()


def test_multi_step_token_identical_for_chunked_carries():
    """The scan loop threads per-opcode carries exactly as host-stepped
    _lk_step does: a chunked item split across one doorbell produces the
    same carry trajectory as three separate triggers."""
    d0 = mb.WorkDescriptor(opcode=1, arg0=7, request_id=9, n_chunks=3)
    chain = [d0, d0.advance(), d0.advance().advance()]

    rt_b = make_rt(chunked=True)
    rt_b.trigger_many(chain)
    batched = _drain_pairs(rt_b)
    rt_b.dispose()

    rt_s = make_rt(chunked=True)
    seq = []
    for d in chain:
        rt_s.trigger(d)
        res, fg = rt_s.wait()
        seq.append((float(res[0]), int(fg[mb.W_REQID]),
                    int(fg[mb.W_STATUS])))
    rt_s.dispose()
    assert batched == seq
    # the carry accumulated: 7, 14, 21; final chunk reports FINISHED
    assert [r[0] for r in batched] == [7.0, 14.0, 21.0]
    assert [r[2] for r in batched] == [mb.THREAD_PREEMPTED,
                                       mb.THREAD_PREEMPTED,
                                       mb.THREAD_FINISHED]


def test_trigger_many_capacity_and_empty():
    rt = make_rt(max_inflight=2)
    assert rt.trigger_many([]) == 0
    with pytest.raises(RuntimeError, match="capacity"):
        rt.trigger_many([mb.WorkDescriptor(opcode=0, arg0=1, request_id=i)
                         for i in range(3)])
    rt.dispose()


def test_ready_memo_and_block_retirement():
    """ready() is memoized per oldest block; a batched block stays ready
    through its host-side retirements and resets when it pops."""
    rt = make_rt()
    rt.trigger_many([mb.WorkDescriptor(opcode=0, arg0=1, request_id=i)
                     for i in range(3)])
    rt.wait()                       # materializes the whole block
    assert rt.ready()               # remaining items retire host-side
    rt.wait()
    rt.wait()
    assert not rt.ready()           # block exhausted; memo reset
    assert rt.inflight == 0
    rt.dispose()


def test_staged_double_buffer_serves_re_trigger():
    """A chunked item's next-chunk descriptor is staged device-side while
    the current chunk runs; the re-trigger consumes it (staged_hits)."""
    rt = make_rt(chunked=True, max_inflight=2)
    d = mb.WorkDescriptor(opcode=1, arg0=3, request_id=4, n_chunks=3)
    rt.trigger(d)
    rt.wait()
    d = d.advance()
    rt.trigger(d)                   # served from the staged buffer
    rt.wait()
    d = d.advance()
    rt.trigger(d)
    rt.wait()
    assert rt.staged_hits == 2
    rt.dispose()


def _chunk_chain(rid, n_chunks=3, arg0=1):
    d = mb.WorkDescriptor(opcode=1, arg0=arg0, request_id=rid,
                          n_chunks=n_chunks)
    out = [d]
    for _ in range(n_chunks - 1):
        d = d.advance()
        out.append(d)
    return out


def test_staged_cap_zero_disables_staging():
    """staged_cap=0 turns the double buffer off: every mid-item
    re-trigger pays a fresh host transfer and counts as a miss."""
    rt = make_rt(chunked=True, staged_cap=0)
    for d in _chunk_chain(rid=3):
        rt.trigger(d)
        rt.wait()
    assert rt.staged_hits == 0
    assert rt.staged_misses == 2            # chunks 1 and 2
    rt.dispose()


def test_staged_cap_negative_rejected():
    with pytest.raises(ValueError, match="staged_cap"):
        PersistentRuntime([("add", add_fn)],
                          result_template=jnp.zeros((1,), jnp.float32),
                          staged_cap=-1)


def test_staged_eviction_under_cap_counts_misses():
    """Two interleaved 3-chunk items against staged_cap=1: each staging
    evicts the other item's entry, so mid-item re-triggers miss until
    the final round, where the survivor's entry hits. The items still
    retire correctly — eviction costs a transfer, never correctness."""
    rt = make_rt(chunked=True, staged_cap=1)
    a, b = _chunk_chain(rid=1), _chunk_chain(rid=2)
    statuses = []
    for step in range(3):
        rt.trigger(a[step])
        rt.trigger(b[step])
        statuses.append(rt.wait()[1][mb.W_STATUS])
        statuses.append(rt.wait()[1][mb.W_STATUS])
    assert rt.staged_hits == 1              # b's final chunk survived
    assert rt.staged_misses == 3            # a.c1, b.c1, a.c2
    assert list(statuses[-2:]) == [mb.THREAD_FINISHED, mb.THREAD_FINISHED]
    assert rt._staged == {} and rt._live_rids == set()
    rt.dispose()


def test_staged_eviction_prefers_non_live_entries():
    """Over-cap eviction takes a NON-live entry (an item whose remainder
    was replayed away from this cluster) before any live item's staged
    chunk."""
    rt = make_rt(chunked=True, staged_cap=2)
    a, b, c = (_chunk_chain(rid=r) for r in (1, 2, 3))
    rt.trigger(a[0])
    rt.trigger(b[0])                        # staged: (1,1), (2,1) — at cap
    rt._live_rids.discard(1)                # a's remainder replayed away
    rt.trigger(c[0])                        # stages (3,1): evicts (1,1)
    for _ in range(3):
        rt.wait()
    rt.trigger(b[1])                        # live entry survived -> hit
    rt.trigger(c[1])                        # live entry survived -> hit
    rt.trigger(a[1])                        # the stale one was evicted
    for _ in range(3):
        rt.wait()
    assert rt.staged_hits == 2
    assert rt.staged_misses == 1
    rt.dispose()


def test_finished_item_releases_staged_entries():
    """FINISHED retirement drops the item's live flag and any leftover
    staged chunks — they must not linger as eviction pressure."""
    rt = make_rt(chunked=True, staged_cap=4)
    d = _chunk_chain(rid=5)
    for step in d:
        rt.trigger(step)
        rt.wait()
    assert rt._staged == {}
    assert rt._live_rids == set()
    assert rt.staged_hits == 2
    assert rt.staged_misses == 0
    rt.dispose()


def test_dispatcher_surfaces_staged_counters():
    """deadline_stats() reports staged_hits AND staged_misses summed over
    the fleet — the dispatcher's chunk re-triggers are served from the
    double buffer."""
    rt = make_rt(chunked=True, max_inflight=2)
    disp = Dispatcher({0: rt})
    disp.submit(mb.WorkDescriptor(opcode=1, arg0=5, request_id=7,
                                  n_chunks=3), admission=False)
    disp.drain()
    stats = disp.deadline_stats()
    assert stats["staged_hits"] == rt.staged_hits
    assert stats["staged_misses"] == rt.staged_misses
    assert stats["staged_hits"] >= 1
    rt.dispose()


def test_batch_stamped_rt_trigger_event(tmp_path):
    """A three-descriptor doorbell is ONE ``lk.trigger`` span in the
    profiler's trace, stamped with the batch and each descriptor."""
    rt = make_rt()
    rt._ensure_multi()
    with jax.profiler.trace(str(tmp_path)):
        rt.trigger_many([mb.WorkDescriptor(opcode=0, arg0=1, request_id=i)
                         for i in range(3)])
        rt.wait_all()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [dict(e.stats) for plane in ProfileData.from_file(path[0]).planes
             for line in plane.lines for e in line.events
             if e.name == "lk.trigger"]
    assert len(spans) == 1          # ONE doorbell span for the batch
    assert spans[0]["batch"] == 3 and spans[0]["depth"] == 3
    assert spans[0]["rids"] == "0 1 2" and spans[0]["ops"] == "0 0 0"
    rt.dispose()


# ---------------------------------------------------------------------------
# dispatcher coalescing + failure replay
# ---------------------------------------------------------------------------

def test_dispatcher_coalesces_kick_into_one_doorbell():
    """N same-cluster submits drained in one pass ride ONE doorbell; the
    telemetry TRIGGER events carry the batch size."""
    tel = TraceCollector()
    rt = make_rt(max_inflight=8)
    disp = Dispatcher({0: rt}, telemetry=tel)
    tickets = [disp.submit(mb.WorkDescriptor(opcode=0, arg0=1,
                                             request_id=i),
                           admission=False)
               for i in range(5)]
    done = disp.drain()
    assert len(done) == 5
    assert all(t.done() for t in tickets)
    assert disp.doorbells == 1
    assert disp.coalesced_triggers == 5
    assert rt.doorbells >= 1
    trig = tel.events_of(EV_TRIGGER)
    assert [e.request_id for e in trig] == list(range(5))
    assert all(e.extra.get("batch") == 5 for e in trig)
    stats = disp.deadline_stats()
    assert stats["doorbells"] == 1
    assert stats["coalesced_triggers"] == 5
    rt.dispose()


class FakeBatchRuntime:
    """Protocol double WITH trigger_many: serves ``die_after`` items then
    dies in wait(), leaving an un-acked batch suffix for the dispatcher
    to replay."""

    def __init__(self, cid, log, max_inflight=8, die_after=None):
        self.cid = cid
        self.log = log
        self.max_inflight = max_inflight
        self.die_after = die_after
        self.served = 0
        self._q = deque()

    def trigger(self, desc):
        self.log.append(("trigger", self.cid, desc.request_id))
        self._q.append(desc)

    def trigger_many(self, descs):
        descs = list(descs)
        self.log.append(("doorbell", self.cid,
                         [d.request_id for d in descs]))
        self._q.extend(descs)
        return len(descs)

    def ready(self):
        return bool(self._q)

    def wait(self):
        desc = self._q.popleft()
        if self.die_after is not None and self.served >= self.die_after:
            raise RuntimeError(f"cluster {self.cid} died mid-block")
        self.served += 1
        fg = np.zeros((mb.DESC_WIDTH,), np.int32)
        fg[mb.W_STATUS] = mb.THREAD_FINISHED
        fg[mb.W_REQID] = desc.request_id
        return np.float32([desc.request_id]), fg


def test_failure_replays_unacked_batch_suffix():
    """A cluster dying mid-block loses nothing: the un-acked suffix of
    its batched doorbell replays — in order — on the survivor."""
    log = []
    disp = Dispatcher({0: FakeBatchRuntime(0, log, die_after=2),
                       1: FakeBatchRuntime(1, log)})
    tickets = [disp.submit(mb.WorkDescriptor(opcode=0, request_id=i),
                           cluster=0, admission=False)
               for i in range(5)]
    done = disp.drain()
    assert len(done) == 5
    assert sorted(c.request_id for c in done) == list(range(5))
    assert all(t.done() for t in tickets)
    # the first doorbell carried all five; the suffix (2, 3, 4) replayed
    # on cluster 1 in its original order
    first = next(e for e in log if e[0] == "doorbell" and e[1] == 0)
    assert first[2] == [0, 1, 2, 3, 4]
    replayed = [rid for kind, cid, rid_or_list in log
                if cid == 1 and kind in ("trigger", "doorbell")
                for rid in (rid_or_list if isinstance(rid_or_list, list)
                            else [rid_or_list])]
    assert replayed == [2, 3, 4]
    assert all(t.cluster == 1 for t in tickets[2:])
    assert 0 not in disp.runtimes


def test_non_batch_runtime_uses_per_item_fallback():
    """A RuntimeProtocol double without trigger_many still works: kick
    falls back to per-item triggers, no doorbell counters move."""
    class PlainRuntime(FakeBatchRuntime):
        trigger_many = None

    log = []
    disp = Dispatcher({0: PlainRuntime(0, log)})
    for i in range(3):
        disp.submit(mb.WorkDescriptor(opcode=0, request_id=i),
                    admission=False)
    done = disp.drain()
    assert len(done) == 3
    assert disp.doorbells == 0
    assert disp.coalesced_triggers == 0
    assert [e for e in log if e[0] == "doorbell"] == []


def test_mailbox_post_many_matches_sequential_posts():
    seq = mb.Mailbox(1)
    batch = mb.Mailbox(1)
    descs = [mb.WorkDescriptor(opcode=0, request_id=i).encode()
             for i in range(4)]
    for d in descs:
        seq.post(0, d)
    assert batch.post_many(0, descs) == 4
    assert [d.request_id for d in seq.pending(0)] == \
        [d.request_id for d in batch.pending(0)]
    assert np.array_equal(seq.to_gpu[0], batch.to_gpu[0])


def test_descriptor_ring_pads_with_nops():
    descs = [mb.WorkDescriptor(opcode=0, request_id=i) for i in range(2)]
    ring = mb.descriptor_ring(descs, 4)
    assert ring.shape == (4, mb.DESC_WIDTH)
    assert int(ring[0, mb.W_REQID]) == 0
    assert int(ring[1, mb.W_REQID]) == 1
    assert int(ring[2, mb.W_STATUS]) == mb.THREAD_NOP
    assert int(ring[3, mb.W_STATUS]) == mb.THREAD_NOP
    with pytest.raises(ValueError, match="capacity"):
        mb.descriptor_ring(descs, 1)
