"""LkSystem facade: declarative boot/dispose, ticket submission, and the
wired self-healing loop (on_failure → mark_failed → recarve → reboot →
register) with zero lost requests."""
from collections import deque

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mailbox as mb
from repro.core.dispatcher import AdmissionError, now_us
from repro.system import LkSystem, WorkClass


class FakeDev:
    def __init__(self, i):
        self.id = i

    def __repr__(self):
        return f"dev{self.id}"


def devs(n):
    return [FakeDev(i) for i in range(n)]


class FakeRuntime:
    """RuntimeProtocol double whose wait can be rigged to die — at once
    (fail_wait) or after N successful retirements (fail_after)."""

    def __init__(self, cid, log, max_inflight=2, fail_wait=False,
                 fail_after=None):
        self.cid = cid
        self.log = log
        self.max_inflight = max_inflight
        self.fail_wait = fail_wait
        self.fail_after = fail_after
        self.waits = 0
        self._q = deque()

    def _dead(self):
        return self.fail_wait or (self.fail_after is not None
                                  and self.waits >= self.fail_after)

    def trigger(self, desc):
        if len(self._q) >= self.max_inflight:
            raise RuntimeError("full")
        self.log.append(("trigger", self.cid, desc.request_id))
        self._q.append(desc)

    def ready(self):
        return bool(self._q) and not self._dead()

    def wait(self):
        desc = self._q.popleft()
        if self._dead():
            raise RuntimeError(f"cluster {self.cid} wait died")
        self.waits += 1
        self.log.append(("wait", self.cid, desc.request_id))
        fg = np.zeros((mb.DESC_WIDTH,), np.int32)
        fg[mb.W_STATUS] = mb.THREAD_FINISHED
        fg[mb.W_REQID] = desc.request_id
        return np.float32([desc.request_id]), fg

    def dispose(self):
        self._q.clear()


def add_one(state, desc):
    state = dict(state)
    state["x"] = state["x"] + 1.0
    return state, state["x"].sum()[None]


def make_system(**kw):
    kw.setdefault("state_factory",
                  lambda cl: {"x": jnp.zeros((4,), jnp.float32)})
    kw.setdefault("result_template", jnp.zeros((1,), jnp.float32))
    return LkSystem(**kw)


# ---------------------------------------------------------------------------
# declarative lifecycle
# ---------------------------------------------------------------------------

def test_boot_submit_dispose_context():
    sys_ = make_system(devices=devs(4), n_clusters=2,
                       work_classes=[WorkClass("w", fn=add_one)])
    assert not sys_.booted
    with sys_:
        assert sys_.booted and len(sys_.cluster_ids()) == 2
        t1, t2 = sys_.submit("w"), sys_.submit("w")
        assert float(t1.result()[0]) > 0
        assert t2.done() or float(t2.result()[0]) > 0
        assert {t1.completion.cluster, t2.completion.cluster} == {0, 1}
    assert not sys_.booted                  # context exit disposed
    assert sys_.runtimes == {}


def test_registration_closes_at_boot():
    sys_ = make_system(devices=devs(2))
    with pytest.raises(RuntimeError, match="WorkClass"):
        sys_.boot()                         # nothing registered
    with pytest.raises(RuntimeError, match="boot"):
        sys_.drain()                        # friendly pre-boot error
    with pytest.raises(RuntimeError, match="boot"):
        sys_.poll()
    sys_.register(WorkClass("a", fn=add_one))
    with pytest.raises(KeyError):
        sys_.register(WorkClass("a", fn=add_one))     # duplicate
    with sys_:
        with pytest.raises(RuntimeError, match="before boot"):
            sys_.register(WorkClass("b", fn=add_one))
        with pytest.raises(KeyError):
            sys_.submit("nope")


def test_out_of_range_pin_rejected_at_boot():
    """A pin that matches no cluster is a config error — silently
    remapping it would break the spatial isolation it promises."""
    sys_ = make_system(devices=devs(4), n_clusters=2,
                       work_classes=[WorkClass("w", fn=add_one, pin=5)])
    with pytest.raises(ValueError, match="pins to cluster 5"):
        sys_.boot()


def test_wcet_seed_drives_admission():
    sys_ = make_system(devices=devs(2), work_classes=[
        WorkClass("slow", fn=add_one, wcet_us=50_000.0)])
    with sys_:
        with pytest.raises(AdmissionError):
            sys_.submit("slow", deadline_us=now_us() + 10)
        t = sys_.submit("slow", deadline_us=now_us() + 10**9)
        t.result()
        assert sys_.stats()["rejected"] == 1


def test_pinned_work_class_routes_to_cluster():
    log = []
    sys_ = make_system(
        devices=devs(4), n_clusters=2,
        runtime_factory=lambda cl: FakeRuntime(cl.cid, log),
        work_classes=[WorkClass("interactive", fn=add_one, pin=0),
                      WorkClass("batch", fn=add_one, pin=1)])
    with sys_:
        ts = [sys_.submit("interactive") for _ in range(3)]
        tb = [sys_.submit("batch") for _ in range(3)]
        sys_.drain()
        assert {t.completion.cluster for t in ts} == {0}
        assert {t.completion.cluster for t in tb} == {1}


# ---------------------------------------------------------------------------
# the self-healing loop
# ---------------------------------------------------------------------------

def test_self_healing_zero_lost_requests():
    """A cluster dying mid-flight (in-flight AND queued work) triggers
    mark_failed → recarve → reboot → register BEFORE the replay, so every
    ticket resolves — on the survivor or on rebuilt capacity."""
    log = []
    arm_fault = [True]

    def factory(cl):
        fail = arm_fault[0] and cl.cid == 0
        return FakeRuntime(cl.cid, log, max_inflight=2, fail_wait=fail)

    # 9 devices / 2 clusters of 4 + 1 spare: after cluster 0 dies, the
    # spare joins the 4 survivors and the recarve rebuilds 2 clusters
    sys_ = make_system(devices=devs(9), n_clusters=2,
                       runtime_factory=factory,
                       work_classes=[WorkClass("w", fn=add_one, pin=0)])
    with sys_:
        arm_fault[0] = False            # replacements must be healthy
        gen0 = sys_.cm.generation
        tickets = [sys_.submit("w") for _ in range(6)]
        done = sys_.drain()
        assert len(done) == 6
        assert all(t.done() for t in tickets)          # zero lost
        assert sorted(t.completion.request_id for t in tickets) == \
            [t.request_id for t in tickets]
        assert sys_.heals == 1
        assert sys_.cm.generation == gen0 + 1
        # rebuilt capacity was registered under fresh dispatcher ids and
        # none of the work ran on the dead cluster
        assert 0 not in sys_.dispatcher.runtimes
        assert {t.completion.cluster for t in tickets} <= \
            set(sys_.dispatcher.runtimes) | {1}
        assert len(sys_.cluster_ids()) == 2
        # the pin was rewritten onto live capacity: new work still flows
        t2 = sys_.submit("w")
        assert t2.result() is not None
        s = sys_.stats()
        assert s["n"] == 7 and s["heals"] == 1


def test_failed_clusters_and_replays_are_counted():
    """deadline_stats() counts every cluster _fail_cluster retired and
    every item it replayed; a healthy run keeps both at zero."""
    log = []
    healthy = make_system(devices=devs(2), work_classes=[
        WorkClass("w", fn=add_one)],
        runtime_factory=lambda cl: FakeRuntime(cl.cid, log))
    with healthy:
        for _ in range(3):
            healthy.submit("w")
        healthy.drain()
        s = healthy.stats()
        assert (s["failed_clusters"], s["replayed"]) == (0, 0)

    arm_fault = [True]

    def factory(cl):
        fail = arm_fault[0] and cl.cid == 0
        return FakeRuntime(cl.cid, log, max_inflight=2, fail_wait=fail)

    sys_ = make_system(devices=devs(9), n_clusters=2,
                       runtime_factory=factory,
                       work_classes=[WorkClass("w", fn=add_one, pin=0)])
    with sys_:
        arm_fault[0] = False
        tickets = [sys_.submit("w") for _ in range(6)]
        sys_.drain()
        assert all(t.done() for t in tickets)
        s = sys_.stats()
        assert s["failed_clusters"] == 1 == sys_.heals
        # the dead cluster held work in flight and queued: all of it
        # was requeued onto live capacity
        assert 1 <= s["replayed"] <= 6
        assert sys_.dispatcher.counters()["dispatcher.failed_clusters"] == 1


def test_displaced_survivor_lame_duck_reaped():
    """When the recarve rearranges the survivor's partition, the old
    runtime finishes its backlog as a lame duck and reap() retires it."""
    log = []
    arm_fault = [True]

    def factory(cl):
        fail = arm_fault[0] and cl.cid == 0
        return FakeRuntime(cl.cid, log, max_inflight=1, fail_wait=fail)

    # 5 devices / 2 clusters of 2 + 1 spare: the 3 surviving devices
    # recarve into 2 clusters of 1 — the survivor's partition changes, so
    # it must lame-duck instead of being killed with work on board
    sys_ = make_system(devices=devs(5), n_clusters=2,
                       runtime_factory=factory,
                       work_classes=[WorkClass("w", fn=add_one, pin=0)])
    with sys_:
        arm_fault[0] = False
        tickets = [sys_.submit("w") for _ in range(4)]
        sys_.drain()
        assert all(t.done() for t in tickets)
        assert sys_.heals == 1
        assert sys_.lame_ducks == set()                # reaped after drain
        assert 1 not in sys_.dispatcher.runtimes       # old survivor gone
        assert len(sys_.cluster_ids()) == 2


def test_lame_duck_death_does_not_corrupt_cluster_state():
    """A dying lame duck holds a PREVIOUS-generation Cluster record: its
    death must drop the runtime and replay its backlog, not mark a
    current healthy cluster failed or trigger a second recarve."""
    log = []
    arm = [True]

    def factory(cl):
        if arm[0] and cl.cid == 0:
            return FakeRuntime(cl.cid, log, max_inflight=1, fail_wait=True)
        if arm[0] and cl.cid == 1:
            # the future lame duck: survives one retirement, then dies
            return FakeRuntime(cl.cid, log, max_inflight=1, fail_after=1)
        return FakeRuntime(cl.cid, log, max_inflight=1)

    sys_ = make_system(devices=devs(5), n_clusters=2,
                       runtime_factory=factory,
                       work_classes=[WorkClass("a", fn=add_one, pin=0),
                                     WorkClass("b", fn=add_one, pin=1)])
    with sys_:
        arm[0] = False
        tb = [sys_.submit("b") for _ in range(3)]   # survivor backlog
        ta = [sys_.submit("a") for _ in range(2)]   # dying cluster's work
        sys_.drain()
        assert all(t.done() for t in ta + tb)       # zero lost, twice over
        assert sys_.heals == 1                      # duck death is no heal
        assert sys_.cm.generation == 2              # exactly one recarve
        assert len(sys_.cm.clusters) == 2
        assert all(c.healthy for c in sys_.cm.clusters)
        assert sys_.lame_ducks == set()


def test_real_runtime_heal_end_to_end():
    """Kill a real PersistentRuntime mid-service: the system reboots fresh
    capacity from state_factory and the replayed descriptors complete."""
    sys_ = make_system(devices=devs(9), n_clusters=2,
                       work_classes=[WorkClass("w", fn=add_one, pin=0)])
    with sys_:
        tickets = [sys_.submit("w") for _ in range(4)]
        sys_.runtimes[0].dispose()      # the fault: cluster 0's runtime dies
        done = sys_.drain()
        assert len(done) == 4
        assert all(t.done() for t in tickets)
        assert all(t.completion.cluster != 0 for t in tickets)
        assert sys_.heals == 1
        # service continues on the healed system
        assert sys_.submit("w").result() is not None


def test_heal_disabled_still_replays_on_survivors():
    log = []

    def factory(cl):
        return FakeRuntime(cl.cid, log, fail_wait=(cl.cid == 0))

    sys_ = make_system(devices=devs(4), n_clusters=2,
                       runtime_factory=factory, heal=False,
                       work_classes=[WorkClass("w", fn=add_one, pin=0)])
    with sys_:
        tickets = [sys_.submit("w") for _ in range(3)]
        sys_.drain()
        assert all(t.done() for t in tickets)          # dispatcher replay
        assert {t.completion.cluster for t in tickets} == {1}
        assert sys_.heals == 0
        assert sys_.cm.generation == 1                 # no recarve
