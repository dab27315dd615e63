"""Persistent work-queue executor + drain megakernel vs pure-numpy
oracles."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import mailbox as mb
from repro.kernels.persistent import (NUM_DRAIN_OPS, OP_ADD, OP_COPY,
                                      OP_MATMUL, OP_NOP, OP_REDUCE, OP_RELU,
                                      OP_SCALE, TILE, build_queue, pack_args,
                                      pack_scale, persistent_drain,
                                      persistent_drain_ref,
                                      persistent_execute,
                                      persistent_execute_ref)


def run_both(progs, nbuf=6, qlen=8, seed=0):
    rng = np.random.default_rng(seed)
    C = len(progs)
    ws = rng.normal(size=(C, nbuf, TILE, TILE)).astype(np.float32)
    q = build_queue(progs, qlen)
    out, fg = persistent_execute(jnp.asarray(q), jnp.asarray(ws),
                                 interpret=True)
    out_ref, fg_ref = persistent_execute_ref(q, ws)
    return out, fg, out_ref, fg_ref


def test_mixed_program_matches_oracle():
    progs = [
        [(OP_MATMUL, *pack_args(3, 0, 1)), (OP_RELU, pack_args(3, 3)[0], 0),
         (OP_MATMUL, *pack_args(4, 3, 2)), (OP_SCALE, *pack_scale(4, 4, 0.5))],
        [(OP_ADD, *pack_args(5, 0, 1)), (OP_COPY, *pack_args(2, 5)),
         (OP_NOP, 0, 0)],
    ]
    out, fg, out_ref, fg_ref = run_both(progs)
    np.testing.assert_allclose(out, out_ref, rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(fg), np.asarray(fg_ref))


def test_work_count_in_from_gpu():
    progs = [[(OP_ADD, *pack_args(2, 0, 1))] * 3, []]
    _, fg, _, _ = run_both(progs)
    assert fg[0, mb.W_STATUS] == mb.THREAD_FINISHED
    assert fg[0, mb.W_ARG0] == 3
    assert fg[1, mb.W_ARG0] == 0                  # all-NOP queue


def test_chained_matmul_mlp():
    """The paper's 'finer-grained kernels' case: a tile-MLP as descriptors."""
    progs = [[(OP_MATMUL, *pack_args(3, 0, 1)),
              (OP_RELU, pack_args(3, 3)[0], 0),
              (OP_MATMUL, *pack_args(4, 3, 2))]]
    rng = np.random.default_rng(1)
    ws = np.zeros((1, 5, TILE, TILE), np.float32)
    ws[0, 0] = rng.normal(size=(TILE, TILE))
    ws[0, 1] = rng.normal(size=(TILE, TILE))
    ws[0, 2] = rng.normal(size=(TILE, TILE))
    q = build_queue(progs, 4)
    out, _ = persistent_execute(jnp.asarray(q), jnp.asarray(ws),
                                interpret=True)
    want = np.maximum(ws[0, 0] @ ws[0, 1], 0) @ ws[0, 2]
    np.testing.assert_allclose(np.asarray(out[0, 4]), want, rtol=1e-4,
                               atol=1e-3)


@pytest.mark.parametrize("n_clusters", [1, 2, 4])
def test_cluster_isolation(n_clusters):
    """Programs on one cluster never touch another cluster's workspace."""
    progs = [[(OP_SCALE, *pack_scale(0, 0, 2.0))]] + \
            [[] for _ in range(n_clusters - 1)]
    out, _, out_ref, _ = run_both(progs, nbuf=2, qlen=2)
    np.testing.assert_allclose(out, out_ref, rtol=1e-6)
    # untouched clusters identical to their input workspace
    rng = np.random.default_rng(0)
    ws = rng.normal(size=(n_clusters, 2, TILE, TILE)).astype(np.float32)
    for c in range(1, n_clusters):
        np.testing.assert_array_equal(np.asarray(out[c]), ws[c])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_random_programs_property(seed):
    rng = np.random.default_rng(seed)
    progs = []
    for c in range(2):
        prog = []
        for _ in range(rng.integers(1, 6)):
            op = int(rng.choice([OP_MATMUL, OP_ADD, OP_SCALE, OP_RELU,
                                 OP_COPY]))
            dst, a, b = rng.integers(0, 4, 3)
            if op == OP_SCALE:
                a0, a1 = pack_scale(int(dst), int(a),
                                    float(rng.uniform(-2, 2)))
            else:
                a0, a1 = pack_args(int(dst), int(a), int(b))
            prog.append((op, a0, a1))
        progs.append(prog)
    out, fg, out_ref, fg_ref = run_both(progs, nbuf=4, qlen=6, seed=seed)
    np.testing.assert_allclose(out, out_ref, rtol=1e-4, atol=1e-3)
    np.testing.assert_array_equal(np.asarray(fg), np.asarray(fg_ref))


# ---------------------------------------------------------------------------
# drain megakernel (device-resident queue) vs its numpy oracle
# ---------------------------------------------------------------------------

def drain_both(descs, qlen=8, head=0, tail=None, stop=0, nbuf=4, seed=0,
               carry0=0.0):
    """One cluster's drain launch through the pallas kernel (interpret)
    and the oracle; returns both 5-tuples plus the input ws."""
    rng = np.random.default_rng(seed)
    ws = (rng.standard_normal((1, nbuf, TILE, TILE)) * 0.1).astype(
        np.float32)
    ring = mb.descriptor_ring(descs, qlen)[None]
    if tail is None:
        tail = len(descs)
    ctrl = mb.queue_control(tail=tail, head=head, stop=stop)[None]
    carry = np.full((1, 1), carry0, np.float32)
    out = persistent_drain(jnp.asarray(ctrl), jnp.asarray(ring),
                           jnp.asarray(ws), jnp.asarray(carry),
                           interpret=True)
    ref = persistent_drain_ref(ctrl, ring, ws, carry)
    return out, ref, ws


def assert_drain_equal(out, ref):
    ws, carry, acks, results, ctrl = out
    ws_r, carry_r, acks_r, results_r, ctrl_r = ref
    np.testing.assert_allclose(np.asarray(ws), ws_r, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(carry), carry_r, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(acks), acks_r)
    np.testing.assert_allclose(np.asarray(results), results_r, rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_array_equal(np.asarray(ctrl), ctrl_r)


def test_drain_mixed_matches_oracle():
    """Every drain opcode in one queue, chunked reduce mid-queue: the
    kernel's acks are byte-identical to the oracle's, including the
    THREAD_PREEMPTED stamp on the non-final chunk."""
    descs = [
        mb.WorkDescriptor(opcode=OP_MATMUL, request_id=1,
                          arg0=pack_args(3, 0, 1)[0],
                          arg1=pack_args(3, 0, 1)[1]),
        mb.WorkDescriptor(opcode=OP_REDUCE, request_id=2,
                          arg0=pack_args(0, 2)[0], n_chunks=4),
        mb.WorkDescriptor(opcode=OP_ADD, request_id=3,
                          arg0=pack_args(2, 0, 1)[0],
                          arg1=pack_args(2, 0, 1)[1]),
        mb.WorkDescriptor(opcode=OP_SCALE, request_id=4,
                          arg0=pack_scale(1, 1, -1.5)[0],
                          arg1=pack_scale(1, 1, -1.5)[1]),
        mb.WorkDescriptor(opcode=OP_RELU, request_id=5,
                          arg0=pack_args(0, 3)[0]),
        mb.WorkDescriptor(opcode=OP_COPY, request_id=6,
                          arg0=pack_args(1, 2)[0]),
        mb.WorkDescriptor(opcode=OP_NOP, request_id=7),
    ]
    out, ref, _ = drain_both(descs)
    assert_drain_equal(out, ref)
    acks = np.asarray(out[2])[0]
    assert int(acks[1, mb.W_STATUS]) == mb.THREAD_PREEMPTED
    assert int(acks[0, mb.W_STATUS]) == mb.THREAD_FINISHED
    assert int(np.asarray(out[4])[0, mb.QC_DRAINED]) == 7


def test_drain_head_tail_window():
    """Rows outside [head, tail) are skipped: NOP acks, zero results,
    untouched workspace, and QC_DRAINED counts only the window."""
    descs = [mb.WorkDescriptor(opcode=OP_SCALE, request_id=i,
                               arg0=pack_scale(0, 0, 2.0)[0],
                               arg1=pack_scale(0, 0, 2.0)[1])
             for i in range(4)]
    out, ref, ws_in = drain_both(descs, head=1, tail=3)
    assert_drain_equal(out, ref)
    acks = np.asarray(out[2])[0]
    assert [int(a[mb.W_STATUS]) for a in acks[:4]] == \
        [mb.THREAD_NOP, mb.THREAD_FINISHED, mb.THREAD_FINISHED,
         mb.THREAD_NOP]
    # request ids ride even the skipped rows' acks? no — skipped rows are
    # all-zero NOP stamps except the copied id words
    assert int(np.asarray(out[4])[0, mb.QC_DRAINED]) == 2
    # the doubling ran exactly twice
    np.testing.assert_allclose(np.asarray(out[0])[0, 0], ws_in[0, 0] * 4,
                               rtol=1e-5)


def test_drain_stop_flag_quiesces():
    descs = [mb.WorkDescriptor(opcode=OP_RELU, request_id=i,
                               arg0=pack_args(1, 0)[0]) for i in range(3)]
    out, ref, ws_in = drain_both(descs, stop=1)
    assert_drain_equal(out, ref)
    np.testing.assert_array_equal(np.asarray(out[0])[0], ws_in[0])
    assert int(np.asarray(out[4])[0, mb.QC_DRAINED]) == 0
    acks = np.asarray(out[2])[0]
    assert all(int(a[mb.W_STATUS]) == mb.THREAD_NOP for a in acks[:3])


def test_drain_reduce_carry_within_and_across_launches():
    """Reduce rows thread ONE resumable carry: sequentially within a
    launch, and the carry output re-fed as the next launch's input
    continues the accumulation."""
    d = mb.WorkDescriptor(opcode=OP_REDUCE, request_id=9,
                          arg0=pack_args(0, 1)[0], n_chunks=8)
    out, ref, ws_in = drain_both([d, d.advance()])
    assert_drain_equal(out, ref)
    s = float(ws_in[0, 1].sum())
    np.testing.assert_allclose(np.asarray(out[3])[0, :2, 0], [s, 2 * s],
                               rtol=1e-4)
    # second launch resumes from the carry the first one left behind
    ring = mb.descriptor_ring([d.advance().advance()], 8)[None]
    ctrl = mb.queue_control(tail=1)[None]
    out2 = persistent_drain(jnp.asarray(ctrl), jnp.asarray(ring),
                            out[0], out[1], interpret=True)
    np.testing.assert_allclose(float(np.asarray(out2[3])[0, 0, 0]), 3 * s,
                               rtol=1e-4)
    np.testing.assert_allclose(float(np.asarray(out2[1])[0, 0]), 3 * s,
                               rtol=1e-4)


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_drain_random_programs_property(seed):
    """Random opcode/arg/chunk mixes with a random [head, tail) window:
    kernel and oracle agree on every output, token for token."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 7))
    descs = []
    for i in range(n):
        op = int(rng.integers(0, NUM_DRAIN_OPS))
        dst, a, b = (int(x) for x in rng.integers(0, 4, 3))
        if op == OP_SCALE:
            a0, a1 = pack_scale(dst, a, float(rng.uniform(-2, 2)))
        else:
            a0, a1 = pack_args(dst, a, b)
        n_chunks = int(rng.integers(1, 4))
        descs.append(mb.WorkDescriptor(
            opcode=op, arg0=a0, arg1=a1, request_id=100 + i,
            chunk=int(rng.integers(0, n_chunks)), n_chunks=n_chunks))
    head = int(rng.integers(0, 2))
    tail = int(rng.integers(head, n + 1))
    out, ref, _ = drain_both(descs, head=head, tail=tail, seed=seed,
                             carry0=float(rng.uniform(-1, 1)))
    assert_drain_equal(out, ref)


def test_drain_clusters_run_their_own_queues():
    """A grid over several clusters: each cluster drains its own queue
    and control window against its own workspace block and carry, exactly
    as the oracle runs them one by one."""
    rng = np.random.default_rng(7)
    C, Q = 3, 8
    ws = (rng.standard_normal((C, 4, TILE, TILE)) * 0.1).astype(np.float32)
    rings, ctrls = [], []
    for c in range(C):
        descs = [mb.WorkDescriptor(opcode=int(rng.integers(0, NUM_DRAIN_OPS)),
                                   arg0=pack_args(*(int(x) for x in
                                                    rng.integers(0, 4, 2)))[0],
                                   arg1=int(rng.integers(0, 4)),
                                   request_id=10 * c + i, n_chunks=2)
                 for i in range(2 + c)]
        rings.append(mb.descriptor_ring(descs, Q))
        ctrls.append(mb.queue_control(tail=len(descs), head=c % 2))
    ring, ctrl = np.stack(rings), np.stack(ctrls)
    carry = rng.uniform(-1, 1, (C, 1)).astype(np.float32)
    out = persistent_drain(jnp.asarray(ctrl), jnp.asarray(ring),
                           jnp.asarray(ws), jnp.asarray(carry),
                           interpret=True)
    assert_drain_equal(out, persistent_drain_ref(ctrl, ring, ws, carry))
