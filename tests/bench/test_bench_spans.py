"""The program's own spans and scopes in a trace (``bench/lib/spans.py``):
op labels, trigger spans paired to executions through the runtime's
enqueue events, and the span-based numbers, on a small trace recorded on
a TPU v5e (``tests/bench/record_trace.py``) and on hand-made ones."""
import json
import time

import pytest

from bench_fixtures import ROOT, tiny_root  # noqa: F401
from bench import trace_spans
from bench import run as bench_run
from bench.lib import spans
from bench.lib import trace as tr

RECORDED = ROOT / "tests" / "bench" / "data" / "trace_v5e_spans.json"


@pytest.fixture(scope="module")
def recorded():
    d = json.loads(RECORDED.read_text())
    t = tr.Trace(t0=d["t0"], t1=d["t1"], ops=[tuple(o) for o in d["ops"]],
                 modules=[tuple(m) for m in d["modules"]],
                 spans=[tuple(s) for s in d["spans"]],
                 offset_ns=d["offset_ns"])
    pg = spans.Program(
        spans=[tuple(s) for s in d["program_spans"]], labels=d["labels"],
        runs=d["runs"],
        enqueued={int(k): v for k, v in d["enqueued"].items()},
        completed={int(k): v for k, v in d["completed"].items()})
    return d, t, pg


def test_every_step_op_belongs_to_a_work_function_or_the_runtime(recorded):
    _, t, pg = recorded
    steps = [m for m in t.modules if spans.is_step(m[0])]
    for (name, s, _), lab in zip(t.ops, pg.labels):
        inside = any(m[1] <= s <= m[1] + m[2] for m in steps)
        assert (lab is not None) == inside, name
        if lab is not None:
            assert lab.startswith("lk.op.") or lab.startswith("runtime")
    labels = set(pg.labels)
    assert {"lk.op.decode", "lk.op.prefill", "lk.op.insert",
            "runtime"} <= labels
    assert {"lk.op.decode/mamba2.ssd", "lk.op.decode/lm_head"} <= labels
    # the two copies of the whole stacked SSM state that take most of a
    # decode step run inside the decode branch (XLA's, with no metadata)
    copies = {}
    for (name, _, d), lab in zip(t.ops, pg.labels):
        if name.startswith("%copy.") and name.endswith("f32[4,4,48,64,128]"):
            copies[(name, lab)] = copies.get((name, lab), 0) + d
    top = sorted(copies, key=copies.get)[-2:]
    assert sorted(top) == [("%copy.198 = f32[4,4,48,64,128]", "lk.op.decode"),
                           ("%copy.204 = f32[4,4,48,64,128]", "lk.op.decode")]


def test_the_span_pairing_pairs_what_match_launches_pairs(recorded):
    d, t, pg = recorded
    pairs = spans.pair_triggers(t, pg)
    matched = tr.match_launches(t, d["launches"])
    assert len(pairs) == len(matched) == 19
    for (sp, ex), (ln, s) in zip(pairs, matched):
        assert ex[2] / 1e9 == s
        assert spans.ints(sp[3]["ops"]) == [op for op, _, _ in ln["items"]]
        assert spans.ints(sp[3]["rids"]) == [r for _, r, _ in ln["items"]]
        assert sp[3]["batch"] == ln["batch"]
    # the device's clock runs behind the host's: on the trace's clock an
    # execution may read as starting before the span that launched it,
    # never more than the lag the trace bounds
    lo, hi = spans.lag_bounds(t, pg)
    assert 0 < lo <= hi < 3_000_000
    assert sum(ex[1] < sp[1] for sp, ex in pairs) >= 10
    assert all(ex[1] + hi >= sp[1] for sp, ex in pairs)


def _leaves_by_hand(t, labels):
    """Innermost step ops in the window: sorted by start (longest first),
    an op holds another iff the next one starts before it ends."""
    ops = sorted((s, -d, lab) for (_, s, d), lab in zip(t.ops, labels)
                 if lab is not None and t.t0 <= s <= t.t1)
    return [(s, -nd, lab) for k, (s, nd, lab) in enumerate(ops)
            if k + 1 == len(ops) or ops[k + 1][0] >= s - nd]


def test_runtime_overhead_share_counted_by_hand(recorded):
    _, t, pg = recorded
    leaves = _leaves_by_hand(t, pg.labels)
    outside = sum(d for _, d, lab in leaves if lab == "runtime")
    total = sum(d for _, d, _ in leaves)
    assert outside == 4844798 and total == 26654269
    assert spans.runtime_overhead_share(t, pg) == pytest.approx(
        100 * outside / total, rel=1e-4)


def test_launch_gap_ms_counted_by_hand(recorded):
    _, t, pg = recorded
    # the window holds whole launches only: the k-th trigger span
    # launched the k-th step execution
    trig = [sp for sp in pg.spans if sp[0] == "lk.trigger"]
    execs = sorted((m for m in t.modules if spans.is_step(m[0])),
                   key=lambda m: m[1])
    decode = [ex for sp, ex in zip(trig, execs)
              if 0 in spans.ints(sp[3]["ops"])]
    assert len(decode) == 17
    gaps = sorted((b[1] - a[1] - a[2]) / 1e6
                  for a, b in zip(decode, decode[1:])
                  if execs.index(b) == execs.index(a) + 1)
    assert len(gaps) == 15          # the insert splits one pair
    assert gaps[7] == pytest.approx(2.901065)
    assert spans.launch_gap_ms(t, pg) == pytest.approx(2.901065)


def test_host_idle_share_counted_by_hand(recorded):
    _, t, pg = recorded
    lag = spans.device_lag_ns(t, pg)
    assert lag == 1690140
    busy = tr.union(((s, s + d) for _, s, d in t.ops), t.t0 - lag,
                    t.t1 - lag)
    polls = [(s, s + d) for n, s, d, _ in pg.spans if n == "streams.poll"]
    # sample the window every microsecond of the host's clock: idle where
    # no op runs at the device's reading of that instant
    idle_in_poll = b = 0
    for x in range(t.t0, t.t1, 1000):
        while b < len(busy) and busy[b][1] <= x - lag:
            b += 1
        if b < len(busy) and busy[b][0] <= x - lag:
            continue
        if any(s <= x < e for s, e in polls):
            idle_in_poll += 1000
    share = 100 * idle_in_poll / (t.t1 - t.t0)
    assert share == pytest.approx(65.02, abs=0.01)
    assert spans.host_idle_share(t, pg) == pytest.approx(share, abs=0.01)


def _skewed():
    """Two executions that read 3 ns early against the host's clock (each
    starts before the span that launched it), and one no span launched
    (in flight when the trace began)."""
    t = tr.Trace(t0=0, t1=100, ops=[("a", 0, 5), ("b", 30, 5),
                                    ("c", 60, 5)],
                 modules=[("jit__lk_step(1)", 0, 5),
                          ("jit__lk_step(1)", 30, 5),
                          ("jit__lk_multi_step(2)", 60, 5)])
    pg = spans.Program(
        spans=[("streams.poll", 20, 80, {}),
               (spans.TRIGGER, 32, 5, {"ops": 0, "batch": 1}),
               (spans.TRIGGER, 62, 5, {"ops": "0 3", "batch": 2})],
        labels=["runtime", "lk.op.decode", "lk.op.decode"],
        runs=[7, 8, 9], enqueued={7: -40, 8: 33, 9: 63},
        completed={7: 9, 8: 39, 9: 69})
    return t, pg


def test_pairing_goes_through_the_enqueue_not_the_device_clock():
    t, pg = _skewed()
    pairs = spans.pair_triggers(t, pg)
    assert [(sp[3]["ops"], ex[1]) for sp, ex in pairs] == [(0, 30),
                                                            ("0 3", 60)]
    assert spans.lag_bounds(t, pg) == (3, 4)
    assert spans.device_lag_ns(t, pg) == 3
    assert spans.launch_gap_ms(t, pg) == pytest.approx(25e-6)
    # idle on the host's clock: [8, 33) and [38, 63) and [68, 103) lie in
    # the poll [20, 100) for 13 + 25 + 32 ns of the 100
    assert spans.host_idle_share(t, pg) == pytest.approx(70.0)


def test_span_helpers():
    assert spans.ints("0 2 2") == [0, 2, 2] and spans.ints(3) == [3]
    assert spans.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert spans.label("", "jit/lk.op.decode/while/mamba2.ssd/mul") == \
        "runtime/mamba2.ssd"
    assert spans.label("lk.op.insert", "") == "lk.op.insert"


def test_a_traced_run_reads_the_program_spans(tiny_root):  # noqa: F811
    args = bench_run.parse_args(["--workload", "tiny-mamba2.tiny", "--seed",
                                 str(2 ** 33 + 5), "--seconds", "3",
                                 "--trace", "1"])
    with trace_spans.hooked() as seen:
        res = bench_run.run(args, root=tiny_root, require_accelerator=False,
                            t_start=time.perf_counter())
    assert res["correct"]
    # a traced run of the tool reads the end-to-end metrics as well
    assert {"itl_p95_ms", "tokens_per_s", "setup_s",
            "steps_per_doorbell"} <= set(res["metrics"])
    pg = seen["program"]
    assert {"streams.poll", "lk.trigger", "lk.wait"} <= \
        {sp[0] for sp in pg.spans}
    assert seen["scopes"] and set(seen["scopes"]) >= {"jit__lk_step"}
    # the CPU has no device plane: nothing is labelled, nothing read
    assert all(lab is None for lab in pg.labels)
    assert all(fn(seen["trace"], pg) is None
               for fn in spans.METRICS.values())
