"""The program's own spans and scopes in the profiler's trace: named
work-function scopes in the compiled step programs (and the Mamba2
block's parts under them), their attribution rules, the host phase spans
of the serve path written into a CPU trace with their arguments,
``WcetTracker`` statistics that tracing leaves alone, and TTFT counted
from a request's arrival."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core import mailbox as mb
from repro.core import op_scopes
from repro.core.sched import CRIT_HIGH, CRIT_LOW
from repro.core.telemetry import EV_STREAM
from repro.core.telemetry.events import now_us
from repro.core.wcet import PHASES, QUEUE_DEPTH, WcetTracker
from repro.distributed import ShardCtx
from repro.models import build
from repro.serving import ServingEngine, StreamFrontend

WORKS = ("decode", "insert", "prefill", "release")
MAMBA = ("mamba2.in_proj", "mamba2.conv", "mamba2.ssd", "mamba2.out_proj")
SERVE_SPANS = ("streams.poll", "streams.admit", "dispatcher.pick",
               "lk.trigger", "lk.wait", "dispatcher.retire",
               "streams.harvest")


@pytest.fixture(scope="module")
def mamba():
    cfg = get_config("mamba2-780m").reduced()
    model = build(cfg, ShardCtx.single(kind="decode"))
    return model, model.init(jax.random.key(0))


def make_engine(mamba, **kw):
    model, params = mamba
    return ServingEngine(model, params, max_batch=2, max_seq=64,
                         chunked_prefill=True, prefill_chunk_tokens=4, **kw)


def traced(log_dir, fn):
    """Run ``fn`` under the profiler; return the program's spans as
    (name, start, dur, args) in start order."""
    with jax.profiler.trace(str(log_dir)):
        fn()
    path = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("lk.", "dispatcher.", "streams.")):
                    out.append((e.name, e.start_ns, e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


def serve_two(fe):
    fe.open_stream(np.arange(1, 10), max_new_tokens=4, criticality=CRIT_HIGH)
    fe.open_stream(np.arange(3, 9), max_new_tokens=3, criticality=CRIT_LOW)
    fe.serve(max_polls=3000)


# -- scopes in the compiled step programs ------------------------------------

@pytest.mark.parametrize("program", ["_lk_step", "_lk_multi_step"])
def test_step_programs_put_each_work_function_under_its_scope(mamba,
                                                              program):
    eng = make_engine(mamba)
    rt = eng.rt
    rt._ensure_multi()
    state, carries = rt.state, rt._carries
    arg = (jnp.asarray(mb.nop_descriptor()) if program == "_lk_step" else
           jnp.tile(jnp.asarray(mb.nop_descriptor()), (rt.max_steps, 1)))
    lowered = jax.jit(getattr(rt, program)).lower(state, carries, arg)
    text = lowered.as_text(dialect="hlo", debug_info=True)
    for work in WORKS:
        assert f"/lk.op.{work}/" in text
    # the compiled programs, as the runtime reports them
    module = f"jit_{program}"
    ops = rt.op_scopes()[module]
    scopes = {scope for scope, _ in ops.values()}
    assert {f"lk.op.{w}" for w in WORKS} <= scopes
    assert "" in scopes                     # the runtime's own plumbing
    for part in MAMBA:
        owners = {scope for scope, op in ops.values() if f"/{part}/" in op}
        assert owners and owners <= {"lk.op.decode", "lk.op.prefill"}
    head = {scope for scope, op in ops.values() if "/lm_head/" in op}
    assert head and head <= {"lk.op.decode", "lk.op.prefill"}
    eng.dispose()


HLO = """HloModule jit__lk_step, entry_computation_layout={()->f32[]}

%fused_copy (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %m = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(_lk_step)/cond/branch_0_fun/lk.op.decode/mamba2.ssd/mul"}
}

%branch_0 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %copy.5 = f32[4]{0} copy(%a)
  ROOT %fusion.1 = f32[4]{0} fusion(%copy.5), kind=kLoop, calls=%fused_copy
}

%branch_1 (b: f32[4]) -> f32[4] {
  %b = f32[4]{0} parameter(0)
  ROOT %add.2 = f32[4]{0} add(%b, %b), metadata={op_name="jit(_lk_step)/cond/branch_1_fun/lk.op.insert/add"}
}

ENTRY %main (x: f32[4], i: s32[]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %i = s32[] parameter(1)
  %copy.9 = f32[4]{0} copy(%x)
  %neg.3 = f32[4]{0} negate(%copy.9), metadata={op_name="jit(_lk_step)/lk.op.decode/neg"}
  ROOT %conditional.4 = f32[4]{0} conditional(%i, %neg.3, %copy.9), branch_computations={%branch_0, %branch_1}
}
"""


def test_attribution_rules_on_a_hand_written_module():
    module, ops = op_scopes.attribute(HLO)
    assert module == "jit__lk_step"
    scope = {name: s for name, (s, _) in ops.items()}
    # rule 1: the op's own op_name (also outside any branch)
    assert scope["m"] == scope["neg.3"] == "lk.op.decode"
    assert scope["add.2"] == "lk.op.insert"
    # rule 2: a copy XLA added inside the decode branch, with no metadata
    assert scope["copy.5"] == scope["fusion.1"] == "lk.op.decode"
    # everything else is the runtime's
    assert scope["copy.9"] == scope["conditional.4"] == scope["x"] == ""
    assert ops["m"][1].endswith("mamba2.ssd/mul")


def test_a_program_without_work_scopes_is_left_out(mamba):
    """An executable the compilation cache returns may come from code
    without the scopes (the cache key leaves metadata out): it is not
    reported, rather than read as all runtime."""
    eng = make_engine(mamba)
    rt = eng.rt
    assert set(rt.op_scopes()) == {"jit__lk_step"}

    class Unscoped:
        def as_text(self):
            return HLO.replace("lk.op.", "other.")
    rt._compiled_multi = Unscoped()
    assert set(rt.op_scopes()) == {"jit__lk_step"}
    rt._compiled_multi = None
    eng.dispose()


# -- host spans in the profiler's trace --------------------------------------

def test_serve_loop_writes_its_spans_into_the_trace(mamba, tmp_path):
    eng = make_engine(mamba)
    fe = StreamFrontend(eng)
    serve_two(fe)                                      # warm: compiles
    serve_two(fe)
    eng.tracker.stats.clear()
    serve_two(fe)
    untraced = {k: v.count for k, v in eng.tracker.stats.items()}
    eng.tracker.stats.clear()
    spans = traced(tmp_path, lambda: serve_two(fe))
    names = {s[0] for s in spans}
    assert set(SERVE_SPANS) <= names
    args = {name: [a for n, _, _, a in spans if n == name]
            for name in SERVE_SPANS}
    trig = args["lk.trigger"]
    assert all({"ops", "rids", "chunks", "batch", "depth"} <= set(a)
               for a in trig)
    assert {a["ops"] for a in trig if a["batch"] == 1} >= {0, 1, 2, 3}
    admits = args["streams.admit"]
    assert len(admits) == 2 and all({"sid", "rid"} <= set(a)
                                    for a in admits)
    # a request's spans share its engine request id
    rids = {a["rid"] for a in admits}
    assert rids <= {a["rids"] for a in trig}
    assert rids <= {a["rid"] for a in args["lk.wait"]}
    assert rids <= {a["rid"] for a in args["dispatcher.retire"]}
    assert all("cluster" in a for a in args["dispatcher.pick"])
    assert all("live" in a for a in args["streams.poll"])
    assert sum(a["tokens"] for a in args["streams.harvest"]) > 0
    # nesting on the one host thread: each lk.trigger lies inside a pick
    # or a retire (a chunk's remainder), each lk.wait inside a retire
    def inside(name, parents):
        outer = [s for s in spans if s[0] in parents]
        return all(any(p[1] <= s[1] and s[1] + s[2] <= p[1] + p[2]
                       for p in outer) for s in spans if s[0] == name)
    assert inside("lk.trigger", ("dispatcher.pick", "dispatcher.retire"))
    assert inside("lk.wait", ("dispatcher.retire",))
    assert inside("dispatcher.retire", ("streams.poll",))
    # the tracker's statistics: the same keys and counts, traced or not
    got = {k: v.count for k, v in eng.tracker.stats.items()}
    assert got == untraced
    assert {"trigger", "wait", QUEUE_DEPTH, "dispatcher.pick",
            "streams.poll"} <= set(got)
    assert set(PHASES) == {"init", "trigger", "wait", "dispose"}
    eng.dispose()


def test_phase_yields_args_only_inside_a_trace(tmp_path):
    t = WcetTracker("t")
    with t.phase("wait", "lk.wait", rid=3) as span:
        assert span is None
    seen = []

    def run():
        with t.phase("wait", "lk.wait", rid=3) as span:
            seen.append(span)
            span["more"] = (1, 2)
    spans = traced(tmp_path, run)
    assert seen == [{"more": (1, 2)}]
    assert [(n, a) for n, _, _, a in spans] == [
        ("lk.wait", {"rid": 3, "more": "1 2"})]
    assert t.stats["wait"].count == 2


# -- TTFT from arrival --------------------------------------------------------

def test_ttft_counts_from_arrival(mamba):
    eng = make_engine(mamba)
    fe = StreamFrontend(eng)
    early = now_us() - 5_000_000          # queued 5 s before open_stream
    a = fe.open_stream(np.arange(1, 6), max_new_tokens=2,
                       criticality=CRIT_LOW, arrival_us=early)
    b = fe.open_stream(np.arange(2, 7), max_new_tokens=2,
                       criticality=CRIT_LOW)
    fe.serve(max_polls=3000)
    sa, sb = fe.streams[a], fe.streams[b]
    assert sa.arrival_us == early and sb.arrival_us == sb.opened_us
    first = {e.request_id: e.extra["ttft_us"]
             for e in fe.collector.events_of(EV_STREAM)
             if e.extra.get("phase") == "first_token"}
    assert first[a] == sa.first_token_us - early >= 5_000_000
    assert first[b] == sb.first_token_us - sb.opened_us < 5_000_000
    opens = {e.request_id: e.extra["arrival_us"]
             for e in fe.collector.events_of(EV_STREAM)
             if e.extra.get("phase") == "open"}
    assert opens == {a: early, b: sb.opened_us}
    hist = fe.collector.hist("stream_ttft_us", sa.opcode)
    assert hist.n == 2 and hist.summary()["worst_us"] >= 5_000_000
    # the arrival moves no deadline: both were admitted from open time
    assert sa.deadline_us > sa.admitted_us and sa.admitted_us > early
    eng.dispose()
