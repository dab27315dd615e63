"""What the program writes into the profiler's trace itself, and the
per-layer numbers that read it.

``trace.extract`` keeps the device's operations and executions and the
harness's spans. This module reads the same ``.xplane.pb`` for the rest:

- the program's own spans (``lk.*``, ``dispatcher.*``, ``streams.*``)
  with their arguments;
- each operation's label, given the runtime's ``op_scopes()``:
  ``lk.op.<work>`` (with the model scope it ran in, as in
  ``lk.op.decode/mamba2.ssd``) or ``runtime`` for an operation of a step
  program, None for any other;
- the ``run_id`` of each execution, and the runtime's host events that
  enqueued it and ran its completion callbacks.

Inside one trace the device's timestamps run behind the host's by a
millisecond or two: an execution reads as starting before the span that
launched it. ``pair_triggers`` needs no tie between the clocks and no
slack: it pairs each ``lk.trigger`` span with the runtime's host event
that enqueued its program, on the host clock, and that event names the
execution by ``run_id``. Where a device interval has to be set against
host spans (an idle gap inside a poll), ``device_lag_ns`` gives the lag
the trace itself bounds: no execution starts before its enqueue, and
none completes after its completion callback.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

from bench.lib import trace as tr
from bench.lib.latency import percentile
from bench.lib.record import OP_DECODE

PROGRAM_PREFIXES = ("lk.", "dispatcher.", "streams.")
TRIGGER = "lk.trigger"
POLL = "streams.poll"
ENQUEUE = "DoEnqueueProgram"    # the runtime's host events, with run_id
COMPLETE = "CompleteCallbacks"
WORK_PREFIX = "lk.op."          # a work function's named scope
RUNTIME = "runtime"             # a step-program op outside every work scope
# the model's named scopes, kept beside the work function in a label
MODEL_SCOPES = ("mamba2.", "lm_head")


@dataclass
class Program:
    # (name, start, dur, args) of the program's spans, in start order
    spans: list = field(default_factory=list)
    # each op's label, in the order of ``Trace.ops``
    labels: list = field(default_factory=list)
    # run_id of each execution, in the order of ``Trace.modules``
    runs: list = field(default_factory=list)
    # run_id -> host start of the event that enqueued that execution
    enqueued: dict = field(default_factory=dict)
    # run_id -> host start of that execution's completion callbacks
    completed: dict = field(default_factory=dict)


def is_step(module: str) -> bool:
    return any(p in module for p in tr.STEP_PROGRAMS)


def label(scope: str, op_name: str) -> str:
    """An op's label from the runtime's (work scope, op_name)."""
    out = scope or RUNTIME
    for part in reversed(op_name.split("/")):
        if part.startswith(MODEL_SCOPES):
            return f"{out}/{part}"
    return out


def _labels(ops: list, modules: list, scopes: dict) -> list:
    """Each op's label: the step program whose execution covers the op
    names the table to look its HLO instruction up in. An op of a step
    program that the table lacks is the runtime's."""
    execs = sorted((m for m in modules if is_step(m[0])),
                   key=lambda m: m[1])
    starts = [m[1] for m in execs]
    tables: dict = {}
    out = []
    for name, s, _ in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s > execs[i][1] + execs[i][2]:
            out.append(None)
            continue
        module = execs[i][0]
        if module not in tables:        # "jit__lk_step(<fingerprint>)"
            tables[module] = next((v for k, v in scopes.items()
                                   if module == k or
                                   module.startswith(k + "(")), {})
        hit = tables[module].get(name.split(" = ")[0].lstrip("%"))
        out.append(label(*hit) if hit else RUNTIME)
    return out


def extract(log_dir: str, scopes: dict | None = None) -> Program:
    """Read the newest ``.xplane.pb`` under ``log_dir``, as
    ``trace.extract`` does. ``scopes`` is the runtime's ``op_scopes()``
    (HLO module -> instruction -> (work scope, op_name)); without it no
    op is labelled."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    pg = Program()
    ops, modules = [], []
    for plane in pd.planes:
        if plane.name == tr.DEVICE_PLANE:
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    ops = [(tr.op_name(e.name), int(e.start_ns),
                            int(e.duration_ns)) for e in line.events]
                elif line.name == tr.MODULES_LINE:
                    for e in line.events:
                        modules.append((e.name, int(e.start_ns),
                                        int(e.duration_ns)))
                        pg.runs.append(dict(e.stats).get("run_id"))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIXES):
                        pg.spans.append((e.name, int(e.start_ns),
                                         int(e.duration_ns), dict(e.stats)))
                    elif e.name in (ENQUEUE, COMPLETE):
                        run = dict(e.stats).get("run_id")
                        if run is not None:
                            into = pg.enqueued if e.name == ENQUEUE \
                                else pg.completed
                            into[int(run)] = int(e.start_ns)
    pg.spans.sort(key=lambda sp: sp[1])
    pg.labels = _labels(ops, modules, scopes) if scopes else \
        [None] * len(ops)
    return pg


# -- reductions ---------------------------------------------------------------

def ints(value) -> list[int]:
    """A span argument that lists numbers (``"0 2 2"`` or ``0``)."""
    return [int(x) for x in str(value).split()]


def pair_triggers(t: tr.Trace, pg: Program) -> list[tuple[tuple, tuple]]:
    """(``lk.trigger`` span, step execution) pairs, in order. Each
    trigger launches one step program, and programs are enqueued in
    trigger order, after the span opened: a span takes the first step
    enqueue left that starts inside or after it, and the enqueue's
    ``run_id`` names the execution. An execution no span launched (one
    in flight when the trace began) is left out, as is one whose enqueue
    the trace lacks."""
    launched = sorted((pg.enqueued[r], m) for m, r in zip(t.modules, pg.runs)
                      if is_step(m[0]) and r in pg.enqueued)
    out, j = [], 0
    for sp in pg.spans:
        if sp[0] != TRIGGER:
            continue
        while j < len(launched) and launched[j][0] < sp[1]:
            j += 1
        if j == len(launched):
            break
        out.append((sp, launched[j][1]))
        j += 1
    return out


def lag_bounds(t: tr.Trace, pg: Program) -> tuple[int, int] | None:
    """(least, most) nanoseconds by which the device's timestamps can run
    behind the host's: every execution started after its enqueue began
    and ended before its completion callbacks began."""
    lo = [pg.enqueued[r] - m[1] for m, r in zip(t.modules, pg.runs)
          if r in pg.enqueued]
    hi = [pg.completed[r] - m[1] - m[2] for m, r in zip(t.modules, pg.runs)
          if r in pg.completed]
    if not lo or not hi:
        return None
    return max(lo), min(hi)


def device_lag_ns(t: tr.Trace, pg: Program) -> int:
    """The middle of ``lag_bounds`` (their least where they cross: the
    lag drifts by some 0.1 ms inside a long trace; 0 where the trace
    cannot bound it): add it to a device time to set it against host
    spans."""
    b = lag_bounds(t, pg)
    return 0 if b is None else (b[0] + max(b)) // 2


def step_leaf_ops(t: tr.Trace, pg: Program) -> list[tuple]:
    """(name, start, dur, label) of the step programs' innermost ops that
    start inside the traced window."""
    idx = [(i, s, d) for i, (_, s, d) in enumerate(t.ops)
           if pg.labels[i] is not None and t.t0 <= s <= t.t1]
    return [(t.ops[i][0], s, d, pg.labels[i])
            for i, s, d in tr.leaf_ops(idx)]


def overlap_ns(a: list, b: list) -> int:
    """Time two lists of sorted, disjoint (start, end) intervals share."""
    total = i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def runtime_overhead_share(t: tr.Trace, pg: Program):
    """Device time of the step programs' innermost ops outside every
    work function's scope, over the device time of all of them, in the
    traced window, in percent (None without labels)."""
    ops = step_leaf_ops(t, pg)
    total = sum(d for _, _, d, _ in ops)
    if not total:
        return None
    outside = sum(d for _, _, d, lab in ops
                  if not lab.startswith(WORK_PREFIX))
    return 100.0 * outside / total


def launch_gap_ms(t: tr.Trace, pg: Program):
    """Median gap on the device between two executions in a row, with no
    other execution between them, whose ``lk.trigger`` spans both carry a
    decode descriptor: start of the second minus end of the first, ms."""
    decode = {ex for sp, ex in pair_triggers(t, pg)
              if OP_DECODE in ints(sp[3].get("ops", ""))}
    runs = sorted((m for m in t.modules if t.t0 <= m[1] <= t.t1),
                  key=lambda m: m[1])
    return percentile([(b[1] - a[1] - a[2]) / 1e6
                       for a, b in zip(runs, runs[1:])
                       if a in decode and b in decode], 50)


def host_idle_gaps(t: tr.Trace, pg: Program) -> list[tuple[int, int]]:
    """The window's idle gaps on the host's clock: the device's, taken
    over the window as the device's clock reads it, moved by its lag."""
    lag = device_lag_ns(t, pg)
    dev = tr.Trace(t0=t.t0 - lag, t1=t.t1 - lag, ops=t.ops)
    return [(s + lag, e + lag) for s, e in tr.idle_gaps(dev)]


def host_idle_share(t: tr.Trace, pg: Program):
    """Time the device sat idle inside ``streams.poll`` spans, over the
    traced window, in percent: idle that the serve loop's own host work
    caused, as against idle for lack of traffic (``harness.sleep``)."""
    if not t.ops:
        return None
    polls = tr.union(((s, s + d) for name, s, d, _ in pg.spans
                      if name == POLL), t.t0, t.t1)
    if not polls:
        return None
    return 100.0 * overlap_ns(host_idle_gaps(t, pg), polls) / (t.t1 - t.t0)


METRICS = {"runtime_overhead_share": runtime_overhead_share,
           "launch_gap_ms": launch_gap_ms,
           "host_idle_share": host_idle_share}


# -- where the time went ------------------------------------------------------

def scope_seconds(t: tr.Trace, pg: Program) -> dict[str, float]:
    """Device seconds of the step programs' innermost ops by label."""
    out: dict[str, float] = {}
    for _, _, d, lab in step_leaf_ops(t, pg):
        out[lab] = out.get(lab, 0.0) + d / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def top_ops(t: tr.Trace, pg: Program, top: int = 10) -> list[list]:
    """The step programs' innermost ops that took most device time in
    the window, each with its label (seconds)."""
    per: dict[tuple, int] = {}
    for name, _, d, lab in step_leaf_ops(t, pg):
        per[(name, lab)] = per.get((name, lab), 0) + d
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    return [[n, lab, d / 1e9] for (n, lab), d in ops]


def span_at(t: tr.Trace, pg: Program, at: int) -> str:
    """Name of the innermost program span covering ``at``, else of the
    harness span (``trace.span_at``)."""
    best = None
    for name, s, d, _ in pg.spans:
        if s <= at <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else tr.span_at(t, at)


def idle_by_span(t: tr.Trace, pg: Program) -> dict[str, float]:
    """Idle seconds of the window by the innermost program span (else
    harness span) that the host was in, each gap cut at the spans'
    edges on the host's clock."""
    edges = sorted({x for _, s, d, _ in pg.spans for x in (s, s + d)})
    out: dict[str, float] = {}
    for a, b in host_idle_gaps(t, pg):
        cuts = [a] + edges[bisect.bisect_right(edges, a):
                           bisect.bisect_left(edges, b)] + [b]
        for lo, hi in zip(cuts, cuts[1:]):
            name = span_at(t, pg, (lo + hi) // 2)
            out[name] = out.get(name, 0.0) + (hi - lo) / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def idle_gaps(t: tr.Trace, pg: Program, top: int = 10) -> list[list]:
    """The longest idle gaps, each named by the innermost program span
    over its middle on the host's clock (seconds)."""
    gaps = sorted(host_idle_gaps(t, pg), key=lambda g: g[0] - g[1])[:top]
    return [[span_at(t, pg, (s + e) // 2), (e - s) / 1e9] for s, e in gaps]
