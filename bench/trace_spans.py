"""One traced run of a cell, read also through the program's own spans.

    python3 bench/trace_spans.py --workload <cell> --seed <n> --seconds <s>

Runs the cell exactly as ``bench/run.py --trace 1`` does, and reads the
same trace once more with ``bench/lib/spans.py``: the step programs'
operations by work function (the runtime's ``op_scopes()``, taken before
the engine is freed) and the program's spans. The last line of standard
output is run.py's result object, its metrics the cell's end-to-end ones
as well as its per-layer ones (what tracing costs shows beside an
untraced run), with one more key, ``program``: the span-based per-layer
numbers (``runtime_overhead_share``, ``launch_gap_ms``,
``host_idle_share``; None where the program writes nothing to read).
Standard error also gets the step programs' device seconds by work
function and model scope, the operations that took most of it with
theirs, the longest idle gaps by the innermost program span over them,
and the window's idle seconds by program span.
"""
from __future__ import annotations

import contextlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run as bench_run  # noqa: E402
from bench.lib import serve, spans, spec  # noqa: E402
from bench.lib import trace as tr  # noqa: E402


@contextlib.contextmanager
def hooked():
    """While open, a traced run reads the cell's end-to-end metrics
    too, ``System.dispose`` keeps the runtime's op scopes first and
    ``trace.extract`` also reads the program's spans from the same
    trace; the dict yielded collects ``trace`` and ``program``."""
    seen: dict = {"scopes": None}
    load, dispose, extract = spec.load, serve.System.dispose, tr.extract

    def load_all_metrics(root, workload):
        cell = load(root, workload)
        cell.per_layer = cell.per_layer + cell.end_to_end
        return cell

    def dispose_keeping_scopes(system):
        op_scopes = getattr(system.engine.rt, "op_scopes", None)
        seen["scopes"] = op_scopes() if op_scopes is not None else None
        dispose(system)

    def extract_with_spans(log_dir, *args, **kwargs):
        seen["trace"] = extract(log_dir, *args, **kwargs)
        seen["program"] = spans.extract(log_dir, seen["scopes"])
        return seen["trace"]

    spec.load = load_all_metrics
    serve.System.dispose = dispose_keeping_scopes
    tr.extract = extract_with_spans
    try:
        yield seen
    finally:
        spec.load = load
        serve.System.dispose = dispose
        tr.extract = extract


def report(t, pg, out=sys.stderr) -> None:
    seen: dict = {}
    for name, _, _, args in pg.spans:
        n, first = seen.get(name, (0, args))
        seen[name] = (n + 1, first)
    for name, (n, args) in sorted(seen.items()):
        print(f"[spans] span {name}: {n}, first args {args}", file=out)
    for lab, s in spans.scope_seconds(t, pg).items():
        print(f"[spans] scope {lab}: {s} s", file=out)
    for name, lab, s in spans.top_ops(t, pg):
        print(f"[spans] op {name} [{lab}]: {s} s", file=out)
    for name, s in spans.idle_gaps(t, pg):
        print(f"[spans] idle gap in {name}: {s} s", file=out)
    for name, s in spans.idle_by_span(t, pg).items():
        print(f"[spans] idle in {name}: {s} s", file=out)
    print(f"[spans] device clock behind the host's by "
          f"{spans.lag_bounds(t, pg)} ns (least, most)", file=out)
    print(f"[spans] lk.trigger spans paired to step executions: "
          f"{len(spans.pair_triggers(t, pg))} of "
          f"{len(tr.step_executions(t))}", file=out, flush=True)


def run(args, **kwargs) -> dict:
    args.trace = 1
    with hooked() as seen:
        result = bench_run.run(args, **kwargs)
    t, pg = seen["trace"], seen["program"]
    result["program"] = {name: fn(t, pg)
                         for name, fn in spans.METRICS.items()}
    report(t, pg)
    return result


def main(argv=None) -> None:
    result = run(bench_run.parse_args(argv))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
