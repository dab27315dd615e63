# One function per paper table. Prints ``name,us_per_call,derived`` CSV and
# writes the same rows as machine-readable JSON so the perf trajectory is
# tracked across PRs. The default output auto-numbers itself as
# ``BENCH_<max existing + 1>.json`` (scanning the repo root), so a new PR's
# run appends to the trajectory without hand-editing this file; pass a path
# positionally to override.
#
#   bench_dispatch    -> paper Tables II (avg) & III (worst): LK vs
#                        traditional phase costs, single-cluster & full,
#                        the pipelined-drain and ticket-result arms, and
#                        the edf/fp/server scheduling-policy comparison
#   bench_throughput  -> train/serve throughput of the persistent stack
#   bench_serving     -> continuous-batching stream frontend: per-stream
#                        TTFT/response percentiles, HIGH bound violations,
#                        shed/re-admit counts, decode/prefill overlap
#   bench_elastic     -> contention-aware elastic recarve: p99 of the
#                        backlogged class before/after a live repartition,
#                        recarve stall (warm-pool reboot vs cold lk_init),
#                        admitted-bound violations across the carve change
#   bench_kernels     -> flash-vs-masked attention, executor dispatch rate
#
# ``--smoke`` is the CI fast path: every module runs with reduced reps so
# bench code cannot silently rot, and NO JSON artifact is written. In
# every mode a module that raises makes the run exit 1.
#
# Roofline terms come from the dry-run (python -m repro.launch.roofline),
# not from wall time — this container is CPU-only.
from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys
import traceback

# repo root on sys.path so ``python benchmarks/run.py`` works from anywhere
_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT))


def default_json_path() -> str:
    """``BENCH_<max existing + 1>.json``: the trajectory numbers itself."""
    nums = [int(m.group(1)) for p in _ROOT.glob("BENCH_*.json")
            for m in (re.fullmatch(r"BENCH_(\d+)\.json", p.name),) if m]
    return f"BENCH_{max(nums, default=0) + 1}.json"


def _prev_values() -> dict[str, float]:
    """``name -> us_per_call`` from the HIGHEST-numbered existing
    BENCH_*.json — the trajectory baseline ``*_speedup`` rows are
    annotated against (empty when no prior file or it is unreadable)."""
    best, best_n = None, -1
    for p in _ROOT.glob("BENCH_*.json"):
        m = re.fullmatch(r"BENCH_(\d+)\.json", p.name)
        if m and int(m.group(1)) > best_n:
            best, best_n = p, int(m.group(1))
    if best is None:
        return {}
    try:
        with open(best) as f:
            records = json.load(f)
        return {r["name"]: r["us_per_call"] for r in records
                if isinstance(r, dict) and r.get("us_per_call") is not None}
    except (OSError, ValueError, KeyError, TypeError):
        return {}


def _row_record(row: str, prev: dict[str, float] | None = None) -> dict:
    """``name,us_per_call[,derived...]`` -> JSON record; non-numeric value
    columns (e.g. ERROR rows) map us_per_call to None. ``*_speedup`` rows
    gain a ``prev=<value>`` derived field from the previous BENCH file so
    each new file shows its own trajectory without hand-diffing."""
    parts = row.split(",")
    name = parts[0]
    try:
        us = float(parts[1]) if len(parts) > 1 else None
    except ValueError:
        us = None
    derived = ",".join(parts[2:]) if len(parts) > 2 else ""
    # ``*_speedup`` rows always carry their trajectory; the lk_dispose
    # rows carry it too as a regression note — PR 8 moved the blocking
    # teardown off the dispose hot path (deferred to ``reap``), and the
    # prev= tag is what shows the ~1890µs -> O(µs) drop in-band.
    # ``*_per_sec`` throughput rows (PR 9's drain-megakernel rate) track
    # the same way: a rate regression shows as prev > current in-band.
    # ``*_p99_us`` tail rows and ``*_overhead_pct`` instrumentation-cost
    # rows (PR 10's flight recorder) are trajectory-tracked too: a tail
    # or probe-cost creep is exactly the regression these exist to catch
    if prev and name in prev and (name.endswith("_speedup")
                                  or name.endswith("_lk_dispose")
                                  or name.endswith("_per_sec")
                                  or name.endswith("_p99_us")
                                  or name.endswith("_overhead_pct")):
        tag = f"prev={prev[name]:g}"
        derived = f"{derived},{tag}" if derived else tag
    return {"name": name, "us_per_call": us, "derived": derived}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("json_path", nargs="?", default=None,
                    help="output JSON (default: auto-numbered "
                         "BENCH_<n+1>.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI path: reduced reps; JSON written only "
                         "when a path is given explicitly")
    args = ap.parse_args(sys.argv[1:] if argv is None else argv)
    explicit_json = args.json_path is not None
    if args.json_path is None:
        args.json_path = default_json_path()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_dispatch, bench_elastic, bench_kernels,
                            bench_serving, bench_throughput)
    prev = _prev_values()
    print("name,us_per_call,derived")
    records = []
    failures = 0
    for mod in (bench_dispatch, bench_throughput, bench_serving,
                bench_elastic, bench_kernels):
        try:
            for row in mod.run(smoke=args.smoke):
                rec = _row_record(row, prev)
                print(",".join([rec["name"],
                                row.split(",")[1] if "," in row else "",
                                rec["derived"]]).rstrip(","), flush=True)
                records.append(rec)
        except Exception as e:  # pragma: no cover — keep the harness going
            traceback.print_exc()
            failures += 1
            row = f"{mod.__name__},ERROR,{type(e).__name__}"
            print(row, flush=True)
            records.append(_row_record(row))
    if args.smoke and not explicit_json:
        print(f"# smoke: {len(records)} rows, no JSON written",
              file=sys.stderr)
    else:
        with open(args.json_path, "w") as f:
            json.dump(records, f, indent=2)
            f.write("\n")
        print(f"# wrote {len(records)} rows to {args.json_path}",
              file=sys.stderr)
    if failures:   # a module raised: its rows are ERROR rows, not results
        sys.exit(1)


if __name__ == "__main__":
    main()
