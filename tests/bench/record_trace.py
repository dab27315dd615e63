"""Record the small chip trace that the trace tests read.

    python3 tests/bench/record_trace.py --out <dir>      # on one chip
    python3 tests/bench/record_trace.py --reduce <dir> \\
        --json tests/bench/data/trace_v5e_spans.json    # anywhere

On one chip: mamba2-780m widths cut to 4 layers, 4 slots, max_seq 256,
bf16, served through ``bench/lib/serve.py`` for a few seconds under the
profiler (two streams opened together, so their prefills share a
doorbell, then more one at a time). ``--out`` keeps the raw bundle: the
``.xplane.pb``, the runtime's op scopes, the dispatcher's launches and
the clock tie of ``harness.mark``, plus ``inventory.txt``, a listing of
the trace's planes, lines and a few events of each with their stats.
``--reduce`` cuts a bundle to a short stretch of the window
(``--start-ms``, ``--ms``) and writes the JSON the tests read.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

LAYERS, SLOTS, MAX_SEQ, SEED = 4, 4, 256, 7


def _requests(vocab: int):
    import numpy as np
    from bench.lib.traffic import HIGH, LOW, Request
    rng = np.random.default_rng(SEED)

    def req(due, n, new, crit=LOW):
        return Request(due, rng.integers(0, vocab, n, dtype=np.int32),
                       new, crit)
    return [req(0.0, 150, 24), req(0.0, 40, 16, HIGH), req(0.5, 100, 20),
            req(1.0, 70, 12, HIGH), req(1.6, 180, 30), req(2.2, 20, 8)]


def inventory(xplane: str, path: Path, per_line: int = 4) -> None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane)
    with open(path, "w") as f:
        for plane in pd.planes:
            lines = list(plane.lines)
            f.write(f"PLANE {plane.name}: {len(lines)} lines\n")
            for line in lines:
                evs = list(line.events)
                f.write(f"  LINE {line.name}: {len(evs)} events\n")
                shown = 0
                for e in evs:
                    keys = {k for k, _ in e.stats}
                    named = e.name.startswith(("lk.", "dispatcher.",
                                               "streams.", "harness.")) \
                        or "run_id" in keys or "xecute" in e.name
                    if shown >= per_line and not (named and shown < 60):
                        continue
                    shown += 1
                    stats = {k: (v if len(str(v)) < 300 else
                                 str(v)[:300] + "...")
                             for k, v in e.stats}
                    f.write(f"    {e.name[:160]!r} start={e.start_ns} "
                            f"dur={e.duration_ns} stats={stats}\n")


def record(out: Path, seconds: float = 3.0,
           require_accelerator: bool = True) -> None:
    import jax
    import jax.numpy as jnp
    from bench.lib import serve, spec
    from bench.lib import trace as tr
    from bench.lib import weights as W
    from repro.models import build

    if require_accelerator and jax.devices()[0].platform == "cpu":
        sys.exit("record_trace: JAX found no accelerator")
    cell = spec.load(ROOT, "mamba2-780m.chat-steady")
    c = dict(cell.config, n_layer=LAYERS)
    fam = cell.family
    dtype = jnp.dtype(c["dtype"])
    model = build(fam.program_config(c))
    params = serve.weights_to_host(W.make(fam.weight_specs(c), SEED, dtype))
    system = serve.build_system(model, params, SLOTS, MAX_SEQ)
    serve.warm_up(system, c["vocab_size"])
    requests = _requests(c["vocab_size"])
    d = tempfile.mkdtemp(prefix="record-trace-")
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation(tr.MARK):
        mark_ns = time.perf_counter_ns()
    t0 = time.perf_counter()
    win = serve.drive(system, requests, t0, seconds, tracing=True)
    jax.profiler.stop_trace()
    out.mkdir(parents=True, exist_ok=True)
    src = max(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                        recursive=True), key=os.path.getmtime)
    shutil.copy(src, out / "trace.xplane.pb")
    scopes = getattr(system.engine.rt, "op_scopes", dict)()
    (out / "scopes.json").write_text(json.dumps(scopes))
    (out / "meta.json").write_text(json.dumps({
        "mark_ns": mark_ns, "t0": win.t0, "t1": win.t1,
        "launches": serve.launches(system, win.t0, win.t1),
        "device": jax.devices()[0].device_kind}))
    inventory(str(out / "trace.xplane.pb"), out / "inventory.txt")
    system.dispose()


def reduce(bundle: Path, json_path: Path, start_ms: float,
           ms: float) -> dict:
    """Cut ``ms`` milliseconds of the bundle's window, from ``start_ms``
    after its start, to the JSON the tests read (events that reach into
    the cut are kept whole)."""
    from bench.lib import spans
    from bench.lib import trace as tr
    meta = json.loads((bundle / "meta.json").read_text())
    scopes = json.loads((bundle / "scopes.json").read_text())
    full = tr.extract(str(bundle), meta["mark_ns"], meta["t0"], meta["t1"])
    pg = spans.extract(str(bundle), scopes)
    t0 = full.t0 + int(start_ms * 1e6)
    t1 = t0 + int(ms * 1e6)

    def inside(s, d):
        return s + d >= t0 and s <= t1

    keep_ops = [i for i, o in enumerate(full.ops) if inside(o[1], o[2])]
    keep_mods = [i for i, m in enumerate(full.modules)
                 if inside(m[1], m[2])]
    runs = [pg.runs[i] for i in keep_mods]
    d = {"source": f"mamba2-780m widths cut to {LAYERS} layers, {SLOTS} "
                   f"slots, max_seq {MAX_SEQ}, bf16, served through "
                   f"bench/lib/serve.py on one {meta['device']}: {ms:g} ms "
                   "of the window, from a prefill chunk through insert, "
                   "decode steps and a two-descriptor doorbell, with the "
                   "program's spans and each op's scope",
         "t0": t0, "t1": t1, "offset_ns": full.offset_ns,
         "ops": [full.ops[i] for i in keep_ops],
         "labels": [pg.labels[i] for i in keep_ops],
         "modules": [full.modules[i] for i in keep_mods],
         "runs": runs,
         "enqueued": {str(r): pg.enqueued[r] for r in runs
                      if r in pg.enqueued},
         "completed": {str(r): pg.completed[r] for r in runs
                       if r in pg.completed},
         "spans": [s for s in full.spans if inside(s[1], s[2])],
         "program_spans": [s for s in pg.spans if inside(s[1], s[2])],
         "launches": [ln for ln in meta["launches"]
                      if inside(full.to_trace_ns(ln["t"]), 0)]}
    json_path.write_text(json.dumps(d, separators=(",", ":")) + "\n")
    return d


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", type=Path)
    ap.add_argument("--reduce", type=Path)
    ap.add_argument("--json", type=Path)
    ap.add_argument("--start-ms", type=float, default=82.3)
    ap.add_argument("--ms", type=float, default=78.0)
    args = ap.parse_args(argv)
    if args.out:
        record(args.out)
    if args.reduce:
        reduce(args.reduce, args.json, args.start_ms, args.ms)


if __name__ == "__main__":
    main()
