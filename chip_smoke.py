"""Smoke run of the persistent-runtime stack on a TPU chip.

    python chip_smoke.py             # one chip: serve phase + drain phase
    python chip_smoke.py --chips 4   # four chips: clusters on their own
                                     # chips + the sharded train step

One chip: ``mamba2-780m`` at its published size (48 layers, d_model 1536,
bf16, random weights from ``--seed``) serves through the normal entry
point (``repro.launch.serve``: ServingEngine -> Dispatcher ->
PersistentRuntime), once plain and once through the StreamFrontend with
chunked prefill. One request's tokens are checked against a plain jitted
prefill-and-decode loop of the same weights. Then ``LkSystem(runtime=
"mega")`` drains tile-op descriptors through the compiled drain megakernel
and its results are checked against ``persistent_drain_ref``.

Four chips (``--chips 4``, only this path runs): ``LkSystem`` boots four
one-chip clusters under both runtimes, checks that each cluster's arrays
sit on its own chip and that every cluster's results match one cluster's,
then checks the sharded train step on a (2, 2) mesh against one chip.

Lines before the last are smoke readings, not benchmarks. The last line
is one JSON object naming the device. Without a TPU the script exits
non-zero and prints no result; it never falls back to the CPU. Everything
runs in this one process: a child process could not reach the chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "mamba2-780m"
# serve phase: requests answered, new tokens each, slots, cache length
REQUESTS, MAX_NEW, MAX_BATCH, MAX_SEQ = 8, 16, 16, 512
# A greedy token of the engine may differ from the plain loop's only where
# the plain loop's logits nearly tie: the engine's token must then score
# within LOGIT_TOL * max|logit| of the plain loop's best at that step
# (8 bf16 unit roundoffs, 2**-8 each, of the largest logit).
LOGIT_TOL = 2.0 ** -5
# drain phase: the compiled kernel against the numpy oracle; f32 tiles go
# through the MXU, so results agree to 2e-2 of the largest magnitude
DRAIN_RTOL = 2e-2
# four-chip cluster check: every cluster against one cluster
CLUSTER_RTOL = 1e-5
# four-chip train check: mamba2-780m widths cut to this depth and batch
TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ = 8, 8, 256
TRAIN_LOSS_RTOL, TRAIN_PARAM_ATOL = 1e-2, 5e-2


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------

def plain_decode_gaps(model, params, prompt, tokens, max_seq: int):
    """Run ``prompt`` through a plain jitted prefill and a batch-1 decode
    loop fed with ``tokens`` (the engine's greedy tokens, teacher-forced).
    Returns (steps where the plain loop's argmax equals the engine's
    token, the largest logit gap where they part over max|logit| there)."""
    prefill = jax.jit(functools.partial(model.prefill, max_seq=max_seq))
    decode = jax.jit(model.decode_step)
    logits, caches = prefill(params, {"tokens": jnp.asarray(prompt[None])})
    steps = [logits[0, -1]]
    n = len(prompt)
    for k in range(1, len(tokens)):
        logits, caches = decode(
            params, caches, jnp.asarray([[tokens[k - 1]]], jnp.int32),
            jnp.asarray([n + k - 1], jnp.int32))
        steps.append(logits[0, 0])
    lg = np.asarray(jnp.stack(steps).astype(jnp.float32))
    eng = np.asarray(tokens)
    best = lg.argmax(-1)
    rows = np.arange(len(eng))
    gap = (lg[rows, best] - lg[rows, eng]) / np.abs(lg).max(-1)
    return int((best == eng).sum()), float(gap.max())


def serve_phase(extra_args: list, *, arch: str = ARCH, reduced: bool = False,
                requests: int = REQUESTS, max_new: int = MAX_NEW,
                max_batch: int = MAX_BATCH, max_seq: int = MAX_SEQ,
                seed: int = 0) -> dict:
    """Serve ``requests`` requests through ``repro.launch.serve`` and check
    them: every request answered in full, no cluster failed or replayed,
    no chunk-protocol error, and request 0's tokens against the plain
    decode loop. Work is drained before dispose, so nothing is
    swallowed."""
    from repro.core.persistent import reap_deferred
    from repro.launch import serve

    argv = ["--arch", arch, "--requests", str(requests),
            "--max-new", str(max_new), "--max-batch", str(max_batch),
            "--max-seq", str(max_seq), "--seed", str(seed)] + extra_args
    if reduced:
        argv.append("--reduced")
    t0 = time.perf_counter()
    run = serve.serve(serve.parse_args(argv))
    seconds = time.perf_counter() - t0
    disp = run.engine.dispatcher
    disp.drain()
    ds = disp.deadline_stats()
    check(len(run.outs) == requests, f"{len(run.outs)}/{requests} answered")
    check(all(len(o) == max_new for o in run.outs),
          f"token counts {[len(o) for o in run.outs]} != {max_new}")
    check(ds["failed_clusters"] == 0 and ds["replayed"] == 0,
          f"failed clusters {ds['failed_clusters']}, "
          f"replayed {ds['replayed']}")
    check(ds["chunk_protocol_errors"] == 0 and ds["ack_mismatches"] == 0
          and not disp.failure_callback_errors,
          f"protocol errors in {ds}")
    matched, gap = plain_decode_gaps(run.model, run.params, run.prompts[0],
                                     run.outs[0], max_seq)
    check(gap <= LOGIT_TOL,
          f"engine token scores {gap:.3g} x max|logit| below the plain "
          f"loop's best (tolerance {LOGIT_TOL:.3g})")
    run.engine.dispose()
    reap_deferred()
    return {"seconds": seconds, "completed": ds["n"],
            "tokens": sum(len(o) for o in run.outs),
            "matched": matched, "steps": len(run.outs[0]),
            "max_gap": gap}


# ---------------------------------------------------------------------------
# drain phase (megakernel) and the clusters phase
# ---------------------------------------------------------------------------

def tile_program(seed: int, n: int = 30) -> list:
    """``n`` random atomic tile ops plus one reduce of four chunks in the
    middle: (class name, arg0, arg1, n_chunks) rows."""
    from repro.kernels.persistent import kernel as K
    from repro.kernels.persistent.ops import TILE_OP_NAMES
    rng = np.random.default_rng(seed)
    prog = []
    for _ in range(n):
        op = int(rng.integers(0, K.OP_REDUCE))
        dst, a, b = (int(x) for x in rng.integers(0, 8, 3))
        if op == K.OP_SCALE:
            a0, a1 = K.pack_scale(dst, a, float(rng.uniform(-1.5, 1.5)))
        else:
            a0, a1 = K.pack_args(dst, a, b)
        prog.append((TILE_OP_NAMES[op], a0, a1, 1))
    prog.insert(n // 2, ("reduce", K.pack_args(0, 5)[0], 0, 4))
    return prog


def tile_oracle(prog: list, seed: int):
    """``persistent_drain_ref`` over the program's rows (a chunked item
    expands to one row per chunk): (per-item results, final workspace)."""
    from repro.core import mailbox as mb
    from repro.kernels.persistent import (TILE_OP_NAMES,
                                          persistent_drain_ref, tile_state)
    rows, last = [], []
    for name, a0, a1, n_chunks in prog:
        for chunk in range(n_chunks):
            rows.append(mb.WorkDescriptor(
                opcode=TILE_OP_NAMES.index(name), arg0=a0, arg1=a1,
                request_id=len(rows) + 1, chunk=chunk, n_chunks=n_chunks))
        last.append(len(rows) - 1)
    ws0 = np.asarray(tile_state(nbuf=8, seed=seed)["ws"])[None]
    ws, _, _, results, _ = persistent_drain_ref(
        mb.queue_control(tail=len(rows))[None],
        mb.descriptor_ring(rows, len(rows))[None], ws0,
        np.zeros((1, 1), np.float32))
    return np.asarray(results)[0, last, 0], np.asarray(ws)[0], len(rows)


def run_tiles(runtime: str, devices: list, prog: list, seed: int) -> dict:
    """Boot ``LkSystem`` with one cluster per device under ``runtime``,
    run ``prog`` on every cluster (the chunked reduce alone, so each
    cluster executes the rows in program order), drain, and return each
    cluster's item results, final workspace and placement."""
    from repro.core import mailbox as mb
    from repro.core.mega import mega_work_classes
    from repro.core.system import LkSystem
    from repro.kernels.persistent import (TILE_OP_NAMES,
                                          TILE_RESULT_TEMPLATE, tile_state)
    sys_ = LkSystem(
        state_factory=lambda cl: tile_state(nbuf=8, seed=seed),
        result_template=TILE_RESULT_TEMPLATE, devices=devices,
        n_clusters=len(devices), runtime=runtime,
        work_classes=mega_work_classes(), max_steps=8, max_inflight=8)
    with sys_:
        dids = sys_.cluster_ids()
        tickets = {d: [] for d in dids}
        for name, a0, a1, n_chunks in prog:
            if n_chunks > 1:
                sys_.drain()
            for d in dids:
                tickets[d].append(sys_.dispatcher.submit(
                    mb.WorkDescriptor(opcode=TILE_OP_NAMES.index(name),
                                      arg0=a0, arg1=a1,
                                      request_id=len(tickets[d]) + 1,
                                      n_chunks=n_chunks),
                    cluster=d, admission=False))
            if n_chunks > 1:
                sys_.drain()
        sys_.drain()
        stats = sys_.stats()
        out = {"heals": sys_.heals, "generation": sys_.cm.generation,
               "stats": stats, "clusters": {}}
        for d in dids:
            rt = sys_.runtimes[d]
            ws = np.asarray(rt.state["ws"] if runtime == "scan"
                            else rt.state[0])
            placed = {dev for leaf in jax.tree.leaves(rt.state)
                      for dev in leaf.sharding.device_set}
            out["clusters"][d] = {
                "results": np.array([float(np.asarray(t.result())[0])
                                     for t in tickets[d]]),
                "ws": ws, "placed": placed, "device": rt.device,
                "interpreted": getattr(rt, "interpreted", None),
                "drained": getattr(rt, "work_drained", None)}
    return out


def check_system_clean(out: dict, what: str) -> None:
    s = out["stats"]
    check(out["heals"] == 0 and out["generation"] == 1,
          f"{what}: heal loop ran (heals {out['heals']}, "
          f"generation {out['generation']})")
    check(s["failed_clusters"] == 0 and s["replayed"] == 0
          and s["ack_mismatches"] == 0 and s["chunk_protocol_errors"] == 0,
          f"{what}: {s}")


def drain_phase(seed: int = 0) -> dict:
    """LkSystem(runtime="mega") on one cluster: a few dozen descriptors,
    a chunked reduce among them, against ``persistent_drain_ref``."""
    prog = tile_program(seed)
    want, ws_want, n_rows = tile_oracle(prog, seed)
    t0 = time.perf_counter()
    out = run_tiles("mega", jax.devices()[:1], prog, seed)
    seconds = time.perf_counter() - t0
    check_system_clean(out, "mega")
    (cl,) = out["clusters"].values()
    scale = 1.0 + float(np.abs(want).max())
    err = float(np.abs(cl["results"] - want).max()) / scale
    ws_err = float(np.abs(cl["ws"] - ws_want).max()) / \
        (1.0 + float(np.abs(ws_want).max()))
    check(err <= DRAIN_RTOL and ws_err <= DRAIN_RTOL,
          f"drain vs persistent_drain_ref: results {err:.3g}, "
          f"workspace {ws_err:.3g} (tolerance {DRAIN_RTOL})")
    check(cl["drained"] == n_rows,
          f"kernel drained {cl['drained']} rows, expected {n_rows}")
    return {"seconds": seconds, "items": len(prog), "rows": n_rows,
            "err": err, "ws_err": ws_err,
            "interpreted": cl["interpreted"], "heals": out["heals"]}


def clusters_phase(devices: list, seed: int = 0) -> dict:
    """Both runtimes: one cluster per device, each cluster's arrays on its
    own device, every cluster's results equal to a one-cluster run."""
    prog = tile_program(seed, n=12)
    report = {}
    for runtime in ("scan", "mega"):
        one = run_tiles(runtime, devices[:1], prog, seed)
        many = run_tiles(runtime, devices, prog, seed)
        check_system_clean(one, runtime)
        check_system_clean(many, runtime)
        (ref,) = one["clusters"].values()
        placed = []
        for d, cl in many["clusters"].items():
            check(cl["placed"] == {cl["device"]},
                  f"{runtime} cluster {d}: arrays on {cl['placed']}, "
                  f"runtime placed on {cl['device']}")
            placed.append(cl["device"])
            # same program on a chip of the same kind; the scan path may
            # batch a cluster's rows into different launches, hence the
            # float32-rounding tolerance instead of bit equality
            for key in ("results", "ws"):
                err = float(np.abs(cl[key] - ref[key]).max()) / \
                    (1.0 + float(np.abs(ref[key]).max()))
                check(err <= CLUSTER_RTOL,
                      f"{runtime} cluster {d} {key} differ from one "
                      f"cluster by {err:.3g}")
        check(set(placed) == set(devices),
              f"{runtime}: clusters on {placed}, not one per device")
        report[runtime] = [f"{d.platform}:{d.id}" for d in placed]
    return report


# ---------------------------------------------------------------------------
# sharded train step (four chips)
# ---------------------------------------------------------------------------

def train_phase(cfg=None, batch: int = TRAIN_BATCH,
                seq: int = TRAIN_SEQ) -> dict:
    """One train step of ``cfg`` (default: mamba2-780m widths at
    ``TRAIN_LAYERS`` layers) on one device, and sharded on the host's
    (data, model) mesh from ``launch.mesh.make_host_mesh`` as
    ``launch/train.py`` builds it; loss and updated parameters must
    agree."""
    from repro.configs import get_config
    from repro.distributed import ShardCtx
    from repro.launch.mesh import make_host_mesh
    from repro.models import build
    from repro.training import (init_state, make_train_step, opt_config_for,
                                state_shardings)
    if cfg is None:
        cfg = dataclasses.replace(get_config(ARCH), num_layers=TRAIN_LAYERS)
    tokens = jax.random.randint(jax.random.key(1), (batch, seq), 0,
                                cfg.vocab_size)
    ocfg = opt_config_for(cfg, lr=1e-3)
    m1 = build(cfg, ShardCtx.single())
    p1, s1 = init_state(m1, ocfg, jax.random.key(0))
    p1, _, met1 = jax.jit(make_train_step(m1, ocfg))(p1, s1,
                                                     {"tokens": tokens})
    mesh = make_host_mesh()
    ctx = ShardCtx.for_mesh(mesh, "train")
    m2 = build(cfg, ctx)
    p2, s2 = init_state(m2, ocfg, jax.random.key(0))
    psh, osh = state_shardings(m2, ocfg, ctx, p2, s2)
    p2, s2 = jax.device_put(p2, psh), jax.device_put(s2, osh)
    with mesh:
        p2, _, met2 = jax.jit(make_train_step(m2, ocfg))(
            p2, s2, {"tokens": tokens})
    l1, l2 = float(met1["loss"]), float(met2["loss"])
    perr = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    check(abs(l1 - l2) <= TRAIN_LOSS_RTOL * abs(l1),
          f"sharded loss {l2} vs one device {l1}")
    check(perr <= TRAIN_PARAM_ATOL,
          f"sharded parameters differ by {perr} from one device")
    return {"mesh": dict(mesh.shape), "layers": cfg.num_layers,
            "loss_1": l1, "loss_mesh": l2, "param_err": perr}


# ---------------------------------------------------------------------------

class CompileLog:
    """Seconds spent in backend compiles (a persistent-cache hit counts
    its retrieval instead) and the cache's hits and misses, from JAX's
    monitoring events."""

    def __init__(self):
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __str__(self):
        return (f"compile {self.seconds:.1f}s, cache hits {self.hits}, "
                f"misses {self.misses}")


def _peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (backend "
                 f"{jax.default_backend()!r}); nothing was run")
    devs = jax.devices()
    dev = devs[0]
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"chips, JAX sees {len(devs)}")
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"compile cache {cache}: {n_cached} entries at start")
    compiles = CompileLog()

    if args.chips == 4:
        t0 = time.perf_counter()
        placed = clusters_phase(devs[:4], seed=args.seed)
        log(f"clusters: 4 one-chip clusters, results equal one cluster; "
            f"scan on {placed['scan']}, mega on {placed['mega']} "
            f"({time.perf_counter() - t0:.1f}s, {compiles}; smoke reading)")
        t0 = time.perf_counter()
        tr = train_phase()
        log(f"train: {ARCH} widths, {tr['layers']} layers, batch "
            f"{TRAIN_BATCH}x{TRAIN_SEQ}, mesh {tr['mesh']}: loss "
            f"{tr['loss_mesh']:.6f} vs one chip {tr['loss_1']:.6f}, max "
            f"param diff {tr['param_err']:.3g} "
            f"({time.perf_counter() - t0:.1f}s; smoke reading)")
    else:
        for label, extra in (("plain", []),
                             ("streams", ["--streams", "--chunked-prefill"])):
            r = serve_phase(extra, seed=args.seed)
            log(f"serve {label}: {ARCH} full size, {r['completed']} "
                f"completions, {r['tokens']} tokens; request 0 "
                f"{r['matched']}/{r['steps']} greedy tokens equal the "
                f"plain decode loop, max logit gap {r['max_gap']:.3g} "
                f"(tolerance {LOGIT_TOL:.3g}); build+compile+serve "
                f"{r['seconds']:.1f}s, so far {compiles} (smoke reading)")
        r = drain_phase(seed=args.seed)
        check(r["interpreted"] is False, "drain kernel ran interpreted")
        log(f"drain: LkSystem(runtime='mega') compiled kernel, "
            f"{r['items']} items / {r['rows']} rows incl. a 4-chunk "
            f"reduce, heals {r['heals']}, vs persistent_drain_ref "
            f"results {r['err']:.3g} workspace {r['ws_err']:.3g} "
            f"(tolerance {DRAIN_RTOL}); {r['seconds']:.1f}s "
            f"(smoke reading)")
    log(f"peak device memory {_peak_bytes(dev)}; {compiles} "
        f"(smoke reading)")
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"compile cache {cache}: {n_cached} entries at end")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
