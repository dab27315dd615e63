"""Serving entrypoint: LightKernel persistent engine, batched requests,
WCET report (paper phases Init/Trigger/Wait/Dispose).

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m --reduced \
        --requests 12 --max-new 16
"""
from __future__ import annotations

import argparse
from dataclasses import dataclass

import jax
import numpy as np

from repro.configs import get_config
from repro.core import wcet
from repro.core.persistent import reap_deferred
from repro.core.telemetry import TraceCollector
from repro.core.wcet import WcetTracker
from repro.distributed import ShardCtx
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build
from repro.serving import ServingEngine


@dataclass
class ServeRun:
    """What one serve run leaves behind: the live engine (not yet
    drained or disposed), the model and weights it serves, the prompts
    it was given, and each request's generated tokens."""
    engine: ServingEngine
    model: object
    params: object
    prompts: list
    outs: list


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--completion-window", type=int, default=1024,
                    help="rolling completion/straggler window kept by the "
                         "dispatcher (stats stay exact beyond it)")
    ap.add_argument("--policy", choices=("edf", "fp", "server"),
                    default="edf",
                    help="scheduling policy: earliest-deadline-first, "
                         "fixed-priority, or per-class budgeted servers "
                         "(decode gets a HIGH-criticality 80%% server)")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="run prefill device-side as resumable chunks "
                         "through the dispatcher (queued work on a "
                         "shared dispatcher can cut in at every chunk "
                         "boundary; admission charges one chunk, not "
                         "one prompt)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prompt tokens per prefill chunk "
                         "(default: the prefill bucket size)")
    ap.add_argument("--max-steps", type=int, default=8,
                    help="descriptor-ring capacity of one batched "
                         "doorbell (trigger_many rows per device "
                         "transfer + compiled multi-step call)")
    ap.add_argument("--no-preempt", action="store_true",
                    help="disable chunk-boundary preemption (chunks of "
                         "one item run back to back — the pre-chunking "
                         "dispatch order)")
    ap.add_argument("--streams", action="store_true",
                    help="serve through the continuous-batching stream "
                         "frontend: each request is an admission-governed "
                         "stream (HIGH/LOW criticality), LOW streams shed "
                         "and re-admitted under overload, per-stream "
                         "TTFT/response quantiles reported")
    ap.add_argument("--high-every", type=int, default=4,
                    help="with --streams: every Nth stream is "
                         "HIGH-criticality (default 4)")
    ap.add_argument("--elastic", action="store_true",
                    help="attach the elastic partitioning controller in "
                         "ADVISORY mode: it observes the dispatcher's "
                         "per-class backlog off the telemetry stream, "
                         "admission-gates every proposed carve, and "
                         "rewrites class pin sets when an imbalance "
                         "sustains; the per-generation cluster-shares "
                         "table prints at exit")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="attach the telemetry collector and export a "
                         "Chrome/Perfetto trace JSON of the run to PATH "
                         "(also prints the per-opcode latency quantiles "
                         "and the runtime-verification ledger)")
    ap.add_argument("--metrics-file", default=None, metavar="PATH",
                    help="attach the continuous metrics registry and pump "
                         "one JSON-lines sample per interval to PATH (a "
                         "Prometheus-text sibling PATH.prom is rewritten "
                         "atomically each sample; tail either with "
                         "launch/top.py)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve /metrics (Prometheus text) and "
                         "/metrics.json from a background HTTP thread on "
                         "127.0.0.1:PORT (0 picks a free port)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI preset: forces --reduced and clamps request "
                         "counts so the serve loop (and its metrics "
                         "exposition) finishes in seconds")
    args = ap.parse_args(argv)
    if args.smoke:
        args.reduced = True
        args.requests = min(args.requests, 6)
        args.max_new = min(args.max_new, 4)
    return args


def main(argv=None):
    run = serve(parse_args(argv))
    # drain explicitly: a failure in the last steps raises here instead
    # of being retired silently by dispose()
    run.engine.dispatcher.drain()
    run.engine.dispose()
    reap_deferred()
    return run.outs


def serve(args: argparse.Namespace) -> ServeRun:
    """Build the engine for ``args``, answer every request and print the
    run's report; the engine is returned live."""
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build(cfg, ShardCtx.single(kind="decode"))
    params = model.init(jax.random.key(args.seed))

    tracker = WcetTracker("serve")
    # the elastic controller and the metrics registry both observe load
    # through the telemetry stream, so --elastic / --metrics-* attach a
    # collector even without --trace (which also turns the runtimes'
    # in-kernel flight recorder on — device-stamped chunk spans feed the
    # per-cluster utilization gauges)
    want_metrics = args.metrics_file is not None or \
        args.metrics_port is not None
    collector = TraceCollector() \
        if (args.trace or args.elastic or want_metrics) else None
    engine = ServingEngine(model, params, max_batch=args.max_batch,
                           max_seq=args.max_seq, tracker=tracker,
                           completion_window=args.completion_window,
                           policy=args.policy,
                           max_steps=args.max_steps,
                           chunked_prefill=args.chunked_prefill,
                           prefill_chunk_tokens=args.prefill_chunk,
                           telemetry=collector)
    if args.no_preempt:
        engine.dispatcher.policy.preemptive = False
    metrics = pump = None
    if want_metrics:
        from repro.core.telemetry import MetricsPump, MetricsRegistry
        metrics = MetricsRegistry(collector)
        pump = MetricsPump(metrics, path=args.metrics_file,
                           port=args.metrics_port, interval_s=0.25).start()
        if args.metrics_port is not None:
            print(f"[serve] metrics: http://127.0.0.1:{pump.port}/metrics")
    elastic = None
    if args.elastic:
        from repro.core.elastic import ElasticController
        from repro.serving.engine import OP_DECODE, OP_INSERT, OP_PREFILL
        classes = {"decode": OP_DECODE, "insert": OP_INSERT}
        if args.chunked_prefill:
            classes["prefill"] = OP_PREFILL
        if args.streams:
            from repro.serving.streams import OP_STREAM_HIGH, OP_STREAM_LOW
            classes["stream_high"] = OP_STREAM_HIGH
            classes["stream_low"] = OP_STREAM_LOW
        elastic = ElasticController().bind_dispatcher(
            engine.dispatcher, classes)
        if metrics is not None:
            # advisory: blend per-cluster device-measured utilization
            # into the backlog-demand signal driving recarve proposals
            elastic.bind_metrics(metrics)
        # advisory threading: ride the telemetry stream — every emitted
        # event gives the controller a (rate-limited) chance to evaluate,
        # so the serve loop needs no explicit tick plumbing
        collector.subscribe(lambda ev: elastic.maybe_tick())
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab_size, rng.integers(4, 24))
               for _ in range(args.requests)]
    extras = None
    if cfg.family == "encdec":
        extras = [{"frames": rng.normal(
            size=(cfg.encoder_frames, cfg.d_model)).astype(np.float32)}
            for _ in range(args.requests)]
    if cfg.family == "vlm":
        extras = [{"vision_embeds": rng.normal(
            size=(cfg.vision_tokens, cfg.d_model)).astype(np.float32)}
            for _ in range(args.requests)]

    if args.streams:
        if extras is not None:
            raise SystemExit("--streams does not support encdec/vlm "
                             "archs (prompt extras need the host "
                             "prefill path with per-request tensors)")
        from repro.core.sched import CRIT_HIGH, CRIT_LOW
        from repro.serving import StreamFrontend
        fe = StreamFrontend(engine, collector=collector)
        fe.open_stream(prompts[0], max_new_tokens=2)      # warm WCETs
        fe.serve()
        sids = []
        for i, p in enumerate(prompts):
            crit = CRIT_HIGH if args.high_every and \
                i % args.high_every == 0 else CRIT_LOW
            sids.append(fe.open_stream(p, max_new_tokens=args.max_new,
                                       criticality=crit))
            fe.poll()             # arrivals land on a loaded engine
        fe.serve()
        outs = [fe.result(s) for s in sids]
        print(f"[serve] streams: opened={fe.opened} shed={fe.shed_count} "
              f"readmitted={fe.readmitted} closed={fe.closed} "
              f"evictions={engine.slots.evictions}")
        for line in fe.collector.format_table("stream_ttft_us"):
            print(f"[serve] {line}")
        for line in fe.collector.format_table("stream_response_us"):
            print(f"[serve] {line}")
    else:
        outs = engine.generate(prompts, max_new_tokens=args.max_new,
                               extras=extras)
    for i, o in enumerate(outs[: min(4, len(outs))]):
        print(f"[serve] req{i}: {o}")
    print(f"[serve] completed {len(outs)} requests, "
          f"{sum(len(o) for o in outs)} tokens")
    for phase, s in tracker.time_phases().items():
        print(f"[serve] {phase:8s} avg={s.avg_ns/1e3:9.1f}us "
              f"worst={s.worst_ns/1e3:9.1f}us jitter={(s.worst_ns-s.avg_ns)/1e3:9.1f}us "
              f"n={s.count}")
    qd = tracker.stats.get(wcet.QUEUE_DEPTH)
    if qd is not None:
        print(f"[serve] queue_depth avg={qd.avg_ns:5.2f} "
              f"worst={qd.worst_ns:3.0f} n={qd.count}")
    ds = engine.dispatcher.deadline_stats()
    print(f"[serve] policy={ds.get('policy', '?')} shed={ds.get('shed', 0)} "
          f"chunks={ds.get('chunks', 0)} "
          f"preemptions={ds.get('preemptions', 0)}")
    print(f"[serve] dispatcher n={ds['n']} met={ds.get('met', 0)} "
          f"rejected={ds.get('rejected', 0)} "
          f"stragglers={ds.get('stragglers', 0)} "
          f"window={ds.get('window', 0)}/{engine.dispatcher.completion_window}")
    if elastic is not None:
        ec = elastic.counters()
        print(f"[serve] elastic: ticks={ec['ticks']} "
              f"applied={ec['applied']} rejected={ec['rejected']} "
              f"recarves={ds.get('recarves', 0)} "
              f"recarve_rejected={ds.get('recarve_rejected', 0)}")
        print("[serve] elastic shares by generation:")
        if elastic.share_history:
            for gen, shares in elastic.share_history:
                cells = " ".join(f"{k}={v}" for k, v in sorted(
                    shares.items()))
                print(f"[serve]   gen {gen:3d}: {cells}")
        else:
            print("[serve]   gen   1: static carve held "
                  "(no sustained imbalance)")
    if collector is not None and args.trace:
        for line in collector.format_table("response_us"):
            print(f"[serve] {line}")
        mc = collector.monitor.counts()
        print(f"[serve] runtime verification: checked={mc['checked']} "
              f"bound_violations={mc['bound_violations']} "
              f"deadline_misses={mc['deadline_misses']} "
              f"wcet_overruns={mc['wcet_overruns']}")
        n_ev = collector.export_chrome(args.trace)
        print(f"[serve] wrote {n_ev} trace events to {args.trace}")
    if pump is not None:
        pump.stop()               # final sample: short runs still export
        snap = metrics.snapshot()
        util = metrics.utilization()
        cells = " ".join(f"cluster{c}={u:.3f}"
                         for c, u in sorted(util.items()))
        chunks = sum(v for k, v in snap.items()
                     if k.startswith("cluster_chunks{"))
        print(f"[serve] metrics: samples={metrics.samples} "
              f"device_chunks={chunks:.0f} "
              f"utilization {cells if cells else '(no device spans)'}")
        if args.metrics_file:
            print(f"[serve] metrics written to {args.metrics_file} "
                  f"(+ .prom sibling)")
    return ServeRun(engine, model, params, prompts, outs)


if __name__ == "__main__":
    main()
