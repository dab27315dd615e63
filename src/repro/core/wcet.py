"""Worst-case execution time accounting (paper Tables II/III).

Phases mirror the paper: LK Init / Trigger / Wait / Dispose (and the
traditional-path Alloc / Spawn / Wait / Dispose). We record wall-clock ns per
phase and report average, worst, variance — the paper's predictability metric
is exactly the avg↔worst gap.

While a ``jax.profiler`` trace is active, each phase is also a span in it
(``TraceAnnotation``), on the trace's own clock and carrying the phase's
arguments as metadata, so the host side of the serve path lines up with the
device operations it launched. With no trace active the span is skipped.
"""
from __future__ import annotations

import math
import time
from collections import defaultdict
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Iterator, Optional

from jax.profiler import TraceAnnotation

PHASES = ("init", "trigger", "wait", "dispose")

# Dimensionless companion series: in-flight pipeline depth sampled at each
# trigger. avg > 1 means host/device overlap actually happened; worst is the
# deepest the pipeline ever got. Not a time phase — report it separately.
QUEUE_DEPTH = "queue_depth"


@dataclass
class PhaseStats:
    count: int = 0
    total_ns: float = 0.0
    total_sq: float = 0.0
    worst_ns: float = 0.0
    best_ns: float = math.inf

    def record(self, ns: float) -> None:
        self.count += 1
        self.total_ns += ns
        self.total_sq += ns * ns
        self.worst_ns = max(self.worst_ns, ns)
        self.best_ns = min(self.best_ns, ns)

    @property
    def avg_ns(self) -> float:
        return self.total_ns / self.count if self.count else 0.0

    @property
    def var_ns2(self) -> float:
        if self.count < 2:
            return 0.0
        m = self.avg_ns
        return max(self.total_sq / self.count - m * m, 0.0)

    @property
    def std_ns(self) -> float:
        return math.sqrt(self.var_ns2)

    def as_dict(self) -> dict:
        return {"count": self.count, "avg_ns": self.avg_ns,
                "worst_ns": self.worst_ns,
                "best_ns": self.best_ns if self.count else 0.0,
                "std_ns": self.std_ns}


def _span_value(v):
    """A span argument as the profiler stores it: numbers and strings as
    they are, a sequence as its items joined by spaces (the trace's
    metadata encoding reserves commas)."""
    if isinstance(v, (list, tuple)):
        return " ".join(str(x) for x in v)
    return v


class WcetTracker:
    """Per-phase timing aggregator with a context-manager interface."""

    def __init__(self, name: str = ""):
        self.name = name
        self.stats: dict[str, PhaseStats] = defaultdict(PhaseStats)

    @contextmanager
    def phase(self, name: str, span: Optional[str] = None,
              **args) -> Iterator[Optional[dict]]:
        """Time the enclosed block as phase ``name``. Inside a profiler
        trace the block is also a span named ``span`` (default ``name``)
        with ``args`` as its metadata; the context then yields a dict
        whose entries join those args at the span's end (what is known
        only afterwards). With no trace active it yields None, so callers
        build costly arguments only when someone is tracing."""
        t0 = time.perf_counter_ns()
        ann = late = None
        if TraceAnnotation.is_enabled():
            ann = TraceAnnotation(span or name,
                                  **{k: _span_value(v)
                                     for k, v in args.items()})
            ann.__enter__()
            late = {}
        try:
            yield late
        finally:
            if ann is not None:
                if late:
                    ann.set_metadata(**{k: _span_value(v)
                                        for k, v in late.items()})
                ann.__exit__(None, None, None)
            self.stats[name].record(time.perf_counter_ns() - t0)

    def record(self, name: str, ns: float) -> None:
        self.stats[name].record(ns)

    def record_depth(self, depth: int) -> None:
        """Sample the in-flight queue depth (see ``QUEUE_DEPTH``)."""
        self.stats[QUEUE_DEPTH].record(float(depth))

    def time_phases(self) -> dict[str, PhaseStats]:
        """Stats minus dimensionless series — safe to print as ns."""
        return {k: v for k, v in self.stats.items() if k != QUEUE_DEPTH}

    def avg(self, name: str) -> float:
        return self.stats[name].avg_ns

    def worst(self, name: str) -> float:
        return self.stats[name].worst_ns

    def jitter(self, name: str) -> float:
        """worst − avg: the paper's predictability gap."""
        s = self.stats[name]
        return s.worst_ns - s.avg_ns

    def report(self) -> dict:
        return {k: v.as_dict() for k, v in self.stats.items()}

    def csv_rows(self) -> list[str]:
        rows = []
        for k in sorted(self.stats):
            s = self.stats[k]
            rows.append(f"{self.name},{k},{s.count},{s.avg_ns:.0f},"
                        f"{s.worst_ns:.0f},{s.std_ns:.0f}")
        return rows
