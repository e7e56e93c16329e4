"""Flight recorder for the edge pipeline.

One ``Observability`` bundle per serving process: a ``MetricsRegistry``
(counters / gauges / log-bucketed histograms with p50/p99/p999) plus a
``SpanTracer`` (bounded ring of Chrome trace events).  The stream,
transport, and fleet layers all record into the same bundle, so one
``/metrics`` scrape or ``/trace`` download covers the whole pipeline.
``current()`` hands that bundle to in-process tools (span arguments
included), without a handle on the server that owns it.

The recorder is hot-path safe by construction -- recording is host-side
integer arithmetic, never a device sync -- and cheap enough to be on by
default (`benchmarks/check_bench.py` gates the instrumented-vs-disabled
resident-tick overhead at <= 5%).  Pass ``obs=False`` to a server to get
shared null instruments with zero recording cost.
"""

from __future__ import annotations

import weakref
from typing import Dict, Optional, Union

from repro.obs.metrics import (
    NULL_INSTRUMENT,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NullInstrument,
    bucket_bounds,
    bucket_index,
)
from repro.obs.tracing import SpanTracer, annotate

__all__ = [
    "Observability",
    "as_obs",
    "current",
    "MetricsRegistry",
    "SpanTracer",
    "Counter",
    "Gauge",
    "Histogram",
    "NullInstrument",
    "NULL_INSTRUMENT",
    "bucket_index",
    "bucket_bounds",
    "annotate",
]


class Observability:
    """Metrics registry + span tracer, enabled or fully inert as a unit."""

    def __init__(self, enabled: bool = True, trace_capacity: int = 4096,
                 jax_annotate: bool = False):
        self.enabled = bool(enabled)
        self.metrics = MetricsRegistry(enabled=self.enabled)
        self.tracer = SpanTracer(capacity=trace_capacity, enabled=self.enabled)
        # opt-in: also wrap device dispatch in jax profiler annotations so
        # spans land inside XLA device profiles (routed via jax_compat)
        self.jax_annotate = bool(jax_annotate) and self.enabled
        if self.enabled:
            global _CURRENT
            _CURRENT = weakref.ref(self)

    def snapshot(self) -> Dict[str, object]:
        """JSON-able state for merging into server/fleet reports."""
        snap = self.metrics.snapshot()
        snap["spans_recorded"] = float(self.tracer.recorded)
        snap["spans_dropped"] = float(self.tracer.dropped)
        return snap


_DISABLED: Optional[Observability] = None
_CURRENT: Optional["weakref.ref[Observability]"] = None


def current() -> Optional[Observability]:
    """The most recently built enabled bundle still alive, else ``None``."""
    return _CURRENT() if _CURRENT is not None else None


def disabled() -> Observability:
    """The shared inert bundle (no per-call state, safe to share)."""
    global _DISABLED
    if _DISABLED is None:
        _DISABLED = Observability(enabled=False)
    return _DISABLED


def as_obs(obs: Union[None, bool, Observability]) -> Observability:
    """Normalize a server's ``obs=`` argument.

    ``None`` / ``True`` -> a fresh enabled bundle (per-server registry, so
    two servers never collide on callback metrics); ``False`` -> the shared
    disabled bundle; an ``Observability`` instance passes through.
    """
    if isinstance(obs, Observability):
        return obs
    if obs is False:
        return disabled()
    return Observability()
