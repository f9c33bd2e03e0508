"""Structured observability: tracing, metrics, and trace reconciliation.

The paper's argument is about *where bytes move* — per-tile caches vs the
mesh networks — yet until this package the runtime could only show that
after the fact, as benchmark diffs.  `repro.obs` makes the traffic visible
live and, in the PR 6-9 spirit, *checked*:

``repro.obs.tracelog``
    Zero-dependency structured tracing: `Span`/`Event`/`Counter`/`Gauge`
    primitives, a thread-safe in-memory `Tracer` with a streaming JSONL
    sink and Chrome trace-event (``chrome://tracing`` / Perfetto) export,
    nested span context managers, and a `NullTracer` whose no-op methods
    make instrumented hot paths free when tracing is off (the default).

``repro.obs.metrics``
    The per-home metrics registry: queue depths, bound sessions, KV-pool
    pages/refs/hit-rate, relayout + inter/intra-pod bytes, wave
    utilisation and per-wave wait histograms, snapshotted at wave
    boundaries — and the ONE rendering path (`summarise` ->
    `format_summary` / JSON) every consumer shares: ``launch/serve``
    exit summaries and the trace's final ``sched.summary`` event are
    the same dict.

``repro.obs.reconcile``
    The offline trace validator: replays a trace and *proves* the counter
    identities (charged relayout bytes == scheduler stats == summary;
    pool acquires − releases − invalidations == live refs; every
    off-home placement has a matching charge; the engine's stamped
    per-level bytes == a fresh `exchange_schedule`).  A regression in the
    observability layer itself shows up as a trace-identity failure, not
    a slower benchmark row.  CLI: ``repro.launch.tracelog --validate``.

Import note: this package must stay import-light (the runtime hot paths
import it), so ``reconcile`` — which pulls in `repro.core.engine` — is a
submodule import, never re-exported here.
"""
from repro.obs.tracelog import (NULL_TRACER, Counter, Event, Gauge,
                                NullTracer, Span, Tracer, get_tracer,
                                read_jsonl, set_tracer, to_chrome)

__all__ = ["Tracer", "NullTracer", "Span", "Event", "Counter", "Gauge",
           "NULL_TRACER", "get_tracer", "set_tracer", "read_jsonl",
           "to_chrome"]
