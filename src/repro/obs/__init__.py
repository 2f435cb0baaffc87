"""Unified observability: spans, a metrics registry, exporters.

The one telemetry layer every subsystem reports through
(DESIGN.md §Observability):

* :func:`span` / :func:`instant` — nested host-side tracing spans
  (``perf_counter_ns``; free when disabled), and TraceMe events in the
  ``jax.profiler`` trace whenever a profiler session is active.  Emitted for fixpoint
  rounds, strata, (rule, pivot) applications, exchange rounds, DRed
  phases, WAL appends, checkpoints/restores, compaction epochs, and
  served queries/apply batches.
* :func:`get_registry` — named counters/gauges/histograms with one
  canonical name per number, one snapshot call, one (per-scope) reset.
  The legacy stats dataclasses publish into it via
  :mod:`repro.obs.adapters`.
* :func:`write_chrome_trace` / :func:`write_metrics` — Chrome
  trace-event / Perfetto JSON and a flat metrics snapshot, wired into
  ``serve_datalog --trace-out/--metrics-out`` and
  ``benchmarks/run.py --json``.

Spans must never fire inside traced/jitted code — instrument at host
boundaries, where the engines already count rounds.
"""

from .adapters import (
    publish_distributed,
    publish_incremental,
    publish_materialisation,
    publish_query_cache,
    publish_serving,
)
from .export import chrome_trace, write_chrome_trace, write_metrics
from .memory import (
    MemoryAccountant,
    MemoryReporter,
    MemorySampler,
    get_accountant,
    publish_predicate_effectiveness,
    register_reporter,
    rss_bytes,
    sample_memory,
    set_accountant,
)
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    set_registry,
)
from .provenance import (
    DerivationJournal,
    DerivationRecord,
    Explainer,
    get_journal,
    proof_to_dot,
    proof_to_json,
)
from .trace import Tracer, get_tracer, instant, set_tracer, span

__all__ = [
    "Tracer",
    "get_tracer",
    "set_tracer",
    "span",
    "instant",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "get_registry",
    "set_registry",
    "MemoryAccountant",
    "MemoryReporter",
    "MemorySampler",
    "get_accountant",
    "set_accountant",
    "register_reporter",
    "sample_memory",
    "rss_bytes",
    "publish_predicate_effectiveness",
    "chrome_trace",
    "write_chrome_trace",
    "write_metrics",
    "publish_materialisation",
    "publish_incremental",
    "publish_distributed",
    "publish_query_cache",
    "publish_serving",
    "DerivationJournal",
    "DerivationRecord",
    "Explainer",
    "get_journal",
    "proof_to_json",
    "proof_to_dot",
]
