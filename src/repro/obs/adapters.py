"""Thin adapters: legacy stats objects -> canonical registry metrics.

The engines keep their existing dataclasses (``MaterialisationStats``,
``DistributedStats``, ``IncrementalStats``, the query-engine cache
counters) — those are the per-call return values tests and benchmarks
already consume.  What changes is that every completed
materialise/apply *also* publishes its numbers here, under one
canonical dotted name per metric, so any consumer can take one
registry snapshot instead of chasing four stats shapes.

Counters are **incremented** by the published value (a registry scope
accumulates across batches/runs until its owner resets it); levels
(fact counts, epochs, byte sizes) are gauges and overwrite.  Field
names are preserved under the prefix — ``cmat.rounds`` is literally
``MaterialisationStats.rounds`` — so the adapter-parity test can diff
the snapshot against the dataclass mechanically.
"""

from __future__ import annotations

from .metrics import MetricsRegistry, get_registry

__all__ = [
    "publish_materialisation",
    "publish_incremental",
    "publish_distributed",
    "publish_query_cache",
    "publish_serving",
    "MATERIALISATION_COUNTERS",
    "MATERIALISATION_GAUGES",
    "INCREMENTAL_COUNTERS",
    "DISTRIBUTED_COUNTERS",
    "DISTRIBUTED_GAUGES",
    "SERVING_GAUGES",
]

#: ServingTier.stats() keys mirrored as gauges (lifetime-cumulative on
#: the tier, so re-publishing is idempotent — same convention as
#: :func:`publish_query_cache`)
SERVING_GAUGES = (
    "queries",
    "batches",
    "mean_batch",
    "max_batch",
    "grouped_queries",
    "single_queries",
    "cache_hits",
    "dedup_hits",
    "groups",
    "stale_reads",
    "applies",
    "checkpoints",
    "compactions",
    "compactions_deferred",
    "max_queue_depth",
    "epoch_lag_max",
    "epochs_published",
    "epochs_retired",
    "epochs_live",
    "epochs_pinned",
    "epoch",
)

#: MaterialisationStats fields that accumulate (counter semantics)
MATERIALISATION_COUNTERS = (
    "rounds",
    "n_rule_applications",
    "rule_applications_skipped",
    "old_snapshot_scans",
    "time_compress",
    "time_match",
    "time_join",
    "time_dedup",
    "time_total",
)

#: MaterialisationStats fields that are levels (gauge semantics)
MATERIALISATION_GAUGES = ("n_strata", "n_meta_facts", "n_facts")

#: IncrementalStats extras (per-batch deltas -> counters)
INCREMENTAL_COUNTERS = (
    "n_del_explicit",
    "n_add_explicit",
    "n_overdeleted",
    "n_rederived",
    "n_deleted",
    "n_inserted",
    "n_count_updates",
    "counting_strata",
    "dred_strata",
    "time_overdelete",
    "time_delete",
    "time_rederive",
    "time_counting",
    "time_insert",
)

#: DistributedStats fields the engine sets that accumulate: the
#: materialisation base fields it fills, and the exchange, maintenance
#: and device->host read counters the host engines have no analogue for
DISTRIBUTED_COUNTERS = (
    "rounds",
    "n_rule_applications",
    "rule_applications_skipped",
    "time_total",
    "rows_joined",
    "exchanges",
    "exchanges_skipped",
    "exchange_regrows",
    "n_del_explicit",
    "n_add_explicit",
    "n_overdeleted",
    "n_rederived",
    "n_deleted",
    "n_inserted",
    "host_syncs",
)

#: DistributedStats levels the engine sets (``n_facts``/``n_meta_facts``
#: and the per-phase times of the host engines are never set there);
#: ``key_words`` is set once per materialise and per apply
DISTRIBUTED_GAUGES = ("n_strata", "epoch", "key_words")


def _publish_rule_scope(reg: MetricsRegistry, stats) -> None:
    """Mirror the per-stratum breakdown and the host (rule, pivot) skip
    counter under the ``rule.*`` scope (shared with the provenance
    journal's per-rule cost gauges, so one snapshot prefix answers
    "where did rule work go").  Per-stratum entries are levels of the
    *last* run — gauges, republish-idempotent."""
    for s in getattr(stats, "per_stratum", ()) or ():
        si = s.get("stratum", 0)
        for f in ("rounds", "rules", "rule_applications"):
            if f in s:
                reg.gauge(f"rule.stratum{si}.{f}").set(s[f])
    reg.counter("rule.applications_skipped").inc(
        getattr(stats, "rule_applications_skipped", 0)
    )


def _publish_plan_cache(
    reg: MetricsRegistry, prefix: str, plan_cache: dict
) -> None:
    # plan-cache counters are cumulative on the cache object; gauges
    # keep 'last seen' semantics so repeated publishes don't double
    for key, val in (plan_cache or {}).items():
        reg.gauge(f"{prefix}.plan_cache.{key}").set(val)


def publish_materialisation(
    stats, registry: MetricsRegistry | None = None, prefix: str = "cmat"
) -> None:
    """Publish a :class:`~repro.core.engine.MaterialisationStats` (the
    CMat/Flat engines call this at the end of ``materialise``)."""
    reg = registry if registry is not None else get_registry()
    for f in MATERIALISATION_COUNTERS:
        reg.counter(f"{prefix}.{f}").inc(getattr(stats, f))
    for f in MATERIALISATION_GAUGES:
        reg.gauge(f"{prefix}.{f}").set(getattr(stats, f))
    _publish_rule_scope(reg, stats)
    _publish_plan_cache(reg, prefix, stats.plan_cache)


def publish_incremental(
    stats, registry: MetricsRegistry | None = None, prefix: str = "inc"
) -> None:
    """Publish an :class:`~repro.incremental.IncrementalStats` (the
    host store calls this after every ``apply`` batch)."""
    reg = registry if registry is not None else get_registry()
    reg.counter(f"{prefix}.batches").inc()
    for f in INCREMENTAL_COUNTERS + ("n_rule_applications", "time_total"):
        reg.counter(f"{prefix}.{f}").inc(getattr(stats, f))
    reg.gauge(f"{prefix}.epoch").set(stats.epoch)
    reg.gauge(f"{prefix}.n_facts").set(stats.n_facts)
    reg.gauge(f"{prefix}.n_meta_facts").set(stats.n_meta_facts)
    reg.gauge(f"{prefix}.journal_bytes").set(stats.journal_bytes)
    reg.histogram(f"{prefix}.apply_s").observe(stats.time_total)
    _publish_plan_cache(reg, prefix, stats.plan_cache)


def publish_distributed(
    stats, registry: MetricsRegistry | None = None, prefix: str = "dist"
) -> None:
    """Publish a :class:`~repro.core.distributed.DistributedStats`
    (after ``materialise`` and after every ``apply``)."""
    reg = registry if registry is not None else get_registry()
    for f in DISTRIBUTED_COUNTERS:
        reg.counter(f"{prefix}.{f}").inc(getattr(stats, f))
    for f in DISTRIBUTED_GAUGES:
        reg.gauge(f"{prefix}.{f}").set(getattr(stats, f))
    _publish_rule_scope(reg, stats)
    _publish_plan_cache(reg, prefix, stats.plan_cache)


def publish_serving(
    tier, registry: MetricsRegistry | None = None, prefix: str = "serve.tier"
) -> None:
    """Publish a :class:`~repro.serving.ServingTier`'s lifetime stats
    under ``serve.tier.*`` gauges.  The tier's live counters/histograms
    (batch sizes, admission latency, epoch lag) already stream into the
    registry under ``serve.*`` — the roll-up takes its own sub-scope so
    gauge names never collide with those counters."""
    reg = registry if registry is not None else get_registry()
    stats = tier.stats()
    for key in SERVING_GAUGES:
        if key in stats:
            reg.gauge(f"{prefix}.{key}").set(stats[key])


def publish_query_cache(
    engine, registry: MetricsRegistry | None = None, prefix: str = "query"
) -> None:
    """Publish a :class:`~repro.query.QueryEngine`'s cache counters.
    The engine's counts are lifetime-cumulative, so these are gauges —
    re-publishing is idempotent."""
    reg = registry if registry is not None else get_registry()
    for key, val in engine.cache_stats().items():
        reg.gauge(f"{prefix}.{key}").set(val)
    reg.gauge(f"{prefix}.epoch").set(engine.epoch)
