"""Batched datalog query serving: materialise once, answer a query stream.

    PYTHONPATH=src python -m repro.launch.serve_datalog --kb lubm \
        --n-queries 2000 --zipf 1.1

The request path the paper's preprocessing framing implies: load a KB,
run the compressed materialisation once, freeze the store, then serve a
stream of templated BGP queries through :class:`repro.query.QueryEngine`
(LRU plan + result caches, scratch-region reclamation per miss) and
report p50/p99 latency, throughput, cache hit rate, and the compressed
answering evidence (flat rows scanned vs stored rows per predicate).

Query streams are drawn from per-KB templates with Zipf-distributed
constants — a serving-realistic skew where popular entities repeat and
the result cache pays off.  ``--no-result-cache`` measures pure
evaluation throughput instead.

``--live`` turns the driver into an *update-serving* loop: the KB is
held in an :class:`repro.incremental.IncrementalStore`, and every
``--update-every`` queries a batch of ``--update-size`` explicit facts
is deleted (and the batch deleted one update earlier re-inserted, so the
KB churns without draining).  Each applied batch bumps the query
engine's epoch, invalidating the version-stamped plan/result caches;
the report adds apply-latency percentiles, per-epoch stale evictions,
and — with ``--live-verify`` — a final differential check against a
from-scratch materialisation of the ending fact set.

``--distributed`` runs the sharded engine alongside the host store: the
KB is hash-partitioned over every visible device, materialised with the
semi-naive delta exchange, and — under ``--live`` — every update batch
is *also* routed through ``DistributedEngine.apply`` (overdelete /
rederive / insert deltas through ``all_to_all``), with a final
differential ``check_integrity`` against the host
:class:`~repro.incremental.IncrementalStore` serving the queries.

``--checkpoint-dir`` makes the store durable (DESIGN.md §Storage):
update batches are write-ahead logged, a snapshot is checkpointed every
``--checkpoint-every`` batches, and ``--restore`` warm-starts from the
latest snapshot + WAL replay instead of re-materialising (recovery
timing lands in the report).  In live mode ``--compact-threshold``
triggers a GC/compaction epoch whenever deletion churn strands more
than that fraction of mu-nodes.  Without ``--live``, the checkpoint dir
holds a single frozen snapshot of the static materialisation and
``--restore`` serves straight from it.
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time

import numpy as np

from ..core import CMatEngine
from ..core.generators import chain, lubm_like, paper_example, star
from ..incremental import IncrementalStore
from ..obs import (
    get_registry,
    get_tracer,
    publish_predicate_effectiveness,
    publish_query_cache,
    publish_serving,
    sample_memory,
    span,
    write_chrome_trace,
    write_metrics,
)
from ..query import QueryEngine
from ..storage import CheckpointManager, load_frozen, write_snapshot


class ReportSink:
    """Report sink: every block prints its legacy ``[tag] ...`` line and
    (with ``--report-json``) appends one JSON object per block —
    ``{"block": tag, ...data}`` — so drivers can scrape structure
    instead of parsing the text.

    Thread-safe: concurrent serving emits from client/executor threads,
    so the print and the JSON append happen under one lock (interleaved
    ``[tag]`` lines and torn JSON records otherwise).  Each record is
    serialised *outside* the lock and written with a single ``write``."""

    def __init__(self, json_path: str | None = None):
        self._fh = open(json_path, "w") if json_path else None
        self._lock = threading.Lock()

    def emit(self, block: str, text: str, data: dict | None = None) -> None:
        line = f"[{block}] {text}"
        rec = None
        if self._fh is not None:
            payload = {"block": block}
            payload.update(data or {})
            rec = json.dumps(payload, default=float, sort_keys=True) + "\n"
        with self._lock:
            print(line)
            if rec is not None and self._fh is not None:
                self._fh.write(rec)
                self._fh.flush()

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None


#: historical name, kept for callers that imported the class directly
Report = ReportSink


def build_kb(name: str, scale: int):
    if name == "lubm":
        return lubm_like(
            n_dept=4 * scale, n_students=100 * scale, n_courses=8 * scale, seed=0
        )
    if name == "chain":
        return chain(n=60 * scale)
    if name == "star":
        return star(n_spokes=400 * scale, n_hubs=3)
    if name == "paper":
        return paper_example(n=4 * scale, m=3 * scale)
    raise ValueError(f"unknown KB {name!r} (use lubm|chain|star|paper)")


def query_templates(name: str, scale: int):
    """(template, constant-pool) pairs; ``{c}`` is filled per request."""
    if name == "lubm":
        return [
            ('?s, ?c <- memberOf(?s, "{c}"), takesCourse(?s, ?c)',
             [f"dept{i}" for i in range(4 * scale)]),
            ('?s <- takesCourse(?s, "{c}"), GraduateStudent(?s)',
             [f"course{i}" for i in range(8 * scale)]),
            ('?s, ?p, ?c <- advisor(?s, ?p), teacherOf(?p, ?c), takesCourse(?s, ?c)',
             None),
            ('?x, ?u <- memberOf(?x, ?dv), subOrganizationOf(?dv, ?u)', None),
            ('?p <- teacherOf(?p, "{c}")', [f"course{i}" for i in range(8 * scale)]),
        ]
    if name == "chain":
        n = 60 * scale
        return [
            ('?y <- path("{c}", ?y)', [f"v{i:06d}" for i in range(n)]),
            ('?x <- path(?x, "{c}")', [f"v{i:06d}" for i in range(1, n + 1)]),
            ('?x, ?z <- edge(?x, ?y), edge(?y, ?z)', None),
        ]
    if name == "star":
        return [
            ('?y <- S("{c}", ?y)', [f"s{i:06d}" for i in range(0, 400 * scale, 2)]),
            ('?x, ?z <- S(?x, ?y), T(?y, ?z)', None),
        ]
    if name == "paper":
        return [
            ("?x, ?y <- S(?x, ?y)", None),
            ('?x, ?z <- P(?x, ?y), T(?y, ?z)', None),
            ('?y <- P("a2", ?y)', None),
        ]
    raise ValueError(name)


def make_stream(name: str, scale: int, n_queries: int, zipf: float, seed: int):
    rng = np.random.default_rng(seed)
    templates = query_templates(name, scale)
    out = []
    for _ in range(n_queries):
        template, pool = templates[int(rng.integers(0, len(templates)))]
        if pool is None:
            out.append(template)
            continue
        # Zipf-ish skew over the pool: popular constants dominate.
        # Fold the tail back with a modulo — clamping would pile every
        # out-of-range draw onto one element and degenerate the skew.
        rank = int(rng.zipf(zipf)) - 1 if zipf > 1.0 else int(
            rng.integers(0, len(pool))
        )
        out.append(template.format(c=pool[rank % len(pool)]))
    return out


def _rows_by_pred(items):
    out: dict[str, list] = {}
    for pred, row in items:
        out.setdefault(pred, []).append(row)
    return {p: np.asarray(r, dtype=np.int64) for p, r in out.items()}


def _parse_fact_spec(spec: str, dictionary):
    """``pred(t1, t2)`` -> ``(pred, (id1, id2))``; terms resolve through
    the KB dictionary, falling back to raw integer ids."""
    spec = spec.strip()
    if "(" not in spec or not spec.endswith(")"):
        raise ValueError(
            f"bad --explain spec {spec!r}; expected pred(term, term)"
        )
    pred, rest = spec.split("(", 1)
    terms = []
    for tok in rest[:-1].split(","):
        tok = tok.strip().strip("'\"")
        if dictionary is not None and tok in dictionary:
            terms.append(dictionary.id_of(tok))
        else:
            terms.append(int(tok))
    return pred.strip(), tuple(terms)


def _proof_summary(node: dict) -> dict:
    depth, n_nodes, all_verified = 0, 0, True
    stack = [(node, 1)]
    while stack:
        nd, d = stack.pop()
        n_nodes += 1
        depth = max(depth, d)
        all_verified = all_verified and bool(nd.get("verified"))
        for child in nd.get("children", ()):
            stack.append((child, d + 1))
    return {"depth": depth, "nodes": n_nodes, "verified": all_verified}


def _sample_derived(mat, explicit, n: int, seed: int):
    """Up to ``n`` (pred, terms) pairs drawn from the materialisation
    minus the explicit set — the facts a proof tree is non-trivial for."""
    from ..core.util import multicol_member

    pool = []
    for pred in sorted(mat):
        rows = np.asarray(mat[pred], dtype=np.int64)
        rows = rows.reshape(rows.shape[0], -1)
        exp = np.asarray(explicit.get(pred, np.zeros((0, 0))), dtype=np.int64)
        if exp.shape[0]:
            exp = exp.reshape(exp.shape[0], -1)
            if exp.shape[1] == rows.shape[1]:
                rows = rows[~multicol_member(rows, exp)]
        pool.extend((pred, tuple(int(v) for v in row)) for row in rows)
    if not pool:
        return []
    rng = np.random.default_rng(seed)
    idx = rng.choice(len(pool), size=min(n, len(pool)), replace=False)
    return [pool[int(i)] for i in idx]


def make_update_batches(dataset, n_updates: int, size: int, seed: int):
    """Rotating explicit-fact update batches: each batch deletes ``size``
    facts from a shuffled pool and re-inserts the batch deleted one
    update earlier (the KB churns but never drains)."""
    rng = np.random.default_rng(seed + 1)
    pool = [
        (pred, tuple(int(v) for v in row))
        for pred, rows in dataset.items()
        for row in np.asarray(rows).reshape(len(rows), -1)
    ]
    rng.shuffle(pool)
    batches = []
    prev: list = []
    off = 0
    for _ in range(n_updates):
        cur = [pool[(off + j) % len(pool)] for j in range(size)]
        off += size
        # (deletions, additions)
        batches.append((_rows_by_pred(cur), _rows_by_pred(prev)))
        prev = cur
    return batches


def _serve_mvcc(args, report, inc, dictionary, stream, batches, ckpt,
                flush_telemetry, update_at):
    """Concurrent MVCC serving loop: ``--concurrency`` closed-loop
    client threads answer through the :class:`~repro.serving.ServingTier`
    (micro-batched admission over pinned epochs) while update batches
    flow through the tier's single writer thread every ``update_at``
    served queries."""
    from ..serving import ServingTier

    tier = ServingTier(
        inc,
        dictionary,
        result_cache_size=0 if args.no_result_cache else 1024,
        use_pallas=args.pallas,
        checkpoint=ckpt if args.live else None,
        checkpoint_every=args.checkpoint_every if args.live else 0,
        compact_threshold=args.compact_threshold if args.live else 0.0,
    )
    n_clients = max(args.concurrency, 1)
    lat_lock = threading.Lock()
    latencies: list[float] = []
    totals = {"answers": 0, "stale": 0, "served": 0}
    apply_lat: list[float] = []
    try:
        # warmup off the measured path: snapshots, plans, caches
        with span("serve.warmup"):
            for text in dict.fromkeys(stream[: min(50, len(stream))]):
                tier.answer(text)
        tier.reset_counters()
        tier.start()

        shards = [stream[i::n_clients] for i in range(n_clients)]

        def client(shard):
            local_lat = []
            answers = stale = 0
            for text in shard:
                t0 = time.perf_counter()
                resp = tier.answer(text)
                local_lat.append(time.perf_counter() - t0)
                answers += resp.n_answers
                stale += int(resp.stale)
                with lat_lock:
                    totals["served"] += 1
            with lat_lock:
                latencies.extend(local_lat)
                totals["answers"] += answers
                totals["stale"] += stale

        threads = [
            threading.Thread(target=client, args=(s,), daemon=True)
            for s in shards
            if s
        ]
        t_serve0 = time.perf_counter()
        for th in threads:
            th.start()
        # the main thread feeds the writer: one update batch per
        # `update_at` served queries, applied through tier.apply (the
        # single writer thread) and published as a fresh epoch
        next_batch = 0
        while any(th.is_alive() for th in threads):
            if (
                args.live
                and next_batch < len(batches)
                and totals["served"] >= (next_batch + 1) * update_at
            ):
                deletions, additions = batches[next_batch]
                next_batch += 1
                t0 = time.perf_counter()
                tier.apply_sync(additions=additions, deletions=deletions)
                apply_lat.append(time.perf_counter() - t0)
                sample_memory(phase="serve_batch", rss=False)
                flush_telemetry()
            else:
                time.sleep(0.001)
        for th in threads:
            th.join()
        t_serve = time.perf_counter() - t_serve0
    finally:
        tier.close()
    if args.live and ckpt is not None:
        ckpt.checkpoint(inc)  # final durable state via the LATEST pointer

    reg = get_registry()
    lat_arr = np.asarray(latencies) if latencies else np.zeros(1)
    lat_ms = lat_arr * 1e3
    lat_hist = reg.histogram("serve.query_s")
    for v in latencies:
        lat_hist.observe(float(v))
    publish_serving(tier)
    st = tier.stats()
    qps = len(latencies) / max(t_serve, 1e-9)
    report.emit(
        "serve",
        f"{len(latencies)} queries in {t_serve:.2f}s ({qps:.0f} q/s), "
        f"{totals['answers']} answers total",
        {"queries": len(latencies), "seconds": t_serve, "qps": qps,
         "answers": totals["answers"]},
    )
    report.emit(
        "latency",
        f"p50={np.percentile(lat_ms, 50):.3f}ms "
        f"p90={np.percentile(lat_ms, 90):.3f}ms "
        f"p99={np.percentile(lat_ms, 99):.3f}ms "
        f"max={lat_ms.max():.3f}ms",
        reg.snapshot("serve.query_s"),
    )
    report.emit(
        "serving",
        f"mvcc concurrency={n_clients}: {qps:.0f} q/s, "
        f"p99={np.percentile(lat_ms, 99):.3f}ms; "
        f"{st['batches']} micro-batches "
        f"(mean {st['mean_batch']:.1f}, max {st['max_batch']}, "
        f"{st['dedup_hits']} dedup / {st['grouped_queries']} grouped / "
        f"{st['cache_hits']} cached), "
        f"epochs: {st['epochs_published']} published, "
        f"{st['epochs_retired']} retired, {st['epochs_live']} live, "
        f"lag<={st['epoch_lag_max']}; {st['stale_reads']} stale reads, "
        f"{st['compactions']} compactions "
        f"({st['compactions_deferred']} deferred)",
        {
            "concurrency": n_clients,
            "qps": qps,
            "p50_ms": float(np.percentile(lat_ms, 50)),
            "p99_ms": float(np.percentile(lat_ms, 99)),
            **st,
        },
    )
    if st["stale_reads"]:
        report.emit(
            "serving-verify",
            f"FAILED: {st['stale_reads']} stale reads (must be 0)",
            {"stale_reads": st["stale_reads"]},
        )
        return 1
    report.emit(
        "store",
        f"{inc.store.n_nodes()} mu-nodes",
        {"mu_nodes": inc.store.n_nodes()},
    )
    if args.live:
        ap_ms = np.asarray(apply_lat) * 1e3 if apply_lat else np.zeros(1)
        inc_snap = reg.snapshot("inc.")
        report.emit(
            "live",
            f"{len(apply_lat)} update batches through the writer thread "
            f"(epoch {inc.epoch}), apply p50={np.percentile(ap_ms, 50):.2f}ms "
            f"p99={np.percentile(ap_ms, 99):.2f}ms; "
            f"{int(inc_snap.get('inc.n_deleted', 0))} deleted / "
            f"{int(inc_snap.get('inc.n_inserted', 0))} inserted facts",
            {**inc_snap, "apply_batches": len(apply_lat)},
        )
        if ckpt is not None:
            reg.gauge("storage.disk_bytes").set(ckpt.disk_nbytes())
            reg.gauge("storage.wal_bytes").set(ckpt.wal.nbytes())
            st_snap = reg.snapshot("storage.")
            report.emit(
                "storage",
                f"{int(st_snap.get('storage.checkpoints', 0))} checkpoints "
                f"under {args.checkpoint_dir} "
                f"({st_snap['storage.disk_bytes'] / 1024:.1f}KiB on disk)",
                st_snap,
            )
        if args.live_verify:
            from ..core import flat_seminaive

            want = {
                p: r
                for p, r in flat_seminaive(inc.program, inc.explicit).items()
                if r.shape[0]
            }
            got = inc.to_dict()
            ok = set(want) == set(got) and all(
                np.array_equal(want[p], got[p]) for p in want
            )
            report.emit(
                "live-verify",
                f"{'OK' if ok else 'MISMATCH'} "
                f"({sum(r.shape[0] for r in want.values())} facts)",
                {"ok": ok,
                 "facts": sum(r.shape[0] for r in want.values())},
            )
            if not ok:
                return 1
    return 0


def _main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--kb", default="lubm", choices=["lubm", "chain", "star", "paper"])
    ap.add_argument("--scale", type=int, default=2)
    ap.add_argument("--n-queries", type=int, default=2000)
    ap.add_argument("--zipf", type=float, default=1.1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-result-cache", action="store_true")
    ap.add_argument("--pallas", action="store_true",
                    help="route constant lookups, and the sharded engine's "
                         "dedup membership, through the Pallas kernels "
                         "(interpret mode on CPU)")
    ap.add_argument("--live", action="store_true",
                    help="serve updates interleaved with queries through "
                         "the incremental maintenance subsystem")
    ap.add_argument("--mvcc", action="store_true",
                    help="serve through the epoch-based MVCC tier "
                         "(repro.serving): concurrent client threads, "
                         "micro-batched admission, single writer thread")
    ap.add_argument("--concurrency", type=int, default=1, metavar="N",
                    help="closed-loop client threads in --mvcc mode")
    ap.add_argument("--distributed", action="store_true",
                    help="shadow the KB on the sharded engine (semi-naive "
                         "delta exchange over all visible devices); with "
                         "--live, updates also ship through all_to_all and "
                         "the final state is differentially verified")
    ap.add_argument("--update-every", type=int, default=200,
                    help="apply an update batch every N queries (--live)")
    ap.add_argument("--update-size", type=int, default=8,
                    help="explicit facts deleted (and re-inserted) per batch")
    ap.add_argument("--live-verify", action="store_true",
                    help="differentially check the final store against a "
                         "from-scratch materialisation (--live)")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="durable storage root: WAL + periodic snapshots")
    ap.add_argument("--checkpoint-every", type=int, default=5,
                    help="checkpoint every N applied update batches "
                         "(--live; a final checkpoint always runs)")
    ap.add_argument("--restore", action="store_true",
                    help="warm-start from the latest snapshot (+ WAL "
                         "replay in --live mode) instead of materialising")
    ap.add_argument("--compact-threshold", type=float, default=0.5,
                    help="dead mu-node fraction that triggers a "
                         "compaction epoch (--live; 0 disables)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable span tracing and write a Chrome "
                         "trace-event / Perfetto JSON file here (in "
                         "--live mode, rewritten after every update "
                         "batch)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a flat JSON metrics-registry snapshot "
                         "here (periodic in --live mode, final always)")
    ap.add_argument("--report-json", default=None, metavar="PATH",
                    help="append one JSON object per report block here")
    ap.add_argument("--provenance", action="store_true",
                    help="record the derivation journal during "
                         "materialisation/updates (implied by --explain, "
                         "--explain-sample, --hot-rules)")
    ap.add_argument("--explain", action="append", default=[],
                    metavar="FACT",
                    help="explain one materialised fact, e.g. "
                         "'path(v000000, v000003)' — repeatable; terms "
                         "resolve through the KB dictionary (or raw ids)")
    ap.add_argument("--explain-sample", type=int, default=0, metavar="N",
                    help="explain N randomly sampled derived "
                         "(non-explicit) facts and verify their proofs")
    ap.add_argument("--hot-rules", action="store_true",
                    help="render the per-rule cost attribution table "
                         "(derived/redundant/time) from the journal")
    args = ap.parse_args(argv)
    if args.mvcc and args.distributed:
        ap.error("--mvcc and --distributed are mutually exclusive")

    want_prov = bool(
        args.provenance or args.explain or args.explain_sample
        or args.hot_rules
    )
    if want_prov:
        from ..obs.provenance import get_journal

        journal = get_journal()
        journal.enabled = True
        journal.clear()

    if args.trace_out:
        get_tracer().enable()
    report = ReportSink(args.report_json)

    def flush_telemetry() -> None:
        if args.metrics_out:
            write_metrics(args.metrics_out)
        if args.trace_out:
            write_chrome_trace(args.trace_out)

    program, dataset, dictionary = build_kb(args.kb, args.scale)
    n_explicit = sum(np.asarray(r).shape[0] for r in dataset.values())
    report.emit(
        f"kb:{args.kb}",
        f"{n_explicit} explicit facts, {len(program)} rules",
        {"explicit_facts": n_explicit, "rules": len(program),
         "scale": args.scale},
    )

    kb_label = f"{args.kb}:scale{args.scale}"
    ckpt = (
        CheckpointManager(args.checkpoint_dir, label=kb_label)
        if args.checkpoint_dir
        else None
    )
    static_snap = (
        os.path.join(args.checkpoint_dir, "frozen")
        if args.checkpoint_dir
        else None
    )

    t0 = time.perf_counter()
    inc = None
    recovery = None
    stats = None
    if args.live or args.mvcc:
        # --mvcc always serves from an IncrementalStore: the MVCC tier
        # publishes epochs by freezing it (static KBs just never apply)
        if ckpt is not None and args.restore and ckpt.has_snapshot():
            inc, recovery = ckpt.restore(program)
        else:
            inc = IncrementalStore(program)
            stats = inc.load(dataset)
            if ckpt is not None:
                # cold start owns the directory: stale snapshots/WAL from
                # a previous run must not interleave with fresh epochs
                ckpt.reset()
                inc.attach_wal(ckpt.wal)
        source = inc
    elif (
        args.restore
        and static_snap is not None
        and os.path.exists(os.path.join(static_snap, "manifest.json"))
    ):
        source = load_frozen(static_snap, expected_label=kb_label)
    else:
        eng = CMatEngine(program, dedup_index=True)
        eng.load(dataset)
        stats = eng.materialise()
        source = eng
        if static_snap is not None:
            frozen = eng.facts.freeze()
            rows = {p: frozen.snapshot(p) for p in frozen.predicates()}
            write_snapshot(
                static_snap, eng.facts, kind="frozen",
                label=kb_label, rows=rows,
            )
    t_mat = time.perf_counter() - t0
    if stats is not None:
        report.emit(
            "materialise",
            f"{stats.rounds} rounds over {stats.n_strata} strata, "
            f"{stats.n_facts} facts in {stats.n_meta_facts} meta-facts, "
            f"{t_mat:.2f}s",
            {"rounds": stats.rounds, "n_strata": stats.n_strata,
             "n_facts": stats.n_facts, "n_meta_facts": stats.n_meta_facts,
             "seconds": t_mat},
        )
        report.emit(
            "fixpoint",
            f"{stats.n_rule_applications} rule applications, "
            f"{stats.rule_applications_skipped} skipped without a probe; "
            f"plans: {stats.plan_cache.get('plans', 0)} compiled, "
            f"{stats.plan_cache.get('plan_hits', 0)} hits, "
            f"{stats.plan_cache.get('plan_replans', 0)} replans",
            {"n_rule_applications": stats.n_rule_applications,
             "rule_applications_skipped": stats.rule_applications_skipped,
             **{f"plan_cache.{k}": v for k, v in stats.plan_cache.items()}},
        )
    elif recovery is not None:
        # rendered from the registry scope the restore path published
        # into (the recovery object only contributes the snapshot name
        # and epochs — strings and levels the registry does not hold)
        snap = get_registry().snapshot("storage.")
        report.emit(
            "restore",
            f"warm start from {recovery.snapshot}: snapshot "
            f"{snap['storage.restore_snapshot_s']:.3f}s + "
            f"{int(snap['storage.wal_replayed'])} WAL "
            f"batches {snap['storage.restore_replay_s']:.3f}s (epoch "
            f"{recovery.snapshot_epoch} -> {recovery.final_epoch}), "
            f"{inc.facts.n_facts()} facts in "
            f"{inc.facts.n_meta_facts()} meta-facts; total {t_mat:.3f}s",
            {**snap, "snapshot": recovery.snapshot,
             "snapshot_epoch": recovery.snapshot_epoch,
             "final_epoch": recovery.final_epoch, "seconds": t_mat},
        )
    else:
        report.emit(
            "restore",
            f"frozen snapshot served from {static_snap}, {t_mat:.3f}s",
            {"snapshot": static_snap, "seconds": t_mat},
        )

    # high-water mark for the load/materialise/restore phase; the
    # per-predicate compression gauges start from the fresh store
    # (compaction epochs re-sample them as structure is re-shared)
    sample_memory(phase="restore" if stats is None else "materialise")
    facts_obj = inc.facts if inc is not None else getattr(source, "facts", None)
    if facts_obj is not None:
        publish_predicate_effectiveness(facts_obj)

    dist = None
    if args.distributed:
        import jax
        from jax.sharding import Mesh

        from ..core.distributed import DistributedEngine

        dprog = DistributedEngine.supported_program(program)
        dist_complete = len(dprog) == len(program)
        mesh = Mesh(np.asarray(jax.devices()), ("data",))
        # size the padded buffers from the host materialisation (2x
        # headroom over the biggest predicate): every device op scales
        # with capacity, not live rows, so oversizing taxes each round
        mat_rows = (
            inc.to_dict() if inc is not None
            else source.materialisation()
            if hasattr(source, "materialisation")
            else None
        )
        cap = 1 << 14
        if mat_rows:
            biggest = max(
                (np.asarray(r).shape[0] for r in mat_rows.values()),
                default=0,
            )
            cap = max(1 << 10, 1 << int(np.ceil(np.log2(max(2 * biggest, 2)))))
        dist = DistributedEngine(
            dprog, mesh, capacity=cap, use_pallas_kernels=args.pallas
        )
        t0 = time.perf_counter()
        # seed from the *restored* explicit set when the host store came
        # back from a checkpoint — the generator dataset no longer
        # reflects prior sessions' WAL batches and the final
        # differential check would flag a phantom mismatch
        dist.materialise(inc.explicit if inc is not None else dataset)
        t_dist = time.perf_counter() - t0
        ds = dist.stats
        report.emit(
            "distributed",
            f"{mesh.shape['data']} shard(s) at capacity {cap}, {dist.rounds} "
            f"rounds over {ds.n_strata} strata in {t_dist:.2f}s; "
            f"{ds.n_rule_applications} rule applications "
            f"({ds.rule_applications_skipped} skipped), "
            f"{ds.rows_joined} rows joined, {ds.exchanges} exchanges "
            f"({ds.exchanges_skipped} elided by planner keys, "
            f"{ds.exchange_regrows} regrows)",
            {**get_registry().snapshot("dist."), "seconds": t_dist,
             "capacity": cap, "shards": mesh.shape["data"]},
        )
        if not dist_complete:
            report.emit(
                "distributed",
                f"{len(program) - len(dprog)} rule(s) outside "
                f"the distributed fragment — differential checks disabled",
                {"unsupported_rules": len(program) - len(dprog)},
            )
        elif not args.live and hasattr(source, "materialisation"):
            reg = get_registry()
            try:
                dist.check_integrity(source.materialisation())
                reg.counter("dist.verify_ok").inc()
                report.emit(
                    "dist-verify",
                    "OK (sharded materialisation == host)",
                    reg.snapshot("dist.verify"),
                )
            except AssertionError as e:
                reg.counter("dist.verify_mismatch").inc()
                report.emit(
                    "dist-verify", f"MISMATCH: {e}",
                    {**reg.snapshot("dist.verify"), "error": str(e)},
                )
                return 1

    stream = make_stream(args.kb, args.scale, args.n_queries, args.zipf, args.seed)
    if not stream:
        print("[serve] empty query stream (--n-queries 0); nothing to do")
        return 0

    update_at = max(args.update_every, 1)
    batches = (
        make_update_batches(
            dataset, len(stream) // update_at + 1, args.update_size, args.seed
        )
        if args.live
        else []
    )

    if args.mvcc:
        rc = _serve_mvcc(
            args, report, inc, dictionary, stream, batches, ckpt,
            flush_telemetry, update_at,
        )
        if rc:
            return rc
        return _emit_tail(args, report, inc, inc, dictionary, flush_telemetry)

    qe = QueryEngine(
        source,
        dictionary,
        result_cache_size=0 if args.no_result_cache else 1024,
        use_pallas=args.pallas,
    )
    # warmup: build snapshots + plans off the measured path
    with span("serve.warmup"):
        for text in dict.fromkeys(stream[: min(50, len(stream))]):
            qe.answer(text)
    warm_cells = qe.frozen.snapshot_cells
    warm_cache = qe.cache_stats()

    latencies = np.zeros(len(stream))
    apply_lat: list[float] = []
    dist_lat: list[float] = []
    apply_tot: list = []  # per-batch stats (the journal is truncated
    n_answers = 0         # by checkpoints, so sums come from here)
    next_batch = 0
    n_checkpoints = 0
    compactions = []
    t_serve0 = time.perf_counter()
    for i, text in enumerate(stream):
        if args.live and i and i % update_at == 0 and next_batch < len(batches):
            with span("serve.update_batch", batch=next_batch):
                deletions, additions = batches[next_batch]
                next_batch += 1
                t0 = time.perf_counter()
                apply_tot.append(
                    inc.apply(additions=additions, deletions=deletions)
                )
                cs = inc.maybe_compact(args.compact_threshold)
                if cs is not None:
                    compactions.append(cs)
                qe.bump_epoch(inc)
                apply_lat.append(time.perf_counter() - t0)
                if dist is not None:
                    # the same batch ships through the all_to_all exchange
                    t0 = time.perf_counter()
                    dist.apply(additions=additions, deletions=deletions)
                    dist_lat.append(time.perf_counter() - t0)
                if (
                    ckpt is not None
                    and args.checkpoint_every > 0
                    and next_batch % args.checkpoint_every == 0
                ):
                    ckpt.checkpoint(inc)
                    n_checkpoints += 1
                sample_memory(phase="serve_batch", rss=False)
            # live telemetry: the trace/metrics files track the serving
            # loop batch by batch, not only at exit
            flush_telemetry()
        t0 = time.perf_counter()
        res = qe.answer(text)
        latencies[i] = time.perf_counter() - t0
        n_answers += res.n_answers
    t_serve = time.perf_counter() - t_serve0
    if args.live and ckpt is not None:
        ckpt.checkpoint(inc)  # final durable state for the next restore
        n_checkpoints += 1

    lat_ms = latencies * 1e3
    # measured-window counters only (warmup answered queries too)
    cache = {
        k: v - warm_cache[k] for k, v in qe.cache_stats().items()
    }
    hit_rate = cache["result_hits"] / max(
        cache["result_hits"] + cache["result_misses"], 1
    )
    # per-query latencies feed the registry histogram so the metrics
    # snapshot carries serving percentiles alongside the counters
    lat_hist = get_registry().histogram("serve.query_s")
    for v in latencies:
        lat_hist.observe(float(v))
    publish_query_cache(qe)
    report.emit(
        "serve",
        f"{len(stream)} queries in {t_serve:.2f}s "
        f"({len(stream) / max(t_serve, 1e-9):.0f} q/s), "
        f"{n_answers} answers total",
        {"queries": len(stream), "seconds": t_serve,
         "qps": len(stream) / max(t_serve, 1e-9), "answers": n_answers},
    )
    report.emit(
        "latency",
        f"p50={np.percentile(lat_ms, 50):.3f}ms "
        f"p90={np.percentile(lat_ms, 90):.3f}ms "
        f"p99={np.percentile(lat_ms, 99):.3f}ms "
        f"max={lat_ms.max():.3f}ms",
        get_registry().snapshot("serve.query_s"),
    )
    report.emit(
        "cache",
        f"result hit rate {hit_rate:.1%} "
        f"(plans: {cache['plan_hits']} hits / {cache['plan_misses']} misses); "
        f"snapshot warmup {warm_cells} cells, "
        f"{qe.frozen.snapshot_cells - warm_cells} after",
        {**get_registry().snapshot("query."), "hit_rate": hit_rate},
    )
    report.emit(
        "store",
        f"{qe.frozen.store.n_nodes()} mu-nodes (flat across stream)",
        {"mu_nodes": qe.frozen.store.n_nodes()},
    )
    if args.live:
        reg = get_registry()
        ap_ms = np.asarray(apply_lat) * 1e3 if apply_lat else np.zeros(1)
        # the registry's inc. scope accumulated these batch by batch via
        # publish_incremental; render the report line from its snapshot
        inc_snap = reg.snapshot("inc.")
        report.emit(
            "live",
            f"{len(apply_lat)} update batches applied "
            f"(epoch {inc.epoch}), apply p50={np.percentile(ap_ms, 50):.2f}ms "
            f"p99={np.percentile(ap_ms, 99):.2f}ms; "
            f"{int(inc_snap.get('inc.n_deleted', 0))} deleted / "
            f"{int(inc_snap.get('inc.n_inserted', 0))} inserted facts, "
            f"{int(inc_snap.get('inc.n_rederived', 0))} rederived; "
            f"{qe.stale_evictions} stale cache entries evicted",
            {**inc_snap, "stale_evictions": qe.stale_evictions,
             "apply_batches": len(apply_lat),
             "seconds": float(np.sum(apply_lat))},
        )
        usage = inc.mu_usage()
        reg.gauge("gc.nodes").set(usage.n_nodes)
        reg.gauge("gc.dead_fraction").set(usage.dead_fraction)
        reg.gauge("gc.resident_bytes").set(usage.total_bytes)
        gc_snap = reg.snapshot("gc.")
        n_compactions = int(gc_snap.get("gc.compactions", 0))
        compact_note = (
            f"{n_compactions} compaction epochs "
            f"(-{int(gc_snap.get('gc.nodes_reclaimed', 0))} "
            f"nodes, {int(gc_snap.get('gc.reshared_leaves', 0))} leaves "
            f"re-shared)"
            if n_compactions
            else "no compactions"
        )
        report.emit(
            "mu-gc",
            f"{usage.n_nodes} nodes "
            f"({usage.dead_fraction:.1%} dead, "
            f"{usage.total_bytes / 1024:.1f}KiB resident); {compact_note}",
            gc_snap,
        )
        if ckpt is not None:
            reg.gauge("storage.disk_bytes").set(ckpt.disk_nbytes())
            reg.gauge("storage.wal_bytes").set(ckpt.wal.nbytes())
            st_snap = reg.snapshot("storage.")
            report.emit(
                "storage",
                f"{int(st_snap.get('storage.checkpoints', 0))} checkpoints "
                f"under {args.checkpoint_dir} "
                f"({st_snap['storage.disk_bytes'] / 1024:.1f}KiB "
                f"on disk, WAL {int(st_snap['storage.wal_bytes'])}B), "
                f"journal {int(inc_snap.get('inc.journal_bytes', 0))}B "
                f"resident",
                st_snap,
            )
        if dist is not None and dist_lat:
            dl_ms = np.asarray(dist_lat) * 1e3
            ds = dist.stats
            report.emit(
                "distributed",
                f"{len(dist_lat)} update batches through the "
                f"exchange, apply p50={np.percentile(dl_ms, 50):.2f}ms "
                f"p99={np.percentile(dl_ms, 99):.2f}ms "
                f"(last batch: {ds.n_overdeleted} overdeleted, "
                f"{ds.n_rederived} rederived, {ds.n_inserted} inserted)",
                {**reg.snapshot("dist."), "apply_batches": len(dist_lat),
                 "seconds": float(np.sum(dist_lat))},
            )
            if dist_complete:
                try:
                    dist.check_integrity(inc)
                    reg.counter("dist.verify_ok").inc()
                    report.emit(
                        "dist-verify",
                        "OK (sharded state == host store)",
                        reg.snapshot("dist.verify"),
                    )
                except AssertionError as e:
                    reg.counter("dist.verify_mismatch").inc()
                    report.emit(
                        "dist-verify", f"MISMATCH: {e}",
                        {**reg.snapshot("dist.verify"), "error": str(e)},
                    )
                    return 1
        if args.live_verify:
            from ..core import flat_seminaive

            want = {
                p: r
                for p, r in flat_seminaive(program, inc.explicit).items()
                if r.shape[0]
            }
            got = inc.to_dict()
            ok = set(want) == set(got) and all(
                np.array_equal(want[p], got[p]) for p in want
            )
            report.emit(
                "live-verify",
                f"{'OK' if ok else 'MISMATCH'} "
                f"({sum(r.shape[0] for r in want.values())} facts)",
                {"ok": ok,
                 "facts": sum(r.shape[0] for r in want.values())},
            )
            if not ok:
                return 1
    return _emit_tail(args, report, inc, source, dictionary, flush_telemetry)


def _emit_tail(args, report, inc, source, dictionary, flush_telemetry) -> int:
    """Shared trailing report blocks (provenance, kernels, memory,
    trace, metrics) for both the single-thread and MVCC serve paths."""
    want_prov = bool(
        args.provenance or args.explain or args.explain_sample
        or args.hot_rules
    )
    if want_prov:
        from ..obs.provenance import get_journal

        journal = get_journal()
        explain_src = (
            inc if inc is not None
            else source if hasattr(source, "explain_fact") else None
        )

        def _decode(tid):
            try:
                return dictionary.term_of(int(tid))
            except (KeyError, IndexError):  # id outside the dictionary
                return int(tid)

        targets = []
        parse_errors = []
        for spec in args.explain:
            try:
                targets.append(_parse_fact_spec(spec, dictionary))
            except ValueError as e:
                parse_errors.append(str(e))
        if args.explain_sample and explain_src is not None:
            mat = (
                inc.to_dict() if inc is not None
                else source.materialisation()
            )
            explicit = inc.explicit if inc is not None else source._explicit
            targets += _sample_derived(
                mat, explicit, args.explain_sample, args.seed
            )

        explanations = []
        if explain_src is not None:
            for pred, terms in targets:
                node = explain_src.explain_fact(pred, terms, decode=_decode)
                if node is None:
                    shown = ", ".join(str(_decode(t)) for t in terms)
                    explanations.append({
                        "fact": f"{pred}({shown})",
                        "found": False, "verified": False,
                    })
                else:
                    explanations.append({
                        "fact": node["fact"], "found": True,
                        **_proof_summary(node),
                    })
        hot = journal.hot_rules(10) if args.hot_rules else []
        n_ok = sum(1 for e in explanations if e["verified"])
        prov_bytes = journal.memory_report()["journal_bytes"]
        text = (
            f"journal {len(journal.records)} records "
            f"({journal.dropped} dropped, {prov_bytes / 1024:.1f}KiB)"
        )
        if explanations:
            text += f"; {n_ok}/{len(explanations)} explanations verified"
        elif targets and explain_src is None:
            text += "; explain skipped (frozen snapshot serving, no engine)"
        report.emit(
            "provenance", text,
            {"records": len(journal.records), "dropped": journal.dropped,
             "journal_bytes": prov_bytes, "explanations": explanations,
             "hot_rules": hot, "parse_errors": parse_errors,
             "explain_available": explain_src is not None},
        )
        for e in explanations:
            mark = "ok" if e["verified"] else (
                "NOT FOUND" if not e["found"] else "UNVERIFIED"
            )
            extra = (
                f" depth={e['depth']} nodes={e['nodes']}" if e["found"] else ""
            )
            print(f"  explain {e['fact']}: {mark}{extra}")
        if hot:
            print("  hot rules (by recorded time):")
            for h in hot:
                print(
                    f"    R{h['rule_id']:<3} {h['time_ns'] / 1e6:8.2f}ms  "
                    f"derived={h['derived']:<8} redundant={h['redundant']:<8} "
                    f"rounds={h['rounds_active']:<3} {h['rule']}"
                )
    if args.pallas:
        from ..kernels import ops

        traffic = ", ".join(
            f"{op}: {m['calls']} calls / {m['elements']} elems"
            for op, m in sorted(ops.meter().items())
        )
        report.emit(
            "kernels",
            traffic or "no kernel launches",
            get_registry().snapshot("kernels."),
        )
    # final roll-up: resident bytes from the reporter registry, RSS from
    # the kernel, and the peak watermarks the phase samples accumulated
    mem_rep = sample_memory()
    mem_snap = get_registry().snapshot("mem.")
    report.emit(
        "memory",
        f"resident {mem_rep['resident_bytes'] / 1024:.1f}KiB "
        f"(peak {int(mem_snap.get('mem.peak_resident_bytes', 0)) / 1024:.1f}"
        f"KiB), rss {mem_rep['rss_bytes'] / (1 << 20):.1f}MiB",
        mem_snap,
    )
    flush_telemetry()
    if args.trace_out:
        tr = get_tracer()
        report.emit(
            "trace",
            f"{len(tr.events)} span/instant events -> {args.trace_out} "
            f"({tr.dropped} dropped)",
            {"events": len(tr.events), "dropped": tr.dropped,
             "path": args.trace_out},
        )
    if args.metrics_out:
        report.emit(
            "metrics",
            f"{len(get_registry().snapshot())} metrics -> "
            f"{args.metrics_out}",
            {"path": args.metrics_out},
        )
    report.close()
    return 0


def main(argv=None):
    # --trace-out enables the process tracer and the provenance flags
    # enable the journal; restore both on every exit path so in-process
    # callers (tests, drivers) see no state leak
    from ..compile_cache import enable_compile_cache
    from ..obs.provenance import get_journal

    enable_compile_cache()
    tr = get_tracer()
    was_enabled = tr.enabled
    journal = get_journal()
    prov_was = journal.enabled
    try:
        return _main(argv)
    finally:
        if not was_enabled:
            tr.disable()
        if not prov_was:
            journal.enabled = False
            journal.clear()


if __name__ == "__main__":
    raise SystemExit(main())
