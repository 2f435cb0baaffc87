"""Benchmark harness: one module per paper table + system benchmarks.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--smoke] \
        [--json PATH]

Prints one CSV block per benchmark.  ``--smoke`` runs tiny sizes for
benches that support it (CI keeps the drivers from rotting without
paying real benchmark time); benches without a ``smoke`` parameter run
at their normal size.  ``--json PATH`` writes a machine-readable result
file — per-bench status, wall time, and whatever structured rows the
bench returns — which CI uploads as a build artifact, and validates it
against the flat-rows-of-scalars schema (:func:`check_schema`) so
artifacts stay diffable across PRs.

The metrics registry (:mod:`repro.obs`) is **reset before every
bench**, so each suite sees only its own counters — the kernel meter
used to be module-global and cross-contaminated suites.  After each
bench the non-zero counters are snapshotted into the bench's
``"metrics"`` key (flat scalars, same contract as rows); the
regression gate (:mod:`benchmarks.compare`) checks selected counters
against the committed baseline with per-metric tolerances.
"""

from __future__ import annotations

import argparse
import inspect
import json
import sys
import time

_SCALAR = (str, int, float, bool, type(None))


def check_schema(payload: dict) -> list[str]:
    """Violations of the bench-artifact contract.

    CI uploads ``--json`` output as a build artifact and diffs runs
    across PRs; that only works while every bench keeps emitting the
    same machine-comparable shape — flat rows of scalars.  Run with the
    check so a bench that starts returning nested objects (or a status
    typo) fails the build instead of silently breaking comparability.
    """
    errs: list[str] = []
    if set(payload) != {"smoke", "failures", "benches"}:
        errs.append(f"top-level keys {sorted(payload)}")
        return errs
    if not isinstance(payload["smoke"], bool):
        errs.append("'smoke' must be a bool")
    if not isinstance(payload["failures"], int):
        errs.append("'failures' must be an int")
    for name, bench in payload["benches"].items():
        if bench.get("status") not in ("ok", "failed"):
            errs.append(f"{name}: status {bench.get('status')!r}")
        if not isinstance(bench.get("seconds"), (int, float)):
            errs.append(f"{name}: 'seconds' missing or non-numeric")
        extra = set(bench) - {"status", "seconds", "rows", "error", "metrics"}
        if extra:
            errs.append(f"{name}: unexpected keys {sorted(extra)}")
        metrics = bench.get("metrics")
        if metrics is not None:
            if not isinstance(metrics, dict):
                errs.append(f"{name}: metrics must be a flat dict")
            else:
                bad = {
                    k: type(v).__name__
                    for k, v in metrics.items()
                    if not isinstance(k, str)
                    or isinstance(v, bool)
                    or not isinstance(v, (int, float))
                }
                if bad:
                    errs.append(f"{name}: non-numeric metrics {bad}")
        if bench.get("status") == "failed" and not isinstance(
            bench.get("error"), str
        ):
            errs.append(f"{name}: failed bench without an 'error' string")
        rows = bench.get("rows")
        if rows is None:
            continue
        if isinstance(rows, dict):
            rows = [rows]
        if not isinstance(rows, list):
            errs.append(f"{name}: rows must be a list or dict")
            continue
        for i, row in enumerate(rows):
            if not isinstance(row, dict):
                errs.append(f"{name}: rows[{i}] is not a dict")
                continue
            bad = {
                k: type(v).__name__
                for k, v in row.items()
                if not isinstance(k, str) or not isinstance(v, _SCALAR)
            }
            if bad:
                errs.append(f"{name}: rows[{i}] non-scalar cells {bad}")
    return errs


def write_history(payload: dict, history_dir: str,
                  now: float | None = None) -> str:
    """Append one timestamped ``BENCH_<UTC>.json`` artifact to
    ``history_dir`` (created if missing) and return its path.  CI
    uploads the directory, so green runs accumulate a dated series of
    bench results next to the latest ``bench-results.json``."""
    import datetime
    import os

    ts = datetime.datetime.fromtimestamp(
        time.time() if now is None else now, tz=datetime.timezone.utc
    )
    name = f"BENCH_{ts.strftime('%Y%m%dT%H%M%SZ')}.json"
    os.makedirs(history_dir, exist_ok=True)
    path = os.path.join(history_dir, name)
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2, default=str)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write per-bench results as JSON")
    ap.add_argument("--history", default=None, metavar="DIR",
                    help="also append a timestamped BENCH_<date>.json "
                         "copy of the results to this directory")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (
        bench_dedup,
        bench_distributed,
        bench_incremental,
        bench_kernels,
        bench_memory,
        bench_provenance,
        bench_query,
        bench_representation,
        bench_roofline,
        bench_runtime,
        bench_serving,
        bench_storage,
    )

    benches = {
        "representation": bench_representation.run,  # paper Table 1/3
        "runtime": bench_runtime.run,                # paper Table 2/4
        "dedup": bench_dedup.run,                    # beyond-paper ablation
        "kernels": bench_kernels.run,                # Pallas microbench
        "roofline": bench_roofline.run,              # deliverable (g)
        "query": bench_query.run,                    # compressed vs flat answering
        "incremental": bench_incremental.run,        # update vs rematerialise
        "storage": bench_storage.run,                # cold vs restore, compaction
        "distributed": bench_distributed.run,        # naive vs semi-naive shards
        "memory": bench_memory.run,                  # obs.memory accounting
        "provenance": bench_provenance.run,          # journal overhead gate
        "serving": bench_serving.run,                # MVCC tier load driver
    }
    from repro.obs import get_registry

    registry = get_registry()
    failures = 0
    results: dict[str, dict] = {}
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        print(f"\n=== bench:{name} ===", flush=True)
        # per-suite isolation: every bench starts from zeroed counters,
        # so its snapshot carries only its own work (the kernel meter
        # used to leak across suites)
        registry.reset()
        t0 = time.time()
        kwargs = {}
        if args.smoke and "smoke" in inspect.signature(fn).parameters:
            kwargs["smoke"] = True
        try:
            rows = fn(**kwargs)
            dt = time.time() - t0
            print(f"=== bench:{name} done in {dt:.1f}s ===")
            results[name] = {"status": "ok", "seconds": round(dt, 2)}
            if isinstance(rows, (list, dict)):
                results[name]["rows"] = rows
            # best-effort memory roll-up: publish mem.* gauges from
            # whatever reporters the bench left alive (rss excluded —
            # kernel numbers are not comparable across runners)
            try:
                from repro.obs import sample_memory

                sample_memory(rss=False)
            except Exception:  # noqa: BLE001 — telemetry must not fail a bench
                pass
            metrics = {
                k: v for k, v in registry.snapshot().items() if v
            }
            if metrics:
                results[name]["metrics"] = metrics
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"=== bench:{name} FAILED: {type(e).__name__}: {e} ===")
            results[name] = {
                "status": "failed",
                "seconds": round(time.time() - t0, 2),
                "error": f"{type(e).__name__}: {e}",
            }
    if args.json or args.history:
        payload = {
            "smoke": bool(args.smoke),
            "failures": failures,
            "benches": results,
        }
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=2, default=str)
        print(f"[json] wrote {args.json}")
        # round-trip through JSON so the check sees what a consumer sees
        schema_errs = check_schema(json.loads(json.dumps(payload, default=str)))
        if schema_errs:
            failures += 1
            print("[json] SCHEMA VIOLATIONS (bench artifacts must stay "
                  "machine-comparable across PRs):")
            for err in schema_errs:
                print(f"  - {err}")
    if args.history:
        path = write_history(payload, args.history)
        print(f"[json] history appended: {path}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
