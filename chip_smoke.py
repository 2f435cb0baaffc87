"""Bring-up smoke of the sharded materialise -> update -> query path on a TPU.

    python chip_smoke.py              # one chip: the whole serving path
    python chip_smoke.py --chips 4    # four chips: the sharded phase only

Runs ``repro.launch.serve_datalog`` in this process (no child process
touches the chip) on the lubm KB at scale 250 with ``--distributed
--pallas --live --live-verify``:

1. host materialisation (``IncrementalStore``);
2. the sharded fixpoint on the device, with the Pallas membership
   kernel deduplicating inside every round;
3. update batches through ``DistributedEngine.apply`` (and the host
   store);
4. the query stream;
5. the final differential checks: sharded state == host store
   (``dist-verify``) and host store == a from-scratch ``flat_seminaive``
   (``live-verify``).

``--chips 4`` runs the sharded phase alone on a 4-device mesh: the
materialisation plus one update batch, then both differential checks,
and requires a non-zero number of ``all_to_all`` exchanges.

It fails, with a message and no result line, when no TPU is found, when
kernels would run in interpret mode, when ``serve_datalog`` fails, when
a differential check is missing or not OK, or when the compiled round
holds no Pallas kernel (``tpu_custom_call``).  On success the last line
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.  Wall
times printed here include compilation; they are bring-up numbers, not
a benchmark.  ``--rehearse`` runs the whole path on any backend (CPU
rehearsals at a small ``--scale``) and only then fails the checks.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out")


def fail(msg: str) -> int:
    print(f"[chip-smoke] FAILED: {msg}", file=sys.stderr)
    return 1


def serve_argv(chips: int, scale: int, report: str) -> list[str]:
    argv = [
        "--kb", "lubm", "--scale", str(scale), "--distributed", "--pallas",
        "--live", "--update-size", "8", "--live-verify", "--no-result-cache",
        "--report-json", report,
    ]
    if chips == 4:
        # one update batch (at query 1), two queries: the sharded phase
        return argv + ["--update-every", "1", "--n-queries", "2"]
    # update batches at queries 100 and 200
    return argv + ["--update-every", "100", "--n-queries", "300"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=int, default=250)
    ap.add_argument("--rehearse", action="store_true",
                    help="run the path on any backend, then check")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        return fail(f"the repro package is not next to this script ({e})")
    cache_dir = enable_compile_cache()

    import jax

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    print(f"[chip-smoke] device {device}; compile cache {cache_dir}")
    problems = []
    if dev.platform != "tpu":
        problems.append(f"no TPU found (jax platform is {dev.platform!r})")
        if not args.rehearse:
            return fail(problems[0])
    if device["count"] != args.chips:
        problems.append(
            f"--chips {args.chips} but jax sees {device['count']} device(s)"
        )
        if not args.rehearse:
            return fail(problems[-1])

    from repro.core import distributed
    from repro.kernels.backend import ENV_VAR, resolve_interpret
    from repro.launch import serve_datalog

    if os.environ.get(ENV_VAR) is not None:
        problems.append(f"{ENV_VAR} is set ({os.environ[ENV_VAR]!r})")
    if resolve_interpret(None) is not False:
        problems.append("Pallas kernels would run in interpret mode")

    # compile accounting: every backend compile, and those inside each
    # sharded update batch (a recompile per batch shows up here)
    compiles = {"n": 0, "seconds": 0.0}

    def on_event(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1
            compiles["seconds"] += secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    engines = []
    batch_compiles = []

    class RecordedEngine(distributed.DistributedEngine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            engines.append(self)

        def apply(self, *a, **kw):
            n0 = compiles["n"]
            try:
                return super().apply(*a, **kw)
            finally:
                batch_compiles.append(compiles["n"] - n0)

    distributed.DistributedEngine = RecordedEngine
    os.makedirs(OUT_DIR, exist_ok=True)
    report_path = os.path.join(OUT_DIR, f"chip_smoke_{args.chips}chip.jsonl")
    t0 = time.perf_counter()
    try:
        rc = serve_datalog.main(serve_argv(args.chips, args.scale, report_path))
    finally:
        distributed.DistributedEngine = RecordedEngine.__mro__[1]
    t_main = time.perf_counter() - t0
    if rc != 0:
        problems.append(f"serve_datalog returned {rc}")

    with open(report_path) as fh:
        blocks = [json.loads(line) for line in fh if line.strip()]

    def block(tag, key=None):
        for b in blocks:
            if b["block"] == tag and (key is None or key in b):
                return b
        return None

    for tag in ("dist-verify", "live-verify"):
        b = block(tag)
        ok = b is not None and (
            b.get("ok") is True or b.get("dist.verify_ok", 0) > 0
        )
        print(f"[chip-smoke] {tag}: {'OK' if ok else 'MISSING/NOT OK'}")
        if not ok:
            problems.append(f"{tag} block missing or not OK")

    mat = block("materialise") or {}
    dmat = block("distributed", "capacity") or {}
    dapply = block("distributed", "apply_batches") or {}
    live = block("live", "apply_batches") or {}
    serve = block("serve") or {}
    phases = {
        "host materialisation": mat.get("seconds"),
        "sharded fixpoint (device)": dmat.get("seconds"),
        "sharded update batches": dapply.get("seconds"),
        "host update batches": live.get("seconds"),
        "query stream": (
            serve.get("seconds", 0.0) - dapply.get("seconds", 0.0)
            - live.get("seconds", 0.0)
        ) if serve else None,
    }
    for name, secs in phases.items():
        shown = "not run" if secs is None else f"{secs:.3f} s"
        print(f"[chip-smoke] phase {name}: {shown} wall (incl. compilation)")
    print(
        f"[chip-smoke] serve_datalog total {t_main:.3f} s wall; "
        f"{compiles['n']} backend compiles, {compiles['seconds']:.3f} s; "
        f"compiles per sharded update batch: {batch_compiles}"
    )
    if args.chips == 4 and not dmat.get("dist.exchanges"):
        problems.append("the 4-chip fixpoint issued no all_to_all exchange")

    # the round program: the engine's largest cached fixpoint variant
    if not engines:
        problems.append("serve_datalog built no sharded engine")
    else:
        eng = engines[0]
        mats = [(k, v) for k, v in eng._variants.items() if k[0] == "mat"]
        if not mats:
            problems.append("no fixpoint round was compiled")
        else:
            key, rec = max(mats, key=lambda kv: len(kv[0][1]))
            t1 = time.perf_counter()
            compiled = rec.fn.lower(*eng._flat_state()).compile()
            hlo = compiled.as_text()
            mem = compiled.memory_analysis()
            print(
                f"[chip-smoke] round program ({len(key[1]) - 1} rule "
                f"applications, capacity {eng.capacity}, {eng.n_shards} "
                f"shard(s), re-lowered in {time.perf_counter() - t1:.3f} s): "
                f"memory_analysis {mem}"
            )
            n_kernels = hlo.count("tpu_custom_call")
            print(f"[chip-smoke] tpu_custom_call in round HLO: {n_kernels}")
            if not n_kernels:
                problems.append("the compiled round holds no tpu_custom_call")
    stats = getattr(dev, "memory_stats", lambda: None)() or {}
    if "peak_bytes_in_use" in stats:
        print(
            f"[chip-smoke] device peak_bytes_in_use "
            f"{stats['peak_bytes_in_use']} of {stats.get('bytes_limit')}"
        )

    if problems:
        return fail("; ".join(problems))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
