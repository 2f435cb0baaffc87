"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, builds its data from
``--seed``, warms up, measures for ``--seconds`` and checks what the
window produced against the plain reference.  The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``: each compared number beside its limit).  With ``--trace 0``
the metrics are the cell's end-to-end metrics; with ``--trace 1`` they
are its per-layer metrics, read from a profiler trace of the window.

The first run of a cell in a checkout first runs the cell once more in
a process of its own (``--warm-only``: one materialisation, no result),
which fills the checkout's compile cache and the Pallas tuner's table;
only then does this process touch the chip.  So the tuner's sweeps, and
what they allocate, never count in a measured process, and the first
run measures what every later one does.

It exits non-zero and prints no result when JAX finds no TPU, fewer
chips than the cell asks for, or a device kind that ``bench/peaks.json``
does not list: it never measures on the CPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--warm-only", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    try:
        cell = harness.load_cell(args.workload)
        cache = harness.use_checkout_cache()
        marker = os.path.join(cache, "warm", args.workload)
        if not args.warm_only and not os.path.exists(marker):
            harness.log("first run in this checkout: warming the caches")
            rc = subprocess.call([
                sys.executable, os.path.abspath(__file__),
                "--workload", args.workload, "--seed", str(args.seed),
                "--seconds", "0", "--trace", "0", "--warm-only",
            ])
            if rc:
                raise harness.BenchError(f"the warm-up process exited with {rc}")
        result = harness.run_cell(
            cell, args.seed, 0 if args.warm_only else args.seconds,
            bool(args.trace) and not args.warm_only, t_start=T_START,
        )
    except harness.BenchError as e:
        harness.log(f"FAILED: {e}")
        return 1
    if args.warm_only:
        os.makedirs(os.path.dirname(marker), exist_ok=True)
        open(marker, "w").close()
        return 0
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
