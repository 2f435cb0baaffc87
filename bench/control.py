"""The control: the plain reference in the engine's place, with its
fixpoint cut one round short, so that it breaks the configuration's
guarantee of a complete least model.  The harness's comparison has to
find it not correct at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...] --seconds <s>

runs the cell's set-up, window and check with this control as the
engine, once per seed, and prints each run's compared numbers.  The
benchmark's own runs never use it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_engine(lines):
    """An engine class backed by :mod:`bench.reference`, cut short."""
    from bench import reference

    parsed = reference.parse_rules(lines)

    def short(facts):
        _, rounds = reference.materialise(facts, parsed)
        got, _ = reference.materialise(facts, parsed, max_rounds=max(rounds - 1, 0))
        return got

    class ControlEngine:
        @classmethod
        def supported_program(cls, program):
            return program

        def __init__(self, program, mesh, **_):
            pass

        def materialise(self, dataset, max_rounds=64):
            return short({p: np.asarray(r) for p, r in dataset.items()})

    return ControlEngine


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    cell = harness.load_cell(args.workload)
    harness.use_checkout_cache()
    cls = control_engine(harness.rules(cell["config"]))
    for seed in args.seeds:
        t = time.perf_counter()
        r = harness.run_cell(cell, seed, args.seconds, False, t_start=t, engine_cls=cls)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "correct": r["correct"],
            "attempted": r["attempted"], "checks": r["checks"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
