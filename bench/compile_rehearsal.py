"""Compile a cell's device programs at its real capacity for a described
TPU v5e, with no chip attached.

    JAX_PLATFORMS=cpu python3 bench/compile_rehearsal.py --workload <cell>

Runs the cell at a tiny size on the CPU to record the programs its
engine builds (each round variant), rebuilds each one at the configuration's capacity over one
described v5e chip, and compiles it with the Pallas kernels compiled,
not interpreted.  Prints, per program, the compile time,
``memory_analysis()`` and the number of Mosaic kernels in it: what the
chip's compiler would refuse shows here at no chip time.  The plans are
those the tiny run chose, which can order a join differently from the
real size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _record(cls, built: list):
    class Recording(cls):
        def _build_round(self, pairs, **kw):
            built.append((pairs, kw))
            return super()._build_round(pairs, **kw)

    return Recording


def _shapes(eng, kw, sharding):
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    n, cap = eng.n_shards, eng.capacity

    def rows(p):
        return jax.ShapeDtypeStruct(
            (n, cap, eng._arities[p]), np.int32, sharding=sharding(P("data", None, None))
        )

    def vec():
        return jax.ShapeDtypeStruct((n,), np.int32, sharding=sharding(P("data")))

    # a materialisation's rounds take (rows, count, delta_lo) per predicate
    assert not kw.get("acc_mode"), "update rounds are not rehearsed"
    return [s for p in eng._preds for s in (rows(p), vec(), vec())]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--limit", type=int, default=0, help="compile at most this many")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import Mesh, NamedSharding

    from bench import harness
    from bench.tests.tiny import run, tiny_cell
    from repro.core.distributed import DistributedEngine
    from repro.kernels import tune

    cell = harness.load_cell(args.workload)
    built: list = []
    engines: list = []

    class Keep(_record(DistributedEngine, built)):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            engines.append(self)

    r = run(tiny_cell(args.workload), engine_cls=Keep)
    print(f"tiny run correct={r['correct']}; {len(built)} programs built", flush=True)

    # compile, do not interpret; block sizes are the tuner's defaults
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    os.environ["REPRO_PALLAS_INTERPRET"] = "0"
    table = os.path.join(tempfile.mkdtemp(), "pallas_tune.json")
    os.environ["REPRO_TUNE_CACHE"] = table
    entries = {
        f"{k}|int32|{1 << b}|cpu": v
        for k, v in tune.DEFAULTS.items() for b in range(8, 25)
    }
    with open(table, "w") as fh:
        json.dump({"version": tune.CACHE_VERSION, "jax": jax.__version__,
                   "entries": entries}, fh)

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    tiny = engines[0]
    eng = DistributedEngine(
        tiny.program, mesh, capacity=int(cell["config"]["capacity"]),
        use_pallas_kernels=bool(cell["config"]["use_pallas_kernels"]),
    )
    eng._preds, eng._arities = tiny._preds, tiny._arities
    todo = built[: args.limit] if args.limit else built
    for i, (pairs, kw) in enumerate(todo):
        variant = eng._build_round(pairs, **kw)
        shapes = _shapes(eng, kw, lambda spec: NamedSharding(mesh, spec))
        t0 = time.perf_counter()
        compiled = variant.fn.lower(*shapes).compile()
        mem = compiled.memory_analysis()
        print(json.dumps({
            "program": i, "rule_applications": len(pairs),
            "compile_s": round(time.perf_counter() - t0, 3),
            "temp_bytes": mem.temp_size_in_bytes,
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "mosaic_kernels": compiled.as_text().count("tpu_custom_call"),
        }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
