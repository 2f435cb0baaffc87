"""Distributed engine on a REAL multi-shard mesh (4 devices): exercises
the hash-partition + all_to_all exchange path — semi-naive delta rounds,
planner-keyed exchange elision, and the incremental delta exchange — not
just the 1-shard degenerate case.  Subprocess-isolated (forced device
count)."""

import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
from jax.sharding import Mesh

from repro.core import flat_seminaive
from repro.core.distributed import DistributedEngine
from repro.core.generators import chain, lubm_like, paper_example

mesh = Mesh(np.asarray(jax.devices()).reshape(4), ("data",))


def mirror_exact(eng):
    # the host mirror of counts and watermarks equals the device's
    for p in eng._preds:
        cnt, lo = eng._mirror[p]
        assert cnt.shape == (4,) and cnt.dtype == np.int32, p
        assert (cnt == np.asarray(eng._state[p][1])).all(), p
        assert (lo == np.asarray(eng._state[p][2])).all(), p
        assert eng._counts[p] == int(cnt.sum()), p


engines = {}
datasets = {}
for name, gen in [
    ("chain", lambda: chain(15)),
    ("paper", lambda: paper_example(4, 3)),
    ("lubm", lambda: lubm_like(n_dept=4, n_students=50, n_courses=8)),
]:
    program, dataset, _ = gen()
    rules = [r for r in program if len(r.body) <= 2]
    program = type(program)(rules)
    want = {p: {tuple(map(int, r)) for r in rows}
            for p, rows in flat_seminaive(program, dataset).items()}
    eng = DistributedEngine(program, mesh, capacity=1 << 11)
    got = eng.materialise(dataset)
    got = {p: {tuple(map(int, r)) for r in rows}
           for p, rows in got.items() if rows.shape[0]}
    assert got == want, f"{name}: mismatch"
    mirror_exact(eng)
    # one wait a round, on its packed block, and one for the pull
    assert eng.stats.host_syncs == (
        eng.stats.rounds + eng.stats.exchange_regrows + 1
    ), name
    engines[name], datasets[name] = eng, dataset
    print(f"{name} OK rounds={eng.rounds} "
          f"skipped={eng.stats.rule_applications_skipped} "
          f"exchanges={eng.stats.exchanges} "
          f"elided={eng.stats.exchanges_skipped}")

# semi-naive skips work and the planner elides aligned exchanges at 4 shards
assert engines["lubm"].stats.rule_applications_skipped > 0
assert engines["chain"].stats.exchanges_skipped > 0
assert engines["chain"].stats.exchanges > 0

# incremental deltas through the 4-shard exchange: delete a chain edge
# (DRed overdelete/rederive), re-add it, compare against re-materialisation
eng, dataset = engines["chain"], datasets["chain"]
program = eng.program
dels = {"edge": np.asarray(dataset["edge"][5:7], np.int64)}
st = eng.apply(deletions=dels)
assert st.n_overdeleted > 0 and st.n_deleted > 0
kept = {"edge": np.asarray(
    [r for r in dataset["edge"].tolist()
     if tuple(r) not in {tuple(x) for x in dels["edge"].tolist()}],
    np.int64)}
mirror_exact(eng)
eng.check_integrity(flat_seminaive(program, kept))
eng.apply(additions=dels)
mirror_exact(eng)
eng.check_integrity(flat_seminaive(program, dataset))
print("APPLY OK")
print("MULTISHARD OK")
"""


def test_distributed_engine_four_shards():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=900,
    )
    assert out.returncode == 0, f"stdout={out.stdout}\nstderr={out.stderr[-3000:]}"
    assert "MULTISHARD OK" in out.stdout
