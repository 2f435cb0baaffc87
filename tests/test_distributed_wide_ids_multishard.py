"""Two-word fact keys on a real four-device mesh: ``materialise`` and
``apply`` at ids remapped past ``2**15`` (as in
``test_distributed_wide_ids``), with the keys hashed, exchanged and
merged across shards.  Subprocess-isolated (forced device count)."""

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
import numpy as np
import jax
from jax.sharding import Mesh

sys.path.insert(0, %(tests)r)
from test_distributed_wide_ids import KBS, as_sets, wide_kb
from repro.core import flat_seminaive
from repro.core.distributed import DistributedEngine
from repro.incremental import IncrementalStore

mesh = Mesh(np.asarray(jax.devices()).reshape(4), ("data",))
program, dataset = wide_kb(dict(KBS)[%(kb)r])
eng = DistributedEngine(program, mesh, capacity=1 << 9,
                        use_pallas_kernels=%(pallas)r)
got = eng.materialise(dataset)
assert eng.stats.key_words == 2
assert as_sets(got) == as_sets(flat_seminaive(program, dataset))
inc = IncrementalStore(program)
inc.load(dataset)
pred = max(sorted(dataset), key=lambda p: len(dataset[p]))
rows = np.asarray(dataset[pred]).reshape(len(dataset[pred]), -1)
assert rows.shape[1] == 2
# delete three facts, add three reversed ones
dels, adds = {pred: rows[:3]}, {pred: np.flip(rows[3:6], axis=1).copy()}
eng.apply(additions=adds, deletions=dels)
inc.apply(additions=adds, deletions=dels)
eng.check_integrity(inc)
assert eng.stats.key_words == 2 and eng.stats.exchanges > 0
print("OK", flush=True)
"""


@pytest.mark.parametrize(
    "kb,pallas", [("lubm", True), ("chain", False)], ids=["lubm-pallas", "chain-jnp"]
)
def test_wide_ids_on_four_devices(kb, pallas):
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    src = os.path.join(os.path.dirname(here), "src")
    env["PYTHONPATH"] = os.pathsep.join([src, env.get("PYTHONPATH", "")]).strip(
        os.pathsep
    )
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT % {"tests": here, "kb": kb, "pallas": pallas}],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    assert "OK" in out.stdout
