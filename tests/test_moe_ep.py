"""EP shard_map MoE vs gather-path equivalence on a multi-device CPU mesh.

Runs in a subprocess so the forced device count never leaks into other
tests (jax locks the device count at first init).
"""

import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs import get_config
from repro.models import moe
from repro.models.sharding_policy import clear_policy, set_policy_from_mesh
from dataclasses import replace

cfg = get_config("qwen2-moe-a2.7b", smoke=True)
# generous capacity so neither path drops tokens -> exact comparison
cfg = replace(cfg, moe=replace(cfg.moe, capacity_factor=8.0))

key = jax.random.PRNGKey(0)
params = moe.moe_init(key, cfg)
x = (jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model)) * 0.1
     ).astype(jnp.bfloat16)

clear_policy()
y_ref, aux_ref = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg))(params, x)

mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2), ("data", "model"))
set_policy_from_mesh(mesh)
with jax.set_mesh(mesh):
    y_ep, aux_ep = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg))(params, x)

np.testing.assert_allclose(
    np.asarray(y_ref, np.float32), np.asarray(y_ep, np.float32),
    rtol=0.05, atol=0.05,
)
# aux differs slightly: per-data-shard load statistics vs global (mean of
# products != product of means); it is a regularizer, 5% is fine
np.testing.assert_allclose(float(aux_ref), float(aux_ep), rtol=5e-2)

# gradient flows through the EP path
with jax.set_mesh(mesh):
    def loss(p):
        y, aux = moe.moe_apply(p, x, cfg)
        return jnp.sum(y.astype(jnp.float32) ** 2) + aux
    g = jax.jit(jax.grad(loss))(params)
leaves = jax.tree_util.tree_leaves(g)
assert all(np.isfinite(np.asarray(l, np.float32)).all() for l in leaves)
assert any(float(jnp.abs(l.astype(jnp.float32)).max()) > 0 for l in leaves)
print("EP==GATHER OK")
"""


def test_moe_ep_matches_gather():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert out.returncode == 0, f"stdout={out.stdout}\nstderr={out.stderr[-3000:]}"
    assert "EP==GATHER OK" in out.stdout
