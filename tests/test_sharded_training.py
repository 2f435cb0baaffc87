"""Numerical equivalence of sharded training: the FSDP x TP train step on
a real 2x2 device mesh must produce the same loss trajectory as the
single-device step (same params, same batches).  Subprocess-isolated."""

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
from repro.data import DataConfig, SyntheticCorpus
from repro.launch.sharding import batch_shardings, state_shardings
from repro.models.sharding_policy import clear_policy, set_policy_from_mesh
from repro.train import TrainConfig, init_train_state, make_train_step

cfg = get_config("llama3.2-1b", smoke=True)
tcfg = TrainConfig(total_steps=6, warmup_steps=1)
dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)
corpus = SyntheticCorpus(dcfg)
batches = [{k: jnp.asarray(v) for k, v in corpus.batch(s).items()}
           for s in range(4)]

def run(mesh=None):
    if mesh is None:
        clear_policy()
        state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
        step = jax.jit(make_train_step(cfg, tcfg))
        losses = []
        for b in batches:
            state, m = step(state, b)
            losses.append(float(m["loss"]))
        return losses
    set_policy_from_mesh(mesh)
    with jax.set_mesh(mesh):
        state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
        st_sh = state_shardings(state, mesh)
        state = jax.tree_util.tree_map(jax.device_put, state, st_sh)
        step = jax.jit(make_train_step(cfg, tcfg))
        losses = []
        for b in batches:
            b_sh = batch_shardings(b, mesh)
            b = jax.tree_util.tree_map(jax.device_put, b, b_sh)
            state, m = step(state, b)
            losses.append(float(m["loss"]))
        return losses

ref = run()
mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2), ("data", "model"))
got = run(mesh)
print("single:", [round(l, 4) for l in ref])
print("2x2   :", [round(l, 4) for l in got])
for a, b in zip(ref, got):
    assert abs(a - b) < 0.05, f"trajectory diverged: {ref} vs {got}"
print("SHARDED==SINGLE OK")
"""


def test_sharded_training_matches_single_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=900,
    )
    assert out.returncode == 0, f"stdout={out.stdout}\nstderr={out.stderr[-3000:]}"
    assert "SHARDED==SINGLE OK" in out.stdout
