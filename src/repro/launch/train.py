"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
        --steps 300 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Wires every substrate together: config -> model -> data pipeline (synthetic
or KB-linearised) -> sharded train step -> checkpointing -> fault-tolerant
supervision loop.  On this CPU container it trains reduced configs; on a
pod the same driver runs the full configs (the mesh adapts to the device
count).
"""

from __future__ import annotations

import argparse
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..data import DataConfig, SyntheticCorpus, TokenStream, linearise_materialisation
from ..optim import AdamWConfig
from ..train import (
    AsyncCheckpointer,
    TrainConfig,
    init_train_state,
    latest_step,
    load_checkpoint,
    make_train_step,
)
from .mesh import make_host_mesh
from ..models.sharding_policy import set_policy_from_mesh


def build_kb_stream(cfg, data_cfg: DataConfig):
    """Materialise a synthetic KB with the CompMat engine and linearise it
    into the training stream (the paper's engine as the data substrate)."""
    from ..core import CMatEngine
    from ..core.generators import lubm_like

    program, dataset, _ = lubm_like(n_dept=20, n_students=400, n_courses=40)
    engine = CMatEngine(program)
    engine.load(dataset)
    engine.materialise()
    tokens = linearise_materialisation(engine, cfg.vocab_size)
    return TokenStream(tokens, data_cfg)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--kb-corpus", action="store_true",
                    help="train on the CompMat-materialised KB stream")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = make_host_mesh(1, 1)
    set_policy_from_mesh(mesh)

    train_cfg = TrainConfig(
        optimizer=AdamWConfig(lr=args.lr),
        microbatches=args.microbatches,
        grad_compression=args.grad_compression,
        warmup_steps=max(args.steps // 20, 1),
        total_steps=args.steps,
    )
    data_cfg = DataConfig(
        vocab_size=cfg.vocab_size,
        seq_len=args.seq,
        global_batch=args.batch,
        seed=args.seed,
    )
    corpus = (
        build_kb_stream(cfg, data_cfg)
        if args.kb_corpus
        else SyntheticCorpus(data_cfg)
    )

    with jax.set_mesh(mesh):
        state = init_train_state(jax.random.PRNGKey(args.seed), cfg, train_cfg)
        step_fn = jax.jit(make_train_step(cfg, train_cfg))

        start = 0
        ckpt = None
        if args.ckpt_dir:
            ckpt = AsyncCheckpointer(args.ckpt_dir)
            if latest_step(args.ckpt_dir) is not None:
                state, start = load_checkpoint(args.ckpt_dir, state)
                start += 1
                print(f"restored checkpoint, resuming at step {start}")

        losses = []
        t0 = time.time()
        for step in range(start, args.steps):
            batch = {
                k: jnp.asarray(v) for k, v in corpus.batch(step).items()
            }
            if cfg.family == "vlm":
                batch["vision_embeds"] = jnp.zeros(
                    (args.batch, 16, cfg.d_model), jnp.bfloat16
                )
            if cfg.family == "encdec":
                batch["src_embeds"] = jnp.zeros(
                    (args.batch, 2 * args.seq, cfg.d_model), jnp.bfloat16
                )
            state, metrics = step_fn(state, batch)
            losses.append(float(metrics["loss"]))
            if step % args.log_every == 0:
                dt = time.time() - t0
                print(
                    f"step {step:5d}  loss {losses[-1]:8.4f}  "
                    f"gnorm {float(metrics['grad_norm']):7.3f}  "
                    f"({dt:.1f}s)", flush=True,
                )
            if ckpt and step % args.ckpt_every == 0 and step > start:
                ckpt.save(step, state)
        if ckpt:
            ckpt.wait()
            ckpt.save(args.steps - 1, state)
            ckpt.wait()

    first = np.mean(losses[: max(len(losses) // 10, 1)])
    last = np.mean(losses[-max(len(losses) // 10, 1):])
    print(f"\ndone: loss {first:.4f} -> {last:.4f} over {len(losses)} steps")
    return 0 if last < first else 1


if __name__ == "__main__":
    raise SystemExit(main())
