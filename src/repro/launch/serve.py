"""Batched serving driver: prefill + decode with a KV/SSM cache.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
        --batch 4 --prompt-len 32 --gen-len 32

Implements the standard serving loop: a batch of requests is prefilled
token-by-token into the cache (teacher-forced), then decoded greedily.
On a pod the same step functions run under the production mesh with the
cache shardings of ``launch.sharding``.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..models.model import Model
from .mesh import make_host_mesh
from ..models.sharding_policy import set_policy_from_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = make_host_mesh(1, 1)
    set_policy_from_mesh(mesh)
    model = Model(cfg)

    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(args.seed))
        max_len = args.prompt_len + args.gen_len
        cache = model.init_cache(args.batch, max_len)
        step = jax.jit(model.decode_step)

        prompts = jax.random.randint(
            jax.random.PRNGKey(args.seed + 1),
            (args.batch, args.prompt_len),
            0,
            cfg.vocab_size,
            jnp.int32,
        )

        # prefill: feed prompt tokens through the decode path
        t0 = time.time()
        logits = None
        for t in range(args.prompt_len):
            logits, cache = step(params, prompts[:, t : t + 1], cache,
                                 jnp.int32(t))
        t_prefill = time.time() - t0

        # greedy decode
        t0 = time.time()
        token = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        generated = [token]
        for t in range(args.prompt_len, max_len - 1):
            logits, cache = step(params, token, cache, jnp.int32(t))
            token = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            generated.append(token)
        t_decode = time.time() - t0

    out = np.concatenate([np.asarray(t) for t in generated], axis=1)
    n_tok = out.shape[0] * out.shape[1]
    print(f"prefill: {args.prompt_len} steps in {t_prefill:.2f}s")
    print(
        f"decode:  {out.shape[1]} steps x batch {args.batch} = {n_tok} tokens "
        f"in {t_decode:.2f}s ({n_tok / max(t_decode, 1e-9):.1f} tok/s)"
    )
    print("sample token ids:", out[0, :16].tolist())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
