"""Mixture-of-Experts FFN: shared experts + routed top-k experts.

Dispatch is sort-based with a static per-expert capacity (TPU-friendly: no
dynamic shapes): tokens are ranked within their chosen expert, tokens past
capacity are dropped (standard GShard/Switch discipline), and expert FFNs
run as one batched einsum over the expert dimension, which shards over the
``model`` mesh axis (expert parallelism).  Router uses softmax-then-top-k
with an auxiliary load-balancing loss.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import dense_init
from . import sharding_policy
from .sharding_policy import constrain


def moe_init(key, cfg):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    params = {
        "router": dense_init(ks[0], (d, m.n_experts), scale=d**-0.5),
        "w_gate": dense_init(ks[1], (m.n_experts, d, m.d_expert_ff)),
        "w_up": dense_init(ks[2], (m.n_experts, d, m.d_expert_ff)),
        "w_down": dense_init(ks[3], (m.n_experts, m.d_expert_ff, d)),
    }
    if m.n_shared:
        f_sh = (m.d_shared_ff or m.d_expert_ff) * m.n_shared
        params["shared"] = {
            "w_gate": dense_init(ks[4], (d, f_sh)),
            "w_up": dense_init(ks[5], (d, f_sh)),
            "w_down": dense_init(jax.random.fold_in(key, 7), (f_sh, d)),
        }
    return params


def _capacity(n_tokens: int, cfg) -> int:
    m = cfg.moe
    cap = int(n_tokens * m.top_k * m.capacity_factor / m.n_experts)
    return max(8, cap + (-cap % 8))


def moe_apply(params, x, cfg):
    """x: (b, s, d) -> (y, aux_loss).

    Two implementations:

    * **EP shard_map path** (production): activations are replicated over
      the ``model`` axis by the surrounding TP layout, so each model-shard
      routes the *local* token block to its **own** expert slice and the
      only collective is one ``psum`` over ``model`` for the combine.
      This removes the cross-shard dispatch gather that GSPMD otherwise
      lowers into dot-shaped data movement (observed 50x FLOP blow-up —
      see EXPERIMENTS.md §Perf iteration 1).
    * **gather fallback** (no mesh policy / tiny batches): sort-based
      capacity dispatch in plain jnp.
    """
    policy = sharding_policy._POLICY
    if policy is not None and policy.get("model"):
        dp = policy.get("batch")
        dp_size = 1
        if dp:
            for a in (dp if isinstance(dp, tuple) else (dp,)):
                dp_size *= policy["sizes"].get(a, 1)
        if dp_size > 1 and x.shape[0] % dp_size == 0:
            return _moe_ep_shardmap(params, x, cfg, policy)
    return _moe_gather(params, x, cfg)


def _moe_gather(params, x, cfg):
    m = cfg.moe
    b, s, d = x.shape
    dtype = x.dtype
    n_tokens = b * s
    xt = x.reshape(n_tokens, d)

    logits = jnp.einsum("td,de->te", xt, params["router"].astype(dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, m.top_k)  # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9
    )

    # load-balancing auxiliary loss (Switch-style)
    me = probs.mean(axis=0)
    one_hot = jax.nn.one_hot(expert_ids[:, 0], m.n_experts, dtype=jnp.float32)
    ce = one_hot.mean(axis=0)
    aux = m.n_experts * jnp.sum(me * ce) * m.router_aux_weight

    # ---- sort-based dispatch with static capacity ---- #
    cap = _capacity(n_tokens, cfg)
    flat_expert = expert_ids.reshape(-1)  # (T*k,)
    flat_token = jnp.repeat(jnp.arange(n_tokens), m.top_k)
    flat_gate = gate_vals.reshape(-1)

    order = jnp.argsort(flat_expert, stable=True)
    se, stok, sgate = flat_expert[order], flat_token[order], flat_gate[order]
    # rank of each entry within its expert
    pos = jnp.arange(se.shape[0]) - jnp.searchsorted(se, se, side="left")
    keep = pos < cap
    slot = jnp.where(keep, se * cap + pos, m.n_experts * cap)  # overflow row

    # token index per (expert, capacity) slot; padded slots -> row n_tokens
    slot_token = jnp.full((m.n_experts * cap + 1,), n_tokens, dtype=jnp.int32)
    slot_token = slot_token.at[slot].set(
        jnp.where(keep, stok, n_tokens).astype(jnp.int32)
    )[: m.n_experts * cap]
    slot_gate = jnp.zeros((m.n_experts * cap + 1,), dtype=jnp.float32)
    slot_gate = slot_gate.at[slot].set(jnp.where(keep, sgate, 0.0))[
        : m.n_experts * cap
    ]

    x_pad = jnp.concatenate([xt, jnp.zeros((1, d), dtype=dtype)])
    # (E@model, cap, d): the gather across data-sharded tokens is the
    # dispatch all-to-all; experts live on the model axis (EP)
    xe = constrain(
        x_pad[slot_token].reshape(m.n_experts, cap, d), ("model", None, None)
    )

    g = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"].astype(dtype))
    u = jnp.einsum("ecd,edf->ecf", xe, params["w_up"].astype(dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * u
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(dtype))

    # combine: scatter-add expert outputs back to tokens, gate-weighted
    ye_flat = ye.reshape(m.n_experts * cap, d) * slot_gate[:, None].astype(dtype)
    y = jnp.zeros((n_tokens + 1, d), dtype=dtype)
    y = y.at[slot_token].add(ye_flat)[:n_tokens]

    if m.n_shared:
        y = y + _shared_experts(params, xt, dtype)

    return y.reshape(b, s, d), aux


def _shared_experts(params, xt, dtype):
    sh = params["shared"]
    g = jnp.einsum("td,df->tf", xt, sh["w_gate"].astype(dtype))
    u = jnp.einsum("td,df->tf", xt, sh["w_up"].astype(dtype))
    hh = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * u
    return jnp.einsum("tf,fd->td", hh, sh["w_down"].astype(dtype))


# --------------------------------------------------------------------- #
# expert-parallel shard_map path
# --------------------------------------------------------------------- #
def _moe_ep_shardmap(params, x, cfg, policy):
    """EP dispatch with shard-local routing (see moe_apply docstring).

    Experts are padded up to a multiple of the model-axis size; every
    model-shard owns a contiguous slice and processes only tokens routed
    to that slice.  Because each token's top-k experts spread over shards,
    the per-shard partial outputs are summed with one ``psum('model')`` —
    the single collective of the whole MoE block.
    """
    m = cfg.moe
    b, s, d = x.shape
    dtype = x.dtype
    dp = policy.get("batch")
    model_axis = policy["model"]
    nm = policy["sizes"].get(model_axis, 1)
    e_pad = -(-m.n_experts // nm) * nm
    e_loc = e_pad // nm

    dp_axes = dp if isinstance(dp, tuple) else (dp,)
    x_spec = P(dp, None, None)
    router_spec = P(None, None)
    expert_spec = P(model_axis, None, None)
    out_spec = P(dp, None, None)
    aux_spec = P()

    w_gate, w_up, w_down = params["w_gate"], params["w_up"], params["w_down"]
    if e_pad != m.n_experts:
        pad = [(0, e_pad - m.n_experts), (0, 0), (0, 0)]
        w_gate, w_up, w_down = (jnp.pad(w, pad) for w in (w_gate, w_up, w_down))

    def block(xb, router, wg, wu, wd):
        # xb: (b_loc, s, d) — replicated over `model`
        b_loc = xb.shape[0]
        t_loc = b_loc * s
        xt = xb.reshape(t_loc, d)
        logits = jnp.einsum("td,de->te", xt, router.astype(xt.dtype))
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, m.top_k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(axis=-1, keepdims=True), 1e-9
        )
        # aux loss (identical on every model shard)
        me = probs.mean(axis=0)
        ce = jax.nn.one_hot(expert_ids[:, 0], m.n_experts,
                            dtype=jnp.float32).mean(axis=0)
        aux = m.n_experts * jnp.sum(me * ce) * m.router_aux_weight
        # aux is computed from the model-replicated x, so it is provably
        # invariant over `model`; pmean over the data axes replicates it
        # fully (required by out_specs P())
        aux = jax.lax.pmean(aux, dp_axes)

        # shard-local expert slice
        shard = jax.lax.axis_index(model_axis)
        e_lo = shard * e_loc
        flat_expert = expert_ids.reshape(-1)
        flat_token = jnp.repeat(jnp.arange(t_loc), m.top_k)
        flat_gate = gate_vals.reshape(-1)
        mine = (flat_expert >= e_lo) & (flat_expert < e_lo + e_loc)
        local_e = jnp.where(mine, flat_expert - e_lo, e_loc)

        cap = max(8, int(t_loc * m.top_k * m.capacity_factor / m.n_experts))
        cap += -cap % 8
        order = jnp.argsort(local_e, stable=True)
        se, stok, sgate = local_e[order], flat_token[order], flat_gate[order]
        pos = jnp.arange(se.shape[0]) - jnp.searchsorted(se, se, side="left")
        keep = (pos < cap) & (se < e_loc)
        slot = jnp.where(keep, se * cap + pos, e_loc * cap)

        slot_token = jnp.full((e_loc * cap + 1,), t_loc, dtype=jnp.int32)
        slot_token = slot_token.at[slot].set(
            jnp.where(keep, stok, t_loc).astype(jnp.int32)
        )[: e_loc * cap]
        slot_gate = jnp.zeros((e_loc * cap + 1,), dtype=jnp.float32)
        slot_gate = slot_gate.at[slot].set(
            jnp.where(keep, sgate, 0.0)
        )[: e_loc * cap]

        x_pad = jnp.concatenate([xt, jnp.zeros((1, d), dtype=xt.dtype)])
        xe = x_pad[slot_token].reshape(e_loc, cap, d)  # local gather
        g = jnp.einsum("ecd,edf->ecf", xe, wg.astype(xt.dtype))
        u = jnp.einsum("ecd,edf->ecf", xe, wu.astype(xt.dtype))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xt.dtype) * u
        ye = jnp.einsum("ecf,efd->ecd", h, wd.astype(xt.dtype))
        ye_flat = ye.reshape(e_loc * cap, d) * slot_gate[:, None].astype(xt.dtype)
        y = jnp.zeros((t_loc + 1, d), dtype=xt.dtype)
        y = y.at[slot_token].add(ye_flat)[:t_loc]
        # combine across expert shards — the one collective
        y = jax.lax.psum(y, model_axis)
        return y.reshape(b_loc, s, d), aux

    mapped = jax.shard_map(
        block,
        in_specs=(x_spec, router_spec, expert_spec, expert_spec, expert_spec),
        out_specs=(out_spec, aux_spec),
    )
    y, aux = mapped(x, params["router"], w_gate, w_up, w_down)

    if m.n_shared:
        xt = x.reshape(b * s, d)
        y = y + _shared_experts(params, xt, dtype).reshape(b, s, d)
    return y, aux
