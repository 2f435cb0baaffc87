"""RLE expansion kernel — unfolding leaf meta-constants.

The paper stores leaf meta-constants run-length encoded (``d * n``); every
join/dedup unfolds them.  A serial decoder is memory-bound and sequential;
on TPU we decode positionally with a compare-and-sum and no gather:
output position ``i`` takes the value of the one run whose span
``[start, end)`` holds it, ``out[i] = sum_k vals[k] * [start_k <= i < end_k]``.

The grid is (output tiles) x (blocks of runs), all ``(rows, 128)`` int32
tiles.  Run starts ascend, so each run block covers one span of output
positions; the spans are scalar-prefetched into SMEM and a block whose
span misses the output tile is skipped, which leaves O(1) compare blocks
per tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret
from .tiles import LANES, block_rows, fold_lanes, to_rows

DEFAULT_BLOCK_OUT = 1024
_BLOCK_RUNS = 1024


def _rle_kernel(smin_ref, emax_ref, s_ref, e_ref, v_ref, o_ref):
    i, j = pl.program_id(0), pl.program_id(1)
    shape = o_ref.shape
    lo = i * (shape[0] * LANES)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(
        jnp.logical_and(smin_ref[j] < lo + shape[0] * LANES, emax_ref[j] > lo)
    )
    def _expand():
        idx = (
            lo
            + jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        )
        o_ref[...] += fold_lanes(
            (s_ref, e_ref, v_ref), shape, jnp.zeros(shape, jnp.int32),
            lambda acc, s, e, v: acc + jnp.where((idx >= s) & (idx < e), v, 0),
        )


def rle_expand(
    run_values: jax.Array,
    run_counts: jax.Array,
    *,
    total: int,
    block_out: int = DEFAULT_BLOCK_OUT,
    interpret: bool | None = None,
) -> jax.Array:
    """Expand RLE runs into ``total`` output elements.

    ``total`` must equal ``run_counts.sum()`` (static, host-known — meta-
    constant lengths are part of the representation).  ``block_out``
    rounds up to whole ``(8, 128)`` tiles.
    ``interpret=None`` resolves per backend/env outside the jit.
    """
    return _rle_expand_jit(
        run_values,
        run_counts,
        total=total,
        rows_out=block_rows(block_out),
        interpret=resolve_interpret(interpret),
    )


@functools.partial(jax.jit, static_argnames=("total", "rows_out", "interpret"))
def _rle_expand_jit(
    run_values: jax.Array,
    run_counts: jax.Array,
    *,
    total: int,
    rows_out: int,
    interpret: bool,
) -> jax.Array:
    r = run_values.shape[0]
    if total == 0 or r == 0:
        return jnp.zeros((0,), dtype=jnp.int32)
    bo = rows_out * LANES
    rows_r = block_rows(_BLOCK_RUNS)
    br = rows_r * LANES
    out_len, r_p = -(-total // bo) * bo, -(-r // br) * br
    ends = jnp.cumsum(run_counts.astype(jnp.int32))
    # pad runs span [int32 max, int32 max): empty, they never match
    s_p = to_rows(ends - run_counts.astype(jnp.int32), r_p)
    e_p = to_rows(ends, r_p)
    v_p = to_rows(run_values, r_p, fill=0)
    run_block = pl.BlockSpec((rows_r, LANES), lambda i, j, *_: (j, 0))
    out = pl.pallas_call(
        _rle_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(out_len // bo, r_p // br),
            in_specs=[run_block, run_block, run_block],
            out_specs=pl.BlockSpec((rows_out, LANES), lambda i, j, *_: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((out_len // LANES, LANES), jnp.int32),
        interpret=interpret,
    )(
        s_p.reshape(r_p // br, br)[:, 0],
        e_p.reshape(r_p // br, br)[:, -1],
        s_p,
        e_p,
        v_p,
    )
    return out.reshape(out_len)[:total]
