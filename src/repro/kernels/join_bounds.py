"""Join-bounds kernel — the cross-join (Algorithm 5) group locator.

For every left key the cross-join needs the span ``[lo, hi)`` of matching
rows in the key-sorted right side:

    lo[i] = #{k : r[k] <  l[i]}        hi[i] = #{k : r[k] <= l[i]}

A serial merge computes these with two pointers; on TPU we accumulate the
counts blockwise over the sorted right side, with a three-way prune per
(left-tile x right-block) on bounds scalar-prefetched into SMEM:

* ``rmax <  lmin``  -> the whole block is below the tile: add BLOCK to
  both counters without comparing,
* ``rmin >  lmax``  -> the whole block is above: skip entirely,
* otherwise        -> an all-pairs compare-count on the VPU
  (:func:`repro.kernels.tiles.fold_lanes`).

Operands are ``(rows, 128)`` int32 tiles.  For sorted inputs only O(1)
blocks per tile take the compare path, so the work is O(n + m) with
machine-width parallelism — this is the paper's merge retimed for a
vector unit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret
from .tiles import LANES, block_rows, fold_lanes, to_rows

DEFAULT_BLOCK_L = 1024
DEFAULT_BLOCK_R = 1024


def _bounds_kernel(
    lmin_ref, lmax_ref, rmin_ref, rmax_ref, l_ref, r_ref, lo_ref, hi_ref
):
    i, j = pl.program_id(0), pl.program_id(1)
    lmin, lmax = lmin_ref[i], lmax_ref[i]
    rmin, rmax = rmin_ref[j], rmax_ref[j]

    @pl.when(j == 0)
    def _init():
        lo_ref[...] = jnp.zeros_like(lo_ref)
        hi_ref[...] = jnp.zeros_like(hi_ref)

    @pl.when(rmax < lmin)
    def _all_below():
        n = r_ref.shape[0] * LANES
        lo_ref[...] += n
        hi_ref[...] += n

    @pl.when(jnp.logical_and(rmax >= lmin, rmin <= lmax))
    def _compare():
        l = l_ref[...]
        lo, hi = fold_lanes(
            (r_ref,), l.shape, (jnp.zeros_like(l), jnp.zeros_like(l)),
            lambda acc, r: (
                acc[0] + (r < l).astype(jnp.int32),
                acc[1] + (r <= l).astype(jnp.int32),
            ),
        )
        lo_ref[...] += lo
        hi_ref[...] += hi


def join_bounds(
    l_keys: jax.Array,
    r_sorted: jax.Array,
    *,
    block_l: int = DEFAULT_BLOCK_L,
    block_r: int = DEFAULT_BLOCK_R,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Return (lo, hi) spans of each left key in the sorted right keys.
    Block sizes round up to whole ``(8, 128)`` tiles.

    ``interpret=None`` resolves per backend/env outside the jit."""
    return _join_bounds_jit(
        l_keys,
        r_sorted,
        rows_l=block_rows(block_l),
        rows_r=block_rows(block_r),
        interpret=resolve_interpret(interpret),
    )


@functools.partial(jax.jit, static_argnames=("rows_l", "rows_r", "interpret"))
def _join_bounds_jit(
    l_keys: jax.Array,
    r_sorted: jax.Array,
    *,
    rows_l: int,
    rows_r: int,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    n, m = l_keys.shape[0], r_sorted.shape[0]
    if n == 0:
        z = jnp.zeros((0,), dtype=jnp.int32)
        return z, z
    if m == 0:
        z = jnp.zeros((n,), dtype=jnp.int32)
        return z, z
    bl, br = rows_l * LANES, rows_r * LANES
    n_p, m_p = -(-n // bl) * bl, -(-m // br) * br
    l_p = to_rows(l_keys, n_p)
    r_p = to_rows(r_sorted, m_p)
    l_tiles = l_p.reshape(n_p // bl, bl)
    r_blocks = r_p.reshape(m_p // br, br)
    tile = pl.BlockSpec((rows_l, LANES), lambda i, j, *_: (i, 0))
    lo, hi = pl.pallas_call(
        _bounds_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_p // bl, m_p // br),
            in_specs=[
                tile,
                pl.BlockSpec((rows_r, LANES), lambda i, j, *_: (j, 0)),
            ],
            out_specs=[tile, tile],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(l_p.shape, jnp.int32),
            jax.ShapeDtypeStruct(l_p.shape, jnp.int32),
        ],
        interpret=interpret,
    )(
        l_tiles.min(axis=1),
        l_tiles.max(axis=1),
        r_blocks[:, 0],
        r_blocks[:, -1],
        l_p,
        r_p,
    )
    return lo.reshape(n_p)[:n], hi.reshape(n_p)[:n]
