"""Block-pruned membership kernel — the semi-join / dedup hot spot.

TPU adaptation of the paper's priority-queue merge (semi-)join and merge
anti-join (Algorithms 3 and 6).  A serial two-pointer merge is O(n+m) but
has loop-carried dependencies that do not vectorise.  On TPU we instead
evaluate membership as a *block-pruned brute-force compare*:

* the probes ``a`` are sorted first (and the answers unsorted after)
  unless the caller already holds them sorted (``a_sorted=True``), so
  each ``a``-tile spans a narrow value range;
* the tile needs only the ``b``-blocks that hold the first ``b >= x`` of
  its smallest and largest probe ``x`` — a range found by a searchsorted
  outside the kernel.  Over sorted tiles these ranges overlap by at most
  one block, so the grid is a flat list of at most ``tiles + blocks``
  (tile, block) pairs, scalar-prefetched and read by the index maps;
* each pair compares an ``a``-tile against a ``b``-block all-pairs on
  the VPU (:func:`repro.kernels.tiles.fold_lanes`), with no
  data-dependent control flow inside.

Operands are ``(rows, 128)`` int32 tiles and the kernel writes int32
hits; the wrapper returns bools.  Used for: dedup anti-join
(``~member``), semi-join filters, and the distributed engine's
``dedup_against``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret
from .tiles import LANES, block_rows, fold_lanes, to_rows

DEFAULT_BLOCK_A = 1024
DEFAULT_BLOCK_B = 1024

_FIRST = 1   # grid step is its tile's first: zero the output block
_ACTIVE = 2  # grid step compares (steps past the pair list only idle)


def _member_kernel(tile_ref, blk_ref, flag_ref, a_ref, b_ref, o_ref):
    del tile_ref, blk_ref  # consumed by the index maps
    flag = flag_ref[pl.program_id(0)]

    @pl.when((flag & _FIRST) != 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((flag & _ACTIVE) != 0)
    def _compare():
        a = a_ref[...]
        hit = fold_lanes(
            (b_ref,), a.shape, jnp.zeros_like(a),
            lambda acc, r: acc | (a == r).astype(jnp.int32),
        )
        o_ref[...] = o_ref[...] | hit


def sorted_member(
    a: jax.Array,
    b_sorted: jax.Array,
    *,
    block_a: int = DEFAULT_BLOCK_A,
    block_b: int = DEFAULT_BLOCK_B,
    a_sorted: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """``out[i] = a[i] in b_sorted``; ``b_sorted`` ascending int32, ids
    below int32 max (the pad value).  ``a_sorted=True`` promises ``a``
    ascending too and skips the sort that would otherwise order the
    probes.  Block sizes round up to whole ``(8, 128)`` tiles.

    ``interpret=None`` resolves per backend/env (see
    :mod:`repro.kernels.backend`) — outside the jit, so the trace cache
    keys on the concrete bool.
    """
    return _sorted_member_jit(
        a,
        b_sorted,
        rows_a=block_rows(block_a),
        rows_b=block_rows(block_b),
        a_sorted=a_sorted,
        interpret=resolve_interpret(interpret),
    )


def _pair_schedule(a_tiles, b_p, n_b: int, bb: int):
    """The flat (tile, block) grid: per step the ``a``-tile, the
    ``b``-block and the :data:`_FIRST`/:data:`_ACTIVE` flags."""
    n_t = a_tiles.shape[0]
    ends = jnp.searchsorted(
        b_p, jnp.stack([a_tiles[:, 0], a_tiles[:, -1]], axis=1), side="left"
    )
    jlo = jnp.minimum(ends[:, 0] // bb, n_b - 1)
    jhi = jnp.minimum(ends[:, 1] // bb, n_b - 1) + 1
    cnt = jhi - jlo
    stop = jnp.cumsum(cnt)
    start = stop - cnt
    t = jnp.arange(n_t + n_b, dtype=jnp.int32)
    tile = jnp.minimum(jnp.searchsorted(stop, t, side="right"), n_t - 1)
    within = t - start[tile]
    active = t < stop[-1]
    # idle steps past the list revisit the last pair: no new DMA, no work
    blk = jnp.where(active, jlo[tile] + within, jhi[-1] - 1)
    flag = jnp.where(active, _ACTIVE, 0) | jnp.where(
        active & (within == 0), _FIRST, 0
    )
    return (
        tile.astype(jnp.int32), blk.astype(jnp.int32), flag.astype(jnp.int32)
    )


@functools.partial(
    jax.jit, static_argnames=("rows_a", "rows_b", "a_sorted", "interpret")
)
def _sorted_member_jit(
    a: jax.Array,
    b_sorted: jax.Array,
    *,
    rows_a: int,
    rows_b: int,
    a_sorted: bool,
    interpret: bool,
) -> jax.Array:
    n, m = a.shape[0], b_sorted.shape[0]
    if n == 0:
        return jnp.zeros((0,), dtype=bool)
    if m == 0:
        return jnp.zeros((n,), dtype=bool)
    ba, bb = rows_a * LANES, rows_b * LANES
    n_p, m_p = -(-n // ba) * ba, -(-m // bb) * bb
    n_t, n_b = n_p // ba, m_p // bb
    a_s = a.astype(jnp.int32)
    if not a_sorted:
        a_s, order = jax.lax.sort(
            (a_s, jnp.arange(n, dtype=jnp.int32)), num_keys=1, is_stable=False
        )
    a_p = to_rows(a_s, n_p)
    b_p = to_rows(b_sorted, m_p).reshape(m_p)
    tile, blk, flag = _pair_schedule(a_p.reshape(n_t, ba), b_p, n_b, bb)
    hits = pl.pallas_call(
        _member_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_t + n_b,),
            in_specs=[
                pl.BlockSpec((rows_a, LANES), lambda t, ti, bi, f: (ti[t], 0)),
                pl.BlockSpec((rows_b, LANES), lambda t, ti, bi, f: (bi[t], 0)),
            ],
            out_specs=pl.BlockSpec(
                (rows_a, LANES), lambda t, ti, bi, f: (ti[t], 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((n_p // LANES, LANES), jnp.int32),
        interpret=interpret,
    )(tile, blk, flag, a_p, b_p.reshape(m_p // LANES, LANES))
    hits = hits.reshape(n_p)[:n]
    if not a_sorted:  # back to the probes' own order
        _, hits = jax.lax.sort((order, hits), num_keys=1, is_stable=False)
    return hits != 0
