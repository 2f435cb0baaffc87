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

Keys of two int32 words — the distributed engine's binary facts once an
id reaches ``2**15`` — go through :func:`sorted_member_wide`: the same
grid over ``(rows, 128)`` tiles of the high and of the low words, block
ranges found by a lexicographic binary search
(:func:`searchsorted_words`), and a hit where both words are equal.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret
from .tiles import LANES, block_rows, fold_lanes, to_rows

DEFAULT_BLOCK_A = 1024
DEFAULT_BLOCK_B = 1024

_FIRST = 1   # grid step is its tile's first: zero the output block
_ACTIVE = 2  # grid step compares (steps past the pair list only idle)


def _member_kernel(tile_ref, blk_ref, flag_ref, *refs):
    """``refs``: the probe tiles of each key word, then the table blocks
    of each word, then the output; a hit needs every word equal."""
    del tile_ref, blk_ref  # consumed by the index maps
    *ins, o_ref = refs
    words = len(ins) // 2
    flag = flag_ref[pl.program_id(0)]

    @pl.when((flag & _FIRST) != 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((flag & _ACTIVE) != 0)
    def _compare():
        a = [r[...] for r in ins[:words]]

        def step(acc, *rows):
            eq = a[0] == rows[0]
            for x, r in zip(a[1:], rows[1:]):
                eq = eq & (x == r)
            return acc | eq.astype(jnp.int32)

        hit = fold_lanes(ins[words:], a[0].shape, jnp.zeros_like(a[0]), step)
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


def sorted_member_wide(
    a: tuple[jax.Array, jax.Array],
    b_sorted: tuple[jax.Array, jax.Array],
    *,
    block_a: int = DEFAULT_BLOCK_A,
    block_b: int = DEFAULT_BLOCK_B,
    a_sorted: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """:func:`sorted_member` over two-word keys: ``a`` and ``b_sorted``
    are ``(high, low)`` int32 word pairs, ``b_sorted`` ascending in the
    lexicographic order of the pairs (``a`` too, with ``a_sorted``), and
    ``out[i]`` is true when some table key equals ``(a[0][i], a[1][i])``
    in both words.  Its own jit, so that traces tell it apart from the
    one-word kernel."""
    return _sorted_member_wide_jit(
        *a,
        *b_sorted,
        rows_a=block_rows(block_a),
        rows_b=block_rows(block_b),
        a_sorted=a_sorted,
        interpret=resolve_interpret(interpret),
    )


def searchsorted_words(table, probes) -> jax.Array:
    """For each probe the first ``i`` with ``table[i] >= probe`` (side
    left), over keys of one or more int32 words compared
    lexicographically: ``table`` and ``probes`` are tuples of words, the
    table ascending.  One word is ``jnp.searchsorted``; more are its
    binary search with the comparison taken word by word."""
    if len(table) == 1:  # side left, jnp's default
        return jnp.searchsorted(table[0], probes[0])
    m = table[0].shape[0]

    def probe_le(row):  # probe <= table row, from the last word up
        le = probes[-1] <= row[-1]
        for p, t in zip(reversed(probes[:-1]), reversed(row[:-1])):
            le = (p < t) | ((p == t) & le)
        return le

    def halve(_, bounds):
        low, high = bounds
        mid = ((low.astype(jnp.uint32) + high.astype(jnp.uint32)) // 2).astype(
            jnp.int32
        )
        left = probe_le(tuple(w[mid] for w in table))
        return jnp.where(left, low, mid), jnp.where(left, mid, high)

    shape = probes[0].shape
    _, high = jax.lax.fori_loop(
        0, int(np.ceil(np.log2(m + 1))), halve,
        (jnp.zeros(shape, jnp.int32), jnp.full(shape, m, jnp.int32)),
    )
    return high


def _pair_schedule(a_tiles, b_p, n_b: int, bb: int):
    """The flat (tile, block) grid: per step the ``a``-tile, the
    ``b``-block and the :data:`_FIRST`/:data:`_ACTIVE` flags.  ``a_tiles``
    and ``b_p`` hold one array per key word."""
    n_t = a_tiles[0].shape[0]
    ends = searchsorted_words(
        b_p, tuple(jnp.stack([t[:, 0], t[:, -1]], axis=1) for t in a_tiles)
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


def _member(a, b_sorted, *, rows_a, rows_b, a_sorted, interpret) -> jax.Array:
    """The membership of keys of ``len(a)`` words (tuples of 1-D int32
    arrays), traced inside one of the two jits below."""
    n, m = a[0].shape[0], b_sorted[0].shape[0]
    if n == 0:
        return jnp.zeros((0,), dtype=bool)
    if m == 0:
        return jnp.zeros((n,), dtype=bool)
    words = len(a)
    ba, bb = rows_a * LANES, rows_b * LANES
    n_p, m_p = -(-n // ba) * ba, -(-m // bb) * bb
    n_t, n_b = n_p // ba, m_p // bb
    a_s = tuple(w.astype(jnp.int32) for w in a)
    if not a_sorted:
        *a_s, order = jax.lax.sort(
            (*a_s, jnp.arange(n, dtype=jnp.int32)), num_keys=words,
            is_stable=False,
        )
    a_p = [to_rows(w, n_p) for w in a_s]
    b_p = [to_rows(w, m_p).reshape(m_p) for w in b_sorted]
    tile, blk, flag = _pair_schedule(
        tuple(w.reshape(n_t, ba) for w in a_p), tuple(b_p), n_b, bb
    )
    a_spec = pl.BlockSpec((rows_a, LANES), lambda t, ti, bi, f: (ti[t], 0))
    b_spec = pl.BlockSpec((rows_b, LANES), lambda t, ti, bi, f: (bi[t], 0))
    hits = pl.pallas_call(
        _member_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_t + n_b,),
            in_specs=[a_spec] * words + [b_spec] * words,
            out_specs=pl.BlockSpec(
                (rows_a, LANES), lambda t, ti, bi, f: (ti[t], 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((n_p // LANES, LANES), jnp.int32),
        interpret=interpret,
    )(tile, blk, flag, *a_p, *(w.reshape(m_p // LANES, LANES) for w in b_p))
    hits = hits.reshape(n_p)[:n]
    if not a_sorted:  # back to the probes' own order
        _, hits = jax.lax.sort((order, hits), num_keys=1, is_stable=False)
    return hits != 0


_STATIC = ("rows_a", "rows_b", "a_sorted", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _sorted_member_jit(
    a: jax.Array,
    b_sorted: jax.Array,
    *,
    rows_a: int,
    rows_b: int,
    a_sorted: bool,
    interpret: bool,
) -> jax.Array:
    return _member(
        (a,), (b_sorted,), rows_a=rows_a, rows_b=rows_b, a_sorted=a_sorted,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=_STATIC)
def _sorted_member_wide_jit(
    a_hi: jax.Array,
    a_lo: jax.Array,
    b_hi: jax.Array,
    b_lo: jax.Array,
    *,
    rows_a: int,
    rows_b: int,
    a_sorted: bool,
    interpret: bool,
) -> jax.Array:
    return _member(
        (a_hi, a_lo), (b_hi, b_lo), rows_a=rows_a, rows_b=rows_b,
        a_sorted=a_sorted, interpret=interpret,
    )
