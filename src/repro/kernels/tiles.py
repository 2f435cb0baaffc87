"""``(rows, 128)`` int32 tiles shared by the blocked Pallas kernels.

Mosaic lays a 32-bit array out in ``(8, 128)`` vector tiles, refuses
1-D blocks whose size is not a whole number of them, and cannot read a
scalar out of a vector.  So the blocked kernels (``sorted_member``,
``join_bounds``, ``rle_expand``) keep every operand as a 2-D
``(rows, 128)`` array with ``rows`` a multiple of 8, and take an
all-pairs comparison of two blocks without a gather: each row of the
second block is broadcast over the first and rotated through its 128
lanes (:func:`fold_lanes`).  Range bounds that decide which blocks meet
are computed outside the kernel and scalar-prefetched into SMEM.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
#: elements in one (8, 128) int32 vector tile
TILE = LANES * SUBLANES
#: pad value; callers guarantee real ids stay below it
SENTINEL = jnp.iinfo(jnp.int32).max

_UNROLL = 8  # lane rotations per loop iteration (Mosaic unrolls fully or not at all)


def block_rows(block: int) -> int:
    """Rows of a ``(rows, 128)`` block holding ``block`` elements, rounded
    up to whole ``(8, 128)`` tiles (so any requested size lowers)."""
    return SUBLANES * max(1, -(-int(block) // TILE))


def to_rows(x: jax.Array, n: int, fill=SENTINEL) -> jax.Array:
    """Pad 1-D ``x`` to ``n`` elements with ``fill`` and view it as
    ``(n // 128, 128)`` int32."""
    x = jnp.pad(x.astype(jnp.int32), (0, n - x.shape[0]), constant_values=fill)
    return x.reshape(n // LANES, LANES)


def fold_lanes(refs, shape, acc, step):
    """Fold ``step(acc, *rows)`` over every element of the ``(rb, 128)``
    blocks in ``refs`` (read in lockstep), each element broadcast to
    ``shape``: ``rows`` are the blocks' ``k``-th rows, broadcast over the
    sublanes and rotated lane by lane, so position ``(i, l)`` of ``shape``
    meets every element of row ``k`` once."""
    refs = tuple(refs)

    def row(k, acc):
        rows = tuple(
            jnp.broadcast_to(ref[pl.ds(k, 1), :], shape) for ref in refs
        )

        def rotate(_, carry):
            acc, rows = carry
            for _ in range(_UNROLL):
                acc = step(acc, *rows)
                rows = tuple(pltpu.roll(r, 1, 1) for r in rows)
            return acc, rows

        acc, _ = jax.lax.fori_loop(0, LANES // _UNROLL, rotate, (acc, rows))
        return acc

    return jax.lax.fori_loop(0, refs[0].shape[0], row, acc)
