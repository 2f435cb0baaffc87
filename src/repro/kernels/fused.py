"""Fused fixpoint kernels: join→dedup and sorted-buffer merge, one launch each.

The per-round hot path of every engine is the same chain: locate join
spans (``join_bounds``), enumerate the matching pairs (gather), pack
them, sort, and drop duplicates — historically four separate launches
with a host round-trip for the ``np.unique`` in the middle.  These two
kernels fuse the chain so a round's derivation traffic never leaves the
device:

* :func:`fused_join_dedup` — span probe → pair enumeration → 16-bit
  pack → sort → adjacent-unique mask → compaction, in **one**
  ``pallas_call``.  Output is the sorted-unique packed pair set, padded
  to a static ``capacity`` with :data:`BIG`; the true pair total is
  returned so the caller can regrow and retry when ``capacity`` was too
  small (the same doubling contract as the distributed exchange).
* :func:`merge_sorted_unique` — merge a round's fresh sorted-unique
  codes into the per-predicate sorted buffer **in place**
  (``input_output_aliases`` + a donating jit variant), so steady-state
  rounds reuse the same device allocation (see :mod:`.buffers`).

Value contract (the one-word keys of ``core.distributed.pack_pairs``;
these kernels have no two-word form): all ids are non-negative int32
below ``2**15``; packed pairs are ``(hi << 16) | (lo & 0xffff)``, so
every packed code is in ``[0, 2**31)`` and :data:`BIG` (int32 max) is a
safe pad sentinel.

Both kernels are single-program launches holding their operands in VMEM
(the pair-enumeration broadcast is O(capacity x n_left)); callers cap
per-call sizes at a few thousand rows and chunk above that — one launch
per chunk still beats the four-launch chain per chunk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import resolve_interpret

__all__ = [
    "BIG",
    "fused_join_dedup",
    "merge_sorted_unique",
    "merge_sorted_unique_donating",
]

#: pad sentinel: larger than any packed code, so sorting moves padding
#: to the tail and adjacent-unique masks never count it
BIG = jnp.iinfo(jnp.int32).max

_LANE = 128  # pad operands to lane multiples so TPU layouts stay happy


def _pad_to(x: jax.Array, n: int) -> jax.Array:
    return jnp.pad(x.astype(jnp.int32), (0, n - x.shape[0]), constant_values=BIG)


def _round_up(n: int, mult: int = _LANE) -> int:
    return max(mult, -(-n // mult) * mult)


# --------------------------------------------------------------------- #
# fused join → dedup
# --------------------------------------------------------------------- #
def _fused_join_dedup_kernel(
    l_ref, lp_ref, r_ref, rp_ref, o_ref, cnt_ref, tot_ref, *, capacity: int
):
    l = l_ref[...]
    lp = lp_ref[...]
    r = r_ref[...]
    rp = rp_ref[...]
    cap = o_ref.shape[0]  # lane-padded >= capacity

    # --- span probe (join_bounds, inlined): r is sorted, so the span of
    # l[i] is [#(r < l[i]), #(r <= l[i])).  BIG pads in r sort above every
    # real key; BIG pads in l are masked out below.
    lo = jnp.sum((r[None, :] < l[:, None]).astype(jnp.int32), axis=1)
    hi = jnp.sum((r[None, :] <= l[:, None]).astype(jnp.int32), axis=1)
    valid_l = l != BIG
    cnt = jnp.where(valid_l, hi - lo, 0)

    # --- pair enumeration: pair t belongs to the left row whose
    # exclusive offset is the largest one <= t (broadcast count instead
    # of searchsorted — Mosaic-safe, and zero-count rows resolve to the
    # last index of their offset tie-run, which is the producing row).
    offs = jnp.cumsum(cnt) - cnt
    total = jnp.sum(cnt)
    t = jax.lax.broadcasted_iota(jnp.int32, (cap, 1), 0)[:, 0]
    li = jnp.sum((offs[None, :] <= t[:, None]).astype(jnp.int32), axis=1) - 1
    li = jnp.clip(li, 0, l.shape[0] - 1)
    rj = jnp.clip(lo[li] + (t - offs[li]), 0, r.shape[0] - 1)
    # truncate at the *caller-visible* capacity, not the lane-padded
    # buffer size, so the numpy reference can mirror the contract
    valid = (t < total) & (t < capacity)

    # --- pack → sort → adjacent-unique → compact.  The second sort is
    # the scatter-free compaction trick: masked-out slots become BIG and
    # sort to the tail, leaving the unique codes sorted at the front.
    packed = jnp.where(valid, (lp[li] << 16) | (rp[rj] & 0xFFFF), BIG)
    s = jnp.sort(packed)
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), s[:-1]])
    uniq = (s != BIG) & (s != prev)
    o_ref[...] = jnp.sort(jnp.where(uniq, s, BIG))
    cnt_ref[0] = jnp.sum(uniq.astype(jnp.int32))
    tot_ref[0] = total


def fused_join_dedup(
    l_keys: jax.Array,
    l_payload: jax.Array,
    r_keys_sorted: jax.Array,
    r_payload: jax.Array,
    *,
    capacity: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Join ``l`` against sorted ``r`` on key and emit the deduplicated
    packed pairs ``(l_payload << 16) | r_payload`` — one kernel launch.

    Returns ``(out, count, total)``: ``out`` is ``(capacity,)`` int32,
    sorted unique, padded with :data:`BIG`; ``count`` the number of
    unique pairs kept; ``total`` the pre-dedup pair count.  When
    ``total > capacity`` the enumeration was truncated — regrow
    ``capacity`` to ``>= total`` and call again (results for the
    truncated call cover exactly the first ``capacity`` pairs in
    left-major order, which the numpy reference mirrors).
    ``interpret=None`` resolves per backend/env outside the jit.
    """
    return _fused_join_dedup_jit(
        l_keys,
        l_payload,
        r_keys_sorted,
        r_payload,
        capacity=capacity,
        interpret=resolve_interpret(interpret),
    )


@functools.partial(jax.jit, static_argnames=("capacity", "interpret"))
def _fused_join_dedup_jit(
    l_keys: jax.Array,
    l_payload: jax.Array,
    r_keys_sorted: jax.Array,
    r_payload: jax.Array,
    *,
    capacity: int,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    n, m = l_keys.shape[0], r_keys_sorted.shape[0]
    one = jax.ShapeDtypeStruct((1,), jnp.int32)
    if n == 0 or m == 0 or capacity == 0:
        return (
            jnp.full((capacity,), BIG, jnp.int32),
            jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), jnp.int32),
        )
    n_p, m_p, cap_p = _round_up(n), _round_up(m), _round_up(capacity)
    out, cnt, tot = pl.pallas_call(
        functools.partial(_fused_join_dedup_kernel, capacity=capacity),
        out_shape=[
            jax.ShapeDtypeStruct((cap_p,), jnp.int32),
            one,
            one,
        ],
        interpret=interpret,
    )(
        _pad_to(l_keys, n_p),
        _pad_to(l_payload, n_p),
        _pad_to(r_keys_sorted, m_p),
        _pad_to(r_payload, m_p),
    )
    return out[:capacity], cnt, tot


# --------------------------------------------------------------------- #
# in-place sorted-unique merge
# --------------------------------------------------------------------- #
def _merge_kernel(buf_ref, fresh_ref, o_ref, cnt_ref, new_ref):
    b = buf_ref[...]
    f = fresh_ref[...]
    cap = o_ref.shape[0]
    s = jnp.sort(jnp.concatenate([b, f]))
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), s[:-1]])
    uniq = (s != BIG) & (s != prev)
    o_ref[...] = jnp.sort(jnp.where(uniq, s, BIG))[:cap]
    n_after = jnp.sum(uniq.astype(jnp.int32))
    cnt_ref[0] = n_after
    new_ref[0] = n_after - jnp.sum((b != BIG).astype(jnp.int32))


def _merge_impl(
    buf: jax.Array, fresh: jax.Array, *, interpret: bool
) -> tuple[jax.Array, jax.Array, jax.Array]:
    cap = buf.shape[0]
    f_p = _round_up(fresh.shape[0]) if fresh.shape[0] else _LANE
    one = jax.ShapeDtypeStruct((1,), jnp.int32)
    return pl.pallas_call(
        _merge_kernel,
        out_shape=[jax.ShapeDtypeStruct((cap,), jnp.int32), one, one],
        input_output_aliases={0: 0},
        interpret=interpret,
    )(buf, _pad_to(fresh, f_p))


def _check_merge_args(buf: jax.Array) -> None:
    if buf.shape[0] % _LANE:
        raise ValueError(
            f"merge buffer capacity must be a multiple of {_LANE}, "
            f"got {buf.shape[0]} (FactBuffers rounds for you)"
        )


def merge_sorted_unique(
    buf: jax.Array, fresh: jax.Array, *, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Merge sorted-unique ``fresh`` codes into the sorted-unique,
    BIG-padded ``buf`` — one launch, output aliased onto ``buf``.

    Returns ``(merged, count, n_new)``.  Precondition (checked by
    :mod:`.buffers`, not here): ``capacity >= count_before + #fresh``,
    so the merge can never overflow — regrow happens *before* the
    donating call, never after, because donation invalidates ``buf``.
    ``interpret=None`` resolves per backend/env outside the jit.
    """
    _check_merge_args(buf)
    return _merge_jit(buf, fresh, interpret=resolve_interpret(interpret))


_merge_jit = jax.jit(_merge_impl, static_argnames=("interpret",))


#: same kernel with the buffer argument donated: XLA reuses ``buf``'s
#: allocation for ``merged``, so a steady-state round allocates nothing.
#: After the call ``buf`` is dead — callers must overwrite their handle.
merge_sorted_unique_donating = jax.jit(
    _merge_impl, static_argnames=("interpret",), donate_argnums=(0,)
)
