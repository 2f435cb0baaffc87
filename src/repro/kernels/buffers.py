"""Per-predicate fact buffers with watermarks — the donation layer.

The distributed engine keeps each shard's facts as ``(rows, count,
delta_lo)``: a padded buffer plus watermarks.  This module generalises
that shape for every engine:

* **host mode** (default) — facts are sorted-unique packed **int64**
  codes in exact-size numpy arrays.  :meth:`FactBuffers.fresh_mask` is
  API-compatible with ``core.dedup.DedupIndex`` (so ``CMatEngine`` can
  take either), but survivors are folded in with the positional
  ``merge_sorted_unique_np`` instead of a full re-sort per round.
* **device mode** (``device=True``) — facts are sorted-unique packed
  **int32** codes (the 16-bit-halves pack of the one-word keys of
  ``core.distributed.pack_pairs``, ids below ``2**15``; there is no
  two-word form here) in ``BIG``-padded device buffers of
  power-of-two capacity, with a host-tracked ``count`` watermark.
  Each round's fresh codes are folded in by the ``merge_sorted_unique``
  Pallas kernel with the buffer **donated**
  (``jax.jit(..., donate_argnums=(0,))`` + ``input_output_aliases``),
  so XLA rewrites the merge into the existing allocation: a
  steady-state round allocates **nothing**.

Watermark invariants (device mode):

1. ``buf[:count]`` is strictly increasing (sorted unique); every slot
   at or beyond ``count`` holds ``BIG``.
2. ``count <= capacity`` and ``capacity`` is a multiple of 128.
3. Regrow happens *before* the donating merge — donation invalidates
   the input buffer, so an overflowing merge could not be retried.
   :meth:`merge` therefore regrows whenever ``count + len(fresh)``
   might exceed capacity, making kernel-side overflow unreachable.

Traffic is metered in the ``kernels.`` scope:
``kernels.buffers.allocations`` (buffer (re)allocations — flat in
steady state, the donation test's assertion), ``.regrows``,
``.merges``, and ``.rows_merged``.
"""

from __future__ import annotations

import numpy as np

from ..core.util import (
    first_occurrence_mask,
    merge_sorted_unique_np,
    sorted_member,
)
from ..obs import get_registry
from ..obs.memory import register_reporter

__all__ = ["FactBuffers", "BIG_NP"]

_SCOPE = "kernels.buffers."

#: numpy view of the device pad sentinel (int32 max)
BIG_NP = np.int32(np.iinfo(np.int32).max)

_MIN_CAPACITY = 128


def _round_capacity(n: int) -> int:
    """Next power of two >= n (floor 128) — doubling keeps the number of
    regrows logarithmic and jit retraces bounded."""
    n = max(int(n), _MIN_CAPACITY)
    return 1 << (n - 1).bit_length()


class FactBuffers:
    """Sorted per-predicate fact code buffers (host or device resident)."""

    def __init__(
        self,
        *,
        device: bool = False,
        interpret: bool | None = None,
        donate: bool | None = None,
        initial_capacity: int = 1024,
    ):
        self.device = bool(device)
        self._initial_capacity = _round_capacity(initial_capacity)
        self._reg = get_registry()
        # per-instance regrow history + peak-occupancy watermark
        # (obs.memory: capacity vs occupancy is the padding waste the
        # power-of-two policy trades for bounded retraces)
        self.regrows = 0
        self._peak_occupied_bytes = 0
        register_reporter("buffers", self)
        if self.device:
            from .backend import backend_name, resolve_interpret

            self.interpret = resolve_interpret(interpret)
            # donation is a no-op (with a warning) on CPU; default it to
            # the backends that honour it, overridable for tests
            self.donate = (
                backend_name() != "cpu" if donate is None else bool(donate)
            )
            self._buf: dict[str, object] = {}  # pred -> jax.Array
            self._count: dict[str, int] = {}
        else:
            self._codes: dict[str, np.ndarray] = {}

    # ------------------------------------------------------------------ #
    # byte accounting (obs.memory reporter protocol)
    # ------------------------------------------------------------------ #
    def occupied_bytes(self) -> int:
        """Bytes of live codes (device: below the watermark)."""
        if self.device:
            return 4 * sum(self._count.values())
        return sum(int(c.nbytes) for c in self._codes.values())

    def capacity_bytes(self) -> int:
        """Bytes allocated (device: BIG-padded power-of-two buffers;
        host: exact-size arrays, so capacity == occupancy)."""
        if self.device:
            return sum(int(b.nbytes) for b in self._buf.values())
        return self.occupied_bytes()

    def _note_occupancy(self) -> None:
        occ = self.occupied_bytes()
        if occ > self._peak_occupied_bytes:
            self._peak_occupied_bytes = occ

    def memory_report(self) -> dict[str, int]:
        """Disjoint parts — ``occupied + padding == capacity`` — plus
        the peak-occupancy watermark and regrow history as auxiliaries
        (non-``_bytes`` keys stay out of the resident roll-up)."""
        occ = self.occupied_bytes()
        cap = self.capacity_bytes()
        self._note_occupancy()
        return {
            "occupied_bytes": occ,
            "padding_bytes": cap - occ,
            "peak_occupied": self._peak_occupied_bytes,
            "regrows": self.regrows,
            "n_predicates": len(self._buf if self.device else self._codes),
        }

    # ------------------------------------------------------------------ #
    # host mode: DedupIndex-compatible surface over int64 packed codes
    # ------------------------------------------------------------------ #
    @staticmethod
    def pack(rows: np.ndarray) -> np.ndarray | None:
        """Row pack (same contract as ``DedupIndex.pack``): arity-1 is
        the id, arity-2 is ``(a << 32) | b``; wider rows return None and
        the caller falls back to joint factorisation."""
        if rows.shape[1] == 1:
            return rows[:, 0].astype(np.int64)
        if rows.shape[1] == 2:
            return (rows[:, 0].astype(np.int64) << 32) | rows[:, 1].astype(
                np.int64
            )
        return None

    def seed(self, pred: str, rows: np.ndarray) -> None:
        """Fold already-known facts in without producing a mask."""
        packed = self.pack(rows)
        if packed is None:
            return
        existing = self._codes.get(pred)
        merged = packed if existing is None else np.concatenate(
            [existing, packed]
        )
        self._codes[pred] = np.unique(merged)
        self._note_occupancy()

    def fresh_mask(self, pred: str, rows: np.ndarray) -> np.ndarray | None:
        """Keep-mask over ``rows``: not already buffered AND first
        occurrence in the block; survivors are merged in.  None when the
        arity is unpackable (caller falls back to factorisation)."""
        packed = self.pack(rows)
        if packed is None:
            return None
        index = self._codes.get(pred)
        if index is None or index.shape[0] == 0:
            not_in = np.ones(rows.shape[0], dtype=bool)
        else:
            not_in = sorted_member(packed, index)
            np.logical_not(not_in, out=not_in)
        keep = not_in & first_occurrence_mask(packed)
        survivors = packed[keep]
        if survivors.shape[0]:
            survivors = np.sort(survivors)
            self._codes[pred] = (
                survivors
                if index is None
                else merge_sorted_unique_np(index, survivors)
            )
            self._note_occupancy()
        return keep

    def codes(self, pred: str) -> np.ndarray:
        if self.device:
            buf = self._buf.get(pred)
            if buf is None:
                return np.zeros(0, dtype=np.int32)
            return np.asarray(buf)[: self._count[pred]]
        return self._codes.get(pred, np.zeros(0, dtype=np.int64))

    def count(self, pred: str) -> int:
        if self.device:
            return self._count.get(pred, 0)
        codes = self._codes.get(pred)
        return 0 if codes is None else int(codes.shape[0])

    def predicates(self) -> list[str]:
        return sorted(self._buf if self.device else self._codes)

    # ------------------------------------------------------------------ #
    # device mode: BIG-padded int32 buffers + donated Pallas merge
    # ------------------------------------------------------------------ #
    def capacity(self, pred: str) -> int:
        buf = self._buf.get(pred)
        return 0 if buf is None else int(buf.shape[0])

    def _alloc(self, pred: str, capacity: int):
        import jax.numpy as jnp

        cap = _round_capacity(capacity)
        old = self._buf.get(pred)
        buf = jnp.full((cap,), BIG_NP, dtype=jnp.int32)
        if old is not None:
            buf = buf.at[: old.shape[0]].set(old)
            self.regrows += 1
            self._reg.counter(f"{_SCOPE}regrows").inc()
        self._buf[pred] = buf
        self._count.setdefault(pred, 0)
        self._reg.counter(f"{_SCOPE}allocations").inc()
        return buf

    def ensure(self, pred: str, min_capacity: int | None = None):
        """Device buffer for ``pred``, (re)allocated to hold at least
        ``min_capacity`` codes (invariant 3: grow before merging)."""
        if not self.device:
            raise RuntimeError("ensure() is device-mode only")
        need = self._initial_capacity if min_capacity is None else min_capacity
        buf = self._buf.get(pred)
        if buf is None or buf.shape[0] < need:
            buf = self._alloc(pred, need)
        return buf

    def merge(self, pred: str, fresh) -> int:
        """Merge a round's fresh sorted-unique code block (BIG-padded or
        exact, e.g. a ``fused_join_dedup`` output) into ``pred``'s
        buffer via the donated in-place kernel.  Returns the number of
        genuinely new codes."""
        if not self.device:
            raise RuntimeError("merge() is device-mode only")
        import jax.numpy as jnp

        from .fused import merge_sorted_unique, merge_sorted_unique_donating

        fresh = jnp.asarray(fresh, dtype=jnp.int32)
        count = self._count.get(pred, 0)
        buf = self.ensure(pred, count + int(fresh.shape[0]))
        if self.donate:
            merged, cnt, n_new = merge_sorted_unique_donating(
                buf, fresh, interpret=self.interpret
            )
        else:
            merged, cnt, n_new = merge_sorted_unique(
                buf, fresh, interpret=self.interpret
            )
        # the donated handle is dead from here on — overwrite it
        self._buf[pred] = merged
        new_count = int(cnt[0])
        assert new_count <= merged.shape[0], "merge overflowed capacity"
        self._count[pred] = new_count
        self._note_occupancy()
        self._reg.counter(f"{_SCOPE}merges").inc()
        self._reg.counter(f"{_SCOPE}rows_merged").inc(int(fresh.shape[0]))
        self._reg.counter("kernels.kernel_launches").inc()
        return int(n_new[0])
