"""Per-kernel validation: shape sweeps + hypothesis vs the ref.py oracles.

All kernels run in interpret=True mode (CPU container; TPU is the target).
"""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st
from numpy.testing import assert_array_equal

from repro.kernels import ops, ref

SHAPES = [
    (0, 5),
    (1, 1),
    (7, 3),
    (64, 64),
    (100, 1000),
    (513, 2049),   # non-multiples of the block sizes
    (1024, 17),
    (2000, 0),
]


def _rand_sorted(rng, m, hi=10_000):
    return np.sort(rng.integers(0, hi, size=m).astype(np.int32))


class TestSortedMember:
    @pytest.mark.parametrize("n,m", SHAPES)
    def test_shapes(self, n, m):
        rng = np.random.default_rng(n * 31 + m)
        a = rng.integers(0, 10_000, size=n).astype(np.int32)
        b = _rand_sorted(rng, m)
        got = np.asarray(ops.member(a, b))
        want = np.asarray(ref.sorted_member_ref(a, b))
        assert_array_equal(got, want)

    @pytest.mark.parametrize("block_a,block_b", [(8, 16), (128, 128), (512, 1024)])
    def test_block_sweep(self, block_a, block_b):
        rng = np.random.default_rng(0)
        a = rng.integers(0, 500, size=300).astype(np.int32)
        b = _rand_sorted(rng, 450, hi=500)
        got = np.asarray(ops.member(a, b, block_a=block_a, block_b=block_b))
        want = np.asarray(ref.sorted_member_ref(a, b))
        assert_array_equal(got, want)

    def test_anti_join(self):
        a = np.asarray([1, 2, 3, 4, 5], dtype=np.int32)
        b = np.asarray([2, 4], dtype=np.int32)
        got = np.asarray(ops.anti_join_mask(a, b))
        assert_array_equal(got, [True, False, True, False, True])

    @settings(max_examples=40, deadline=None)
    @given(
        a=st.lists(st.integers(0, 1000), max_size=200),
        b=st.lists(st.integers(0, 1000), max_size=200),
    )
    def test_property(self, a, b):
        a = np.asarray(a, dtype=np.int32)
        b = np.sort(np.asarray(b, dtype=np.int32))
        got = np.asarray(ops.member(a, b, block_a=64, block_b=64))
        want = np.isin(a, b)
        assert_array_equal(got, want)


BIG = np.iinfo(np.int32).max


def _wide_table(rng, m, hi_range, lo_range=2**31 - 1):
    """``m`` two-word keys sorted lexicographically, high words from a
    small range so that many keys tie in it."""
    hi = rng.integers(0, hi_range, size=m).astype(np.int32)
    lo = rng.integers(0, lo_range, size=m).astype(np.int32)
    order = np.lexsort((lo, hi))
    return hi[order], lo[order]


def _wide_oracle(a, b):
    table = set(zip(b[0].tolist(), b[1].tolist()))
    return np.asarray([k in table for k in zip(a[0].tolist(), a[1].tolist())],
                      dtype=bool)


class TestSortedMemberWide:
    """The two-word kernel against the engine's jnp two-word membership
    (and a set), in interpret mode."""

    @staticmethod
    def _probes(rng, b, n):
        """Half hits drawn from the table, half near misses that share a
        table key's high word."""
        pick = rng.integers(0, len(b[0]), size=n)
        hi, lo = b[0][pick].copy(), b[1][pick].copy()
        miss = rng.random(n) < 0.5
        lo[miss] = rng.integers(0, 2**31 - 1, size=int(miss.sum()))
        return hi, lo

    def _check(self, a, b, **kw):
        from repro.core.distributed import sorted_member_jnp

        got = np.asarray(ops.member(a, b, **kw))
        assert_array_equal(got, _wide_oracle(a, b))
        want = np.asarray(sorted_member_jnp(
            tuple(map(jnp.asarray, a)), tuple(map(jnp.asarray, b))
        ))
        assert_array_equal(got, want)
        return got

    @pytest.mark.parametrize("n,m", [(1, 1), (7, 3), (300, 450), (1024, 17),
                                     (2049, 513)])
    def test_ties_in_the_high_word(self, n, m):
        rng = np.random.default_rng(n * 7 + m)
        b = _wide_table(rng, m, hi_range=4)
        a = self._probes(rng, b, n)
        got = self._check(a, b)
        assert got.any() or n < 4

    @pytest.mark.parametrize("block_a,block_b", [(8, 16), (128, 128)])
    def test_sorted_probes_across_blocks(self, block_a, block_b):
        rng = np.random.default_rng(1)
        b = _wide_table(rng, 3000, hi_range=3, lo_range=5000)
        a = self._probes(rng, b, 2500)
        order = np.lexsort((a[1], a[0]))
        a = (a[0][order], a[1][order])
        self._check(a, b, a_sorted=True, block_a=block_a, block_b=block_b)

    def test_unsorted_probes(self):
        rng = np.random.default_rng(2)
        b = _wide_table(rng, 700, hi_range=2**31 - 1)
        a = self._probes(rng, b, 900)
        assert (np.diff(a[0].astype(np.int64)) < 0).any()  # not sorted
        self._check(a, b, block_a=128, block_b=128)

    def test_probes_at_the_sentinel(self):
        """A BIG-padded table (the engine's invalid rows) and probes at the
        sentinel in either word: the kernel answers as the jnp path."""
        rng = np.random.default_rng(3)
        hi, lo = _wide_table(rng, 200, hi_range=5)
        b = (np.concatenate([hi, np.full(56, BIG, np.int32)]),
             np.concatenate([lo, np.full(56, BIG, np.int32)]))
        a = (np.concatenate([hi[:10], [BIG, BIG, 3]]).astype(np.int32),
             np.concatenate([lo[:10], [BIG, 0, BIG]]).astype(np.int32))
        got = self._check(a, b)
        assert got[:10].all() and got[10] and not got[11:].any()

    def test_empty_table(self):
        a = (np.arange(5, dtype=np.int32), np.arange(5, dtype=np.int32))
        empty = (np.zeros(0, np.int32), np.zeros(0, np.int32))
        assert not np.asarray(ops.member(a, empty)).any()
        assert np.asarray(ops.member((a[0][:0], a[1][:0]), a)).shape == (0,)


class TestRleExpand:
    @pytest.mark.parametrize(
        "runs",
        [
            [(5, 1)],
            [(3, 4), (7, 2), (9, 10)],
            [(1, 1000)],
            [(i, 1) for i in range(100)],
            [(i, (i % 7) + 1) for i in range(300)],
        ],
    )
    def test_shapes(self, runs):
        vals = np.asarray([v for v, _ in runs], dtype=np.int32)
        cnts = np.asarray([c for _, c in runs], dtype=np.int32)
        total = int(cnts.sum())
        got = np.asarray(ops.expand_rle(vals, cnts, total))
        want = np.asarray(ref.rle_expand_ref(vals, cnts, total))
        assert_array_equal(got, want)

    @pytest.mark.parametrize("block_out", [16, 128, 1024])
    def test_block_sweep(self, block_out):
        rng = np.random.default_rng(1)
        vals = rng.integers(0, 100, size=50).astype(np.int32)
        cnts = rng.integers(1, 9, size=50).astype(np.int32)
        total = int(cnts.sum())
        got = np.asarray(ops.expand_rle(vals, cnts, total, block_out=block_out))
        want = np.asarray(ref.rle_expand_ref(vals, cnts, total))
        assert_array_equal(got, want)

    @settings(max_examples=40, deadline=None)
    @given(
        runs=st.lists(
            st.tuples(st.integers(0, 100), st.integers(1, 20)),
            min_size=1,
            max_size=60,
        )
    )
    def test_property(self, runs):
        vals = np.asarray([v for v, _ in runs], dtype=np.int32)
        cnts = np.asarray([c for _, c in runs], dtype=np.int32)
        total = int(cnts.sum())
        got = np.asarray(ops.expand_rle(vals, cnts, total, block_out=64))
        assert_array_equal(got, np.repeat(vals, cnts))


class TestJoinBounds:
    @pytest.mark.parametrize("n,m", SHAPES)
    def test_shapes(self, n, m):
        rng = np.random.default_rng(n * 7 + m)
        l = rng.integers(0, 300, size=n).astype(np.int32)
        r = _rand_sorted(rng, m, hi=300)
        lo_g, hi_g = ops.group_spans(l, r)
        lo_w, hi_w = ref.join_bounds_ref(l, r)
        assert_array_equal(np.asarray(lo_g), np.asarray(lo_w))
        assert_array_equal(np.asarray(hi_g), np.asarray(hi_w))

    @pytest.mark.parametrize("block_l,block_r", [(8, 8), (64, 256), (512, 1024)])
    def test_block_sweep(self, block_l, block_r):
        rng = np.random.default_rng(3)
        l = rng.integers(0, 100, size=333).astype(np.int32)
        r = _rand_sorted(rng, 777, hi=100)
        lo_g, hi_g = ops.group_spans(l, r, block_l=block_l, block_r=block_r)
        lo_w, hi_w = ref.join_bounds_ref(l, r)
        assert_array_equal(np.asarray(lo_g), np.asarray(lo_w))
        assert_array_equal(np.asarray(hi_g), np.asarray(hi_w))

    def test_prune_fastpath_correct(self):
        """Left tile far above right blocks exercises the += BLOCK path."""
        l = np.full(64, 1_000_000, dtype=np.int32)
        r = np.arange(4096, dtype=np.int32)
        lo_g, hi_g = ops.group_spans(l, r, block_l=64, block_r=256)
        assert_array_equal(np.asarray(lo_g), np.full(64, 4096, dtype=np.int32))
        assert_array_equal(np.asarray(hi_g), np.full(64, 4096, dtype=np.int32))

    @settings(max_examples=40, deadline=None)
    @given(
        l=st.lists(st.integers(0, 500), max_size=150),
        r=st.lists(st.integers(0, 500), max_size=150),
    )
    def test_property(self, l, r):
        l = np.asarray(l, dtype=np.int32)
        r = np.sort(np.asarray(r, dtype=np.int32))
        lo_g, hi_g = ops.group_spans(l, r, block_l=32, block_r=32)
        lo_w = np.searchsorted(r, l, side="left")
        hi_w = np.searchsorted(r, l, side="right")
        assert_array_equal(np.asarray(lo_g), lo_w.astype(np.int32))
        assert_array_equal(np.asarray(hi_g), hi_w.astype(np.int32))
