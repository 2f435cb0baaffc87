"""PR 7 device fast path: fused kernels vs ref oracles, buffer
donation/watermarks, the autotuner cache, and backend interpret
resolution.

Differential tests deliberately include the degenerate shapes the
kernels must contract over: empty sides, all-duplicate pair sets, and
totals that overflow the static capacity (the regrow protocol).  The
randomised sweeps here are seeded loops so they run without hypothesis;
the hypothesis property versions live in ``test_fused_property.py``."""

import os

import numpy as np
import pytest
from numpy.testing import assert_array_equal

from repro.kernels import backend, ref, tune
from repro.kernels.buffers import BIG_NP, FactBuffers
from repro.kernels.fused import fused_join_dedup, merge_sorted_unique
from repro.obs import get_registry


def _i32(xs):
    return np.asarray(xs, dtype=np.int32)


class TestFusedJoinDedup:
    @pytest.mark.parametrize("capacity", [1, 7, 64, 256, 1000])
    def test_matches_ref(self, capacity):
        rng = np.random.default_rng(capacity)
        for trial in range(20):
            n = int(rng.integers(0, 80))
            m = int(rng.integers(0, 80))
            l_keys = rng.integers(0, 50, size=n).astype(np.int32)
            r_keys = np.sort(rng.integers(0, 50, size=m).astype(np.int32))
            l_pay = rng.integers(0, 2**15, size=n).astype(np.int32)
            r_pay = rng.integers(0, 2**16, size=m).astype(np.int32)
            out, cnt, tot = fused_join_dedup(
                l_keys, l_pay, r_keys, r_pay, capacity=capacity
            )
            r_out, r_cnt, r_tot = ref.fused_join_dedup_ref(
                l_keys, l_pay, r_keys, r_pay, capacity=capacity
            )
            assert int(tot[0]) == r_tot
            assert int(cnt[0]) == r_cnt
            assert_array_equal(np.asarray(out), r_out)

    def test_empty_sides(self):
        empty = np.zeros(0, np.int32)
        some = _i32([1, 2, 3])
        for l, r in [(empty, some), (some, empty), (empty, empty)]:
            out, cnt, tot = fused_join_dedup(
                l, l.copy(), np.sort(r), r.copy(), capacity=64
            )
            assert int(cnt[0]) == 0 and int(tot[0]) == 0
            assert (np.asarray(out) == BIG_NP).all()

    def test_all_duplicates_collapse_to_one(self):
        # every (l, r) match packs to the identical code
        l_keys = np.full(37, 5, np.int32)
        r_keys = np.full(11, 5, np.int32)
        l_pay = np.full(37, 9, np.int32)
        r_pay = np.full(11, 3, np.int32)
        out, cnt, tot = fused_join_dedup(
            l_keys, l_pay, r_keys, r_pay, capacity=512
        )
        assert int(tot[0]) == 37 * 11
        assert int(cnt[0]) == 1
        assert int(np.asarray(out)[0]) == (9 << 16) | 3

    def test_overflow_reports_total_and_regrow_recovers(self):
        # 20x20 all-matching -> 400 pairs; capacity 64 truncates
        rng = np.random.default_rng(0)
        l_keys = np.zeros(20, np.int32)
        r_keys = np.zeros(20, np.int32)
        l_pay = rng.integers(0, 2**15, size=20).astype(np.int32)
        r_pay = rng.integers(0, 2**16, size=20).astype(np.int32)
        out, cnt, tot = fused_join_dedup(
            l_keys, l_pay, r_keys, r_pay, capacity=64
        )
        assert int(tot[0]) == 400 > 64  # caller sees the overflow
        # regrow to >= total and retry: the full dedup'd pair set
        out2, cnt2, tot2 = fused_join_dedup(
            l_keys, l_pay, r_keys, r_pay, capacity=512
        )
        assert int(tot2[0]) == 400
        expect = np.unique(
            (l_pay.astype(np.int64)[:, None] << 16)
            | r_pay.astype(np.int64)[None, :]
        )
        assert int(cnt2[0]) == expect.size
        assert_array_equal(
            np.asarray(out2)[: expect.size], expect.astype(np.int32)
        )


class TestMergeSortedUnique:
    def test_matches_ref(self):
        rng = np.random.default_rng(7)
        for trial in range(40):
            nb = int(rng.integers(0, 61))
            nf = int(rng.integers(0, 61))
            buf = np.full(128, BIG_NP, np.int32)
            sv = np.unique(rng.integers(0, 2**30, size=nb).astype(np.int32))
            buf[: sv.size] = sv
            fresh = np.unique(rng.integers(0, 2**30, size=nf).astype(np.int32))
            merged, cnt, n_new = merge_sorted_unique(buf, fresh)
            r_merged, r_cnt, r_new = ref.merge_sorted_unique_ref(buf, fresh)
            assert int(cnt[0]) == r_cnt
            assert int(n_new[0]) == r_new
            assert_array_equal(np.asarray(merged), r_merged)

    def test_capacity_must_be_lane_multiple(self):
        with pytest.raises(ValueError, match="multiple of 128"):
            merge_sorted_unique(
                np.full(100, BIG_NP, np.int32), _i32([1, 2])
            )

    def test_merge_is_idempotent(self):
        buf = np.full(128, BIG_NP, np.int32)
        buf[:3] = [1, 5, 9]
        fresh = _i32([1, 5, 9])
        merged, cnt, n_new = merge_sorted_unique(buf, fresh)
        assert int(cnt[0]) == 3 and int(n_new[0]) == 0


class TestFactBuffersDevice:
    def _reg(self):
        reg = get_registry()
        reg.reset("kernels.")
        return reg

    def test_steady_state_allocates_nothing(self):
        """The donation contract: after the first allocation, rounds
        that fit in capacity must not allocate (kernels.buffers.
        allocations stays flat while merges keep counting)."""
        reg = self._reg()
        fb = FactBuffers(device=True, donate=False, initial_capacity=1024)
        fb.ensure("P", 1024)
        snap = reg.snapshot("kernels.")
        assert snap.get("kernels.buffers.allocations", 0) == 1
        rng = np.random.default_rng(1)
        for i in range(6):
            fresh = np.unique(
                rng.integers(0, 2**20, size=50).astype(np.int32)
            )
            fb.merge("P", fresh)
        snap = reg.snapshot("kernels.")
        assert snap["kernels.buffers.allocations"] == 1  # still just one
        assert snap["kernels.buffers.merges"] == 6
        assert snap["kernels.kernel_launches"] >= 6
        # watermark invariant 1: sorted unique below count, BIG above
        buf = np.asarray(fb._buf["P"])
        n = fb.count("P")
        assert (np.diff(buf[:n]) > 0).all()
        assert (buf[n:] == BIG_NP).all()

    def test_regrow_before_merge_preserves_codes(self):
        reg = self._reg()
        fb = FactBuffers(device=True, donate=False, initial_capacity=128)
        rng = np.random.default_rng(2)
        seen = np.zeros(0, np.int32)
        for i in range(5):
            fresh = np.unique(
                rng.integers(0, 2**20, size=100).astype(np.int32)
            )
            fb.merge("P", fresh)
            seen = np.union1d(seen, fresh).astype(np.int32)
        assert_array_equal(fb.codes("P"), seen)
        assert fb.capacity("P") >= seen.size
        assert reg.snapshot("kernels.")["kernels.buffers.regrows"] >= 1

    def test_donating_merge_same_result(self):
        fb_d = FactBuffers(device=True, donate=True, initial_capacity=256)
        fb_p = FactBuffers(device=True, donate=False, initial_capacity=256)
        rng = np.random.default_rng(3)
        for _ in range(4):
            fresh = np.unique(
                rng.integers(0, 2**20, size=40).astype(np.int32)
            )
            n_d = fb_d.merge("P", fresh)
            n_p = fb_p.merge("P", fresh)
            assert n_d == n_p
        assert_array_equal(fb_d.codes("P"), fb_p.codes("P"))


class TestFactBuffersHost:
    def test_fresh_mask_matches_dedup_index(self):
        from repro.core.dedup import DedupIndex

        rng = np.random.default_rng(4)
        fb, di = FactBuffers(), DedupIndex()
        seed = rng.integers(0, 1000, size=(50, 2)).astype(np.int64)
        fb.seed("P", seed)
        di.seed("P", seed)
        for _ in range(5):
            rows = rng.integers(0, 1000, size=(80, 2)).astype(np.int64)
            assert_array_equal(fb.fresh_mask("P", rows), di.fresh_mask("P", rows))

    def test_wide_rows_fall_back(self):
        fb = FactBuffers()
        rows = np.zeros((4, 3), dtype=np.int64)
        assert fb.fresh_mask("P", rows) is None


class TestBackendResolution:
    def test_default_is_cpu_detected(self, monkeypatch):
        monkeypatch.delenv(backend.ENV_VAR, raising=False)
        # this container is CPU-only, so None resolves to True
        assert backend.backend_name() == "cpu"
        assert backend.resolve_interpret(None) is True
        # explicit bools pass straight through
        assert backend.resolve_interpret(False) is False
        assert backend.resolve_interpret(True) is True

    @pytest.mark.parametrize("val,expect", [
        ("1", True), ("true", True), ("YES", True), ("on", True),
        ("0", False), ("false", False), ("No", False), ("off", False),
    ])
    def test_env_override(self, monkeypatch, val, expect):
        monkeypatch.setenv(backend.ENV_VAR, val)
        assert backend.resolve_interpret(None) is expect

    def test_env_garbage_raises(self, monkeypatch):
        monkeypatch.setenv(backend.ENV_VAR, "maybe")
        with pytest.raises(ValueError, match="REPRO_PALLAS_INTERPRET"):
            backend.resolve_interpret(None)

    @pytest.mark.parametrize("val", ["1", "true"])
    def test_env_interpret_refused_off_cpu(self, monkeypatch, val):
        # an accelerator never interprets: the flag raises, it does not
        # quietly run the emulator under a device run's name
        monkeypatch.setattr(backend, "backend_name", lambda: "tpu")
        monkeypatch.setenv(backend.ENV_VAR, val)
        with pytest.raises(RuntimeError, match="interpret only on CPU"):
            backend.resolve_interpret(None)
        monkeypatch.setenv(backend.ENV_VAR, "0")
        assert backend.resolve_interpret(None) is False
        monkeypatch.delenv(backend.ENV_VAR)
        assert backend.resolve_interpret(None) is False


def _stub_runner(kernel, bucket, blocks, interpret):
    """Stands in for a kernel launch: every tiling runs (CPU cannot
    compile Mosaic kernels, so the real runner always fails here)."""


class TestTuneCache:
    @pytest.fixture(autouse=True)
    def _tmp_cache(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune.json"))
        tune._cache = None
        yield
        tune._cache = None

    def test_default_cache_lives_in_the_checkout(self, monkeypatch):
        from repro.compile_cache import CACHE_DIR

        monkeypatch.delenv("REPRO_TUNE_CACHE")
        assert tune.cache_path() == os.path.join(CACHE_DIR, "pallas_tune.json")
        assert not tune.cache_path().startswith(os.path.expanduser("~") + "/.")

    def test_sweep_raises_when_no_tiling_runs(self):
        # compiled (non-interpret) kernels cannot run on the CPU backend:
        # the sweep must say so instead of returning untested defaults
        with pytest.raises(RuntimeError, match="no rle_expand tiling runs"):
            tune.get_blocks("rle_expand", n=300, interpret=False)
        assert not os.path.exists(tune.cache_path())

    def test_interpret_mode_returns_defaults_without_cache(self):
        reg = get_registry()
        reg.reset("kernels.tune.")
        blocks = tune.get_blocks("sorted_member", n=5000, interpret=True)
        assert blocks == tune.DEFAULTS["sorted_member"]
        assert not os.path.exists(tune.cache_path())  # no sweep, no file
        snap = reg.snapshot("kernels.tune.")
        assert snap["kernels.tune.defaults"] == 1

    def test_sweep_writes_cache_then_hits(self, monkeypatch):
        import json

        monkeypatch.setattr(tune, "_runner", _stub_runner)
        reg = get_registry()
        reg.reset("kernels.tune.")
        b1 = tune.get_blocks("rle_expand", n=300, interpret=False)
        assert os.path.exists(tune.cache_path())
        b2 = tune.get_blocks("rle_expand", n=300, interpret=False)
        assert b1 == b2
        snap = reg.snapshot("kernels.tune.")
        assert snap["kernels.tune.sweeps"] == 1
        assert snap["kernels.tune.cache_hits"] == 1
        raw = json.load(open(tune.cache_path()))
        assert raw["version"] == tune.CACHE_VERSION
        key = f"rle_expand|int32|{tune.size_bucket(300)}|cpu"
        assert raw["entries"][key] == b1

    def test_sweep_runs_eagerly_inside_a_trace(self, monkeypatch):
        # the sharded round looks its blocks up while shard_map traces it;
        # the sweep still launches (here: interpret-mode) kernels for real
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from jax.sharding import PartitionSpec as P

        runner, ran = tune._runner, []

        def run_interpreted(kernel, bucket, blocks, interpret):
            runner(kernel, bucket, blocks, True)
            ran.append(blocks)

        monkeypatch.setattr(tune, "_runner", run_interpreted)
        seen = {}

        def body(x):
            seen.update(tune.get_blocks("sorted_member", n=300, interpret=False))
            return x

        mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
        jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False,
        ))(jnp.arange(8))
        assert seen in tune.CANDIDATES["sorted_member"]
        # every candidate: one warm-up launch and three timed ones
        assert len(ran) == 4 * len(tune.CANDIDATES["sorted_member"])

    def test_version_mismatch_discards(self, monkeypatch):
        import json

        monkeypatch.setattr(tune, "_runner", _stub_runner)
        tune.get_blocks("rle_expand", n=300, interpret=False)
        raw = json.load(open(tune.cache_path()))
        raw["version"] = tune.CACHE_VERSION + 1
        json.dump(raw, open(tune.cache_path(), "w"))
        tune._cache = None
        assert tune._load_cache() == {}

    def test_corrupt_cache_is_cold(self):
        with open(tune.cache_path(), "w") as fh:
            fh.write("{not json")
        tune._cache = None
        assert tune._load_cache() == {}

    def test_size_bucket(self):
        assert tune.size_bucket(1) == 256
        assert tune.size_bucket(256) == 256
        assert tune.size_bucket(257) == 512
        assert tune.size_bucket(5000) == 8192

    def test_unknown_kernel_raises(self):
        with pytest.raises(KeyError):
            tune.get_blocks("nope", n=10, interpret=True)
