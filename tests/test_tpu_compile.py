"""Compile-only checks of the Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached.  Each kernel of the main path
is compiled with ``interpret=False`` at a deployment size and must come
out as a Mosaic kernel (``tpu_custom_call``) in the program text.  The
fused kernels have no caller on the main path and do not lower yet;
their tests are strict xfails that flip when a kernel change makes them
lower.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest-xdist workers import every
test file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # described-device compiles cannot be read back from the persistent
    # cache without a chip: keep them out of it while this module runs
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _i32(n, sharding):
    return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sharding)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("a_sorted", [False, True])
def test_sorted_member_compiles(one_chip, a_sorted):
    from repro.kernels.sorted_member import _sorted_member_jit

    compiled = _sorted_member_jit.lower(
        _i32(8192, one_chip), _i32(1 << 19, one_chip),
        rows_a=8, rows_b=8, a_sorted=a_sorted, interpret=False,
    ).compile()
    _assert_mosaic(compiled)


@pytest.mark.parametrize("a_sorted", [False, True])
def test_sorted_member_wide_compiles(one_chip, a_sorted):
    from repro.kernels.sorted_member import _sorted_member_wide_jit

    probes, table = _i32(8192, one_chip), _i32(1 << 19, one_chip)
    compiled = _sorted_member_wide_jit.lower(
        probes, probes, table, table,
        rows_a=8, rows_b=8, a_sorted=a_sorted, interpret=False,
    ).compile()
    _assert_mosaic(compiled)


def test_join_bounds_compiles(one_chip):
    from repro.kernels.join_bounds import _join_bounds_jit

    compiled = _join_bounds_jit.lower(
        _i32(8192, one_chip), _i32(1 << 19, one_chip),
        rows_l=8, rows_r=8, interpret=False,
    ).compile()
    _assert_mosaic(compiled)


def test_rle_expand_compiles(one_chip):
    from repro.kernels.rle_expand import _rle_expand_jit

    runs = (1 << 16) // 8
    compiled = _rle_expand_jit.lower(
        _i32(runs, one_chip), _i32(runs, one_chip),
        total=1 << 16, rows_out=8, interpret=False,
    ).compile()
    _assert_mosaic(compiled)


@pytest.mark.xfail(
    strict=True, raises=NotImplementedError,
    reason="Mosaic: 'Unimplemented primitive in Pallas TPU lowering for "
           "KernelType.TC: cumsum'",
)
def test_fused_join_dedup_compiles(one_chip):
    from repro.kernels.fused import _fused_join_dedup_jit

    n = 1024
    compiled = _fused_join_dedup_jit.lower(
        *(_i32(n, one_chip) for _ in range(4)), capacity=n, interpret=False,
    ).compile()
    _assert_mosaic(compiled)


@pytest.mark.xfail(
    strict=True, raises=NotImplementedError,
    reason="Mosaic: 'Unimplemented primitive in Pallas TPU lowering for "
           "KernelType.TC: sort'",
)
def test_merge_sorted_unique_compiles(one_chip):
    from repro.kernels.fused import _merge_jit

    n = 1024
    compiled = _merge_jit.lower(
        _i32(n, one_chip), _i32(n, one_chip), interpret=False
    ).compile()
    _assert_mosaic(compiled)
