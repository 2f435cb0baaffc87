"""The trace reduction and the roofline bytes, on synthetic events."""

from __future__ import annotations

import pytest

from bench import roofline, tracereduce
from bench.metrics import (
    device_idle_pct,
    host_ms_per_round,
    round_program_ms,
    rounds,
)

MS = 1_000_000  # ns


def _trace():
    # window [0, 100) ms; ops busy [10, 30) and [25, 40) (overlapping)
    # and [60, 70), one op outside the window
    ops = {0: [(10 * MS, 30 * MS, "sort"), (25 * MS, 40 * MS, "member"),
               (60 * MS, 70 * MS, "sort"), (150 * MS, 160 * MS, "sort")]}
    modules = {0: [(10 * MS, 40 * MS, "jit_body"), (60 * MS, 70 * MS, "jit_body"),
                   (80 * MS, 81 * MS, "jit_other")]}
    notes = [
        (0, 100 * MS, tracereduce.WINDOW),
        (0, 50 * MS, "bench.materialise"),
        (5 * MS, 45 * MS, "bench.round"),
        (50 * MS, 100 * MS, "bench.materialise"),
    ]
    return tracereduce.build(ops, modules, notes)


def test_union_and_gaps():
    busy = tracereduce.union(
        [(10, 30), (25, 40), (60, 70), (150, 160)], 0, 100
    )
    assert busy == [(10, 40), (60, 70)]
    assert tracereduce.gaps(busy, 0, 100) == [(0, 10), (40, 60), (70, 100)]
    assert tracereduce.union([(5, 20)], 10, 15) == [(10, 15)]
    assert tracereduce.gaps([], 0, 10) == [(0, 10)]


def test_busy_idle_and_attribution():
    tr = _trace()
    assert tr.window_s == pytest.approx(0.1)
    assert tracereduce.busy_s(tr) == pytest.approx(0.040)
    idle = tracereduce.idle_by_annotation(tr)
    # [0,10) -> round (innermost open at 5 ms), [40,60) -> materialise
    # (50 ms: the second one opens there), [70,100) -> materialise
    assert idle == pytest.approx(
        {"bench.round": 0.010, "bench.materialise": 0.050}
    )
    assert tracereduce.annotation_at(tr, 200 * MS) == "-"


def test_seconds_by_name_in_window():
    tr = _trace()
    ops = tracereduce.seconds_by_name(tracereduce.in_window(tr, tr.ops))
    assert ops == pytest.approx({"sort": 0.030, "member": 0.015})
    mods = tracereduce.seconds_by_name(
        tr.modules, lambda n: n.startswith("jit_body")
    )
    assert mods == pytest.approx({"jit_body": 0.040})
    assert tracereduce.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0], ["c", 2.0]]


def test_no_window_no_trace():
    assert tracereduce.build({}, {}, [(0, 1, "bench.round")]) is None


def test_readers_on_synthetic_trace():
    tr = _trace()
    ctx = {"trace": tr, "units": 2, "rounds": 6, "lowered_in_window": 0,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert device_idle_pct.read(ctx) == pytest.approx(60.0)
    assert host_ms_per_round.read(ctx) == pytest.approx(60.0 / 6)
    assert rounds.read(ctx) == pytest.approx(3.0)
    assert round_program_ms.read(ctx) == pytest.approx(40.0 / 2)
    # nothing to read: the readers return nothing, never 0
    empty = dict(ctx, trace=None, rounds=0)
    for reader in (device_idle_pct, host_ms_per_round, rounds, round_program_ms):
        assert reader.read(empty) is None


def test_sorted_member_bytes():
    assert roofline.sorted_member_bytes(1024, 4096) == 4 * (2 * 1024 + 4096)
    # 819 MB in 1 ms at 819 GB/s is the whole roofline
    assert roofline.share_pct(819e6, 1e-3, 819e9) == pytest.approx(100.0)


def test_roofline_reader_on_a_kernel_op_as_the_chip_names_it():
    from bench.metrics import sorted_member_roofline

    # the op name as a v5e trace gives it: the call's HLO text
    name = (
        "%_sorted_member_jit.1 = s32[256,128]{1,0:T(8,128)S(1)} custom-call("
        "s32[48]{0:T(128)S(1)} %get-tuple-element.280, s32[48]{0:T(128)S(1)} "
        "%get-tuple-element.278, s32[48]{0:T(128)S(1)} %get-tuple-element.276, "
        "s32[256,128]{1,0:T(8,128)S(1)} %fusion.12, s32[512,128]{1,0:T(8,128)S(1)} "
        "%fusion.13), custom_call_target=\"tpu_custom_call\""
    )
    ops = {0: [(10 * MS, 11 * MS, name), (20 * MS, 22 * MS, "%while.3 = s32[] while()")]}
    tr = tracereduce.build(ops, {}, [(0, 100 * MS, tracereduce.WINDOW)])
    ctx = {"trace": tr, "peaks": {"hbm_bytes_per_s": 819e9}}
    want = roofline.sorted_member_bytes(256 * 128, 512 * 128) / 819e9 / 1e-3 * 100
    assert sorted_member_roofline.read(ctx) == pytest.approx(want)
    assert sorted_member_roofline.read(dict(ctx, trace=tracereduce.build(
        {0: ops[0][1:]}, {}, [(0, 100 * MS, tracereduce.WINDOW)]))) is None
    assert tracereduce.short_name(name) == "%_sorted_member_jit.1"
    nested = {0: [(0, 10, "a"), (2, 5, "b"), (10, 12, "c")]}
    assert tracereduce.outermost(nested) == {0: [(0, 10, "a"), (10, 12, "c")]}
