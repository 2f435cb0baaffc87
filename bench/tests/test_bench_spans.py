"""The engine-span reduction and its readers, on a synthetic trace, and
the span reader on a real profiler trace of the CPU."""

from __future__ import annotations

import pytest

from bench import spanreduce, tracereduce
from bench.metrics import (
    dedup_ms,
    gc_ms,
    host_syncs_per_round,
    idle_unattributed_pct,
    join_ms,
    launch_ms_per_round,
    prepare_ms,
    pull_ms,
    schedule_ms_per_round,
    sync_ms_per_round,
)

MS = 1_000_000  # ns
LINE = ("/host:CPU", "python")


def _ctx():
    # window [0, 100) ms; device busy [10, 40) and [60, 70)
    ops = {0: [
        (10 * MS, 30 * MS, "%while.1"), (30 * MS, 40 * MS, "%fusion.2"),
        (32 * MS, 35 * MS, "%sort.3"), (60 * MS, 70 * MS, "%fusion.4"),
        (150 * MS, 160 * MS, "%fusion.5"),
    ]}
    trace = tracereduce.build(ops, {}, [(0, 100 * MS, tracereduce.WINDOW)])
    phased = {0: [
        (10 * MS, 30 * MS, "join"), (30 * MS, 40 * MS, "dedup"),
        (32 * MS, 35 * MS, "join"),  # nested in the dedup op: not counted
        (60 * MS, 70 * MS, "merge"), (150 * MS, 160 * MS, "join"),
    ]}
    spans = [
        # materialisation 1: one round
        (0, 50, "dist.materialise"), (0, 8, "dist.prepare"),
        (8, 45, "dist.round"), (8, 10, "dist.launch"), (10, 40, "dist.wait"),
        (40, 42, "dist.sync"), (42, 46, "dist.schedule"), (43, 45, "dist.sync"),
        (46, 50, "dist.pull"),
        # materialisation 2: one round, a collection, nothing in [71, 80)
        (50, 100, "dist.materialise"), (50, 58, "dist.prepare"),
        (58, 72, "dist.round"), (58, 60, "dist.launch"), (60, 70, "dist.wait"),
        (70, 71, "dist.sync"), (80, 90, "host.gc"), (90, 99, "dist.pull"),
        # one that the window cuts: clipped, and not a denominator
        (95, 120, "dist.materialise"), (95, 105, "dist.prepare"),
        (100, 110, "dist.round"),
    ]
    host = [(s * MS, e * MS, n, LINE) for s, e, n in spans]
    return {"trace": trace, "spans": spanreduce.build(host, phased),
            "units": 2, "rounds": 2, "peaks": None}


def test_span_readers_per_materialisation_and_round():
    ctx = _ctx()
    # [0,8) + [50,58) + [95,100), the last clipped by the window, over 2
    assert prepare_ms.read(ctx) == pytest.approx(21 / 2)
    assert pull_ms.read(ctx) == pytest.approx((4 + 9) / 2)
    assert gc_ms.read(ctx) == pytest.approx(10 / 2)
    assert launch_ms_per_round.read(ctx) == pytest.approx(4 / 2)
    assert sync_ms_per_round.read(ctx) == pytest.approx((2 + 2 + 1) / 2)
    # self time: the schedule's [42, 46) less its sync [43, 45)
    assert schedule_ms_per_round.read(ctx) == pytest.approx(2 / 2)


def test_idle_attribution_to_leaf_spans():
    # idle [0,10) [40,60) [70,100) = 60 ms; no leaf is open in [71, 80)
    assert idle_unattributed_pct.read(_ctx()) == pytest.approx(100 * 9 / 60)


def test_phase_readers_take_outermost_ops_in_the_window():
    ctx = _ctx()
    assert join_ms.read(ctx) == pytest.approx(20 / 2)
    assert dedup_ms.read(ctx) == pytest.approx(10 / 2)
    unscoped = dict(ctx, spans=spanreduce.build(
        ctx["spans"].host, {0: [(10 * MS, 30 * MS, None)]}))
    assert join_ms.read(unscoped) is None


def test_phase_of_scope_paths():
    assert spanreduce.phase_of("jit(body_round)/jit(main)/join/r3/sort") == "join"
    assert spanreduce.phase_of("jit(body_round)/join/r3/exchange/sort") == "exchange"
    assert spanreduce.phase_of("jit(body_round)/merge/dedup/while") == "dedup"
    assert spanreduce.phase_of("jit(f)/add") is None
    assert spanreduce.phase_of(None) is None


def test_nothing_to_read_gives_nothing():
    ctx = _ctx()
    readers = (prepare_ms, pull_ms, gc_ms, launch_ms_per_round,
               sync_ms_per_round, schedule_ms_per_round,
               idle_unattributed_pct, join_ms, dedup_ms)
    # no device trace (the CPU), or a trace without the engine's spans
    # (a program that emits none)
    for case in (dict(ctx, trace=None, spans=None),
                 dict(ctx, spans=spanreduce.build([], {}))):
        for reader in readers:
            assert reader.read(case) is None, reader.__name__
    assert host_syncs_per_round.read(dict(ctx, trace=None)) is None


def test_host_syncs_per_round_reads_the_registry():
    from repro.obs import MetricsRegistry, set_registry

    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        assert host_syncs_per_round.read(_ctx()) is None  # no counter yet
        reg.counter("dist.host_syncs").inc(1890)
        reg.counter("dist.rounds").inc(21)
        assert host_syncs_per_round.read(_ctx()) == pytest.approx(90.0)
    finally:
        set_registry(prev)


def test_engine_spans_read_from_a_cpu_profile(tmp_path):
    import jax

    from repro.obs import span

    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("dist.materialise"):
            with span("dist.sync"):
                pass
        with span("other.thing"):
            pass
    finally:
        jax.profiler.stop_trace()
    got = spanreduce.from_xplane(tracereduce.find_xplane(str(tmp_path)))
    mine = [ev for ev in got.host if not ev[2].startswith("host.gc")]
    assert [n for _s, _e, n, _line in mine] == ["dist.materialise", "dist.sync"]
    (s0, e0, *_), (s1, e1, *_) = mine
    assert s0 <= s1 and e1 <= e0
    assert got.ops == {}  # no TPU plane on the CPU


#: a v5e-shaped trace: op scopes live on the event metadata (``tf_op``,
#: a string or a reference to a stat name), a ``while`` carries none,
#: and one op name appears in two programs with different phases
XSPACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 5000000 }
    events { metadata_id: 3 offset_ps: 20000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 30000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = s32[] while()" } }
  event_metadata { key: 2 value { id: 2 name: "%sort.2 = s32[] sort()"
    stats { metadata_id: 7 str_value: "jit(body_round)/join/r3/sort:" } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = s32[] fusion()"
    stats { metadata_id: 7 ref_value: 8 } } }
  event_metadata { key: 4 value { id: 4 name: "%iota.4 = s32[] iota()"
    stats { metadata_id: 7 str_value: "jit(body_round)/merge/iota:" } } }
  event_metadata { key: 5 value { id: 5 name: "%iota.4 = s32[] iota()"
    stats { metadata_id: 7 str_value: "jit(body_round)/merge/dedup/iota:" } } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "jit(body_round)/merge/dedup/x:" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 2 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "dist.materialise" } }
  event_metadata { key: 2 value { id: 2 name: "dist.prepare" } }
  event_metadata { key: 3 value { id: 3 name: "bench.round" } }
}
'''


def test_op_phases_from_the_event_metadata(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    assert spanreduce.op_phases(str(path)) == {0: {
        "%while.1 = s32[] while()": None,
        "%sort.2 = s32[] sort()": "join",
        "%fusion.3 = s32[] fusion()": "dedup",
        "%iota.4 = s32[] iota()": None,  # merge in one program, dedup in another
    }}
    got = spanreduce.from_xplane(str(path))
    us = 1000  # ns
    assert got.ops == {0: [
        (1 * us, 21 * us, None), (2 * us, 7 * us, "join"),
        (21 * us, 25 * us, "dedup"), (31 * us, 32 * us, None),
    ]}
    assert [n for _s, _e, n, _line in got.host] == ["dist.materialise", "dist.prepare"]
    # the while takes the one phase of the op that ran inside it
    assert spanreduce.outer_phases(got.ops[0]) == [
        (1 * us, 21 * us, "join"), (21 * us, 25 * us, "dedup"),
        (31 * us, 32 * us, None),
    ]
    mixed = [(0, 10, None), (1, 2, "join"), (3, 4, "dedup")]
    assert spanreduce.outer_phases(mixed) == [(0, 10, None)]
