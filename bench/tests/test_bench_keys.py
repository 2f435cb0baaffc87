"""The readers of the fact keys' width and cost (``keys_ms``,
``key_words``, ``sorted_member_wide_roofline``) on a real profiler trace
of a traced CPU run, with a TPU plane of synthetic device ops added to
it; and the ``lubm5`` configuration's data past the one-word id range."""

from __future__ import annotations

import copy
import os

import pytest

from bench import harness, roofline, roofline_wide, tracereduce
from bench.metrics import key_words, keys_ms, sorted_member_wide_roofline
from bench.tests.tiny import run, tiny_cell

US = 1000  # ns

#: the wide kernel's op as a v5e trace names it, its HLO text: the three
#: prefetched schedules, the probes' high and low words, the table's
WIDE_OP = (
    "%_sorted_member_wide_jit.1 = s32[256,128]{1,0:T(8,128)S(1)} custom-call("
    "s32[48]{0:T(128)S(1)} %get-tuple-element.280, s32[48]{0:T(128)S(1)} "
    "%get-tuple-element.278, s32[48]{0:T(128)S(1)} %get-tuple-element.276, "
    "s32[256,128]{1,0:T(8,128)S(1)} %fusion.12, s32[256,128]{1,0:T(8,128)S(1)} "
    "%fusion.13, /*index=5*/s32[512,128]{1,0:T(8,128)S(1)} %fusion.14, "
    "s32[512,128]{1,0:T(8,128)S(1)} %fusion.15), "
    'custom_call_target="tpu_custom_call"'
)


def _device_plane(t0: int, keys_scope: str) -> str:
    """A TPU plane whose ops start at ``t0`` ns: a join sort (2 us), a sort
    under ``keys_scope`` (3 us) and the wide kernel in ``merge/dedup``
    (1 us)."""
    op = WIDE_OP.replace('"', '\\"')
    return (
        'planes { id: 90 name: "/device:TPU:0"\n'
        f'  lines {{ id: 1 name: "XLA Ops" timestamp_ns: {t0}\n'
        "    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }\n"
        "    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 3000000 }\n"
        "    events { metadata_id: 3 offset_ps: 7000000 duration_ps: 1000000 }\n"
        "  }\n"
        '  event_metadata { key: 1 value { id: 1 name: "%sort.1 = s32[] sort()"\n'
        '    stats { metadata_id: 7 str_value: "jit(body_round)/join/r3/sort:" } } }\n'
        '  event_metadata { key: 2 value { id: 2 name: "%sort.2 = s32[] sort()"\n'
        f'    stats {{ metadata_id: 7 str_value: "jit(body_round)/{keys_scope}/sort:" }} }} }}\n'
        f'  event_metadata {{ key: 3 value {{ id: 3 name: "{op}"\n'
        '    stats { metadata_id: 7 str_value: "jit(body_round)/merge/dedup/x:" } } }\n'
        '  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }\n'
        "}\n"
    )


@pytest.fixture
def registry():
    from repro.obs import MetricsRegistry, set_registry

    reg = MetricsRegistry()
    prev = set_registry(reg)
    yield reg
    set_registry(prev)


def _traced(tmp_path, monkeypatch, cell, keys_scope="merge/keys"):
    """A traced CPU run of ``cell``, its trace given a TPU plane of
    synthetic ops inside the window; the readers' context."""
    from jax.profiler import ProfileData

    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    r = run(cell, trace=True)
    assert r["correct"] and r["failed"] == 0
    path = tracereduce.find_xplane(str(tmp_path))
    lo, _ = tracereduce.from_xplane(path).window
    with open(path, "ab") as fh:  # serialized XSpaces concatenate
        fh.write(ProfileData.text_proto_to_serialized_xspace(
            _device_plane(lo + 10 * US, keys_scope)
        ))
    trace = tracereduce.from_xplane(path)
    assert trace.ops  # the synthetic plane is read as the device's
    return {
        "trace": trace,
        "units": r["attempted"],
        "rounds": r["attempted"],
        "peaks": harness.load_peaks()["devices"]["TPU v5 lite"],
    }


def _wide_cell():
    """The tiny lubm5 cell with 40,000 universities for degrees to name:
    every id after them is past ``2**15``."""
    cell = copy.deepcopy(tiny_cell("lubm5.materialise"))
    cell["config"]["params"]["universities"] = 40_000
    return cell


def test_readers_on_a_cpu_trace_with_device_ops(tmp_path, monkeypatch, registry):
    ctx = _traced(tmp_path, monkeypatch, tiny_cell("lubm1.materialise"))
    n = ctx["units"]
    assert keys_ms.read(ctx) == pytest.approx(3e-3 / n)
    assert key_words.read(ctx) == 1
    want = 100 * roofline_wide.sorted_member_wide_bytes(
        256 * 128, 512 * 128
    ) / 819e9 / 1e-6
    assert sorted_member_wide_roofline.read(ctx) == pytest.approx(want)
    # the one-word kernel's reader never takes the wide kernel's op
    from bench.metrics import sorted_member_roofline

    assert sorted_member_roofline.read(ctx) is None


def test_key_words_reads_two_past_the_wall(tmp_path, monkeypatch, registry):
    ctx = _traced(tmp_path, monkeypatch, _wide_cell())
    assert key_words.read(ctx) == 2


def test_readers_give_nothing_where_the_program_has_nothing(
    tmp_path, monkeypatch, registry
):
    # programs without the keys scope: their keys sort sits in merge
    ctx = _traced(tmp_path, monkeypatch, tiny_cell("lubm1.materialise"),
                  keys_scope="merge")
    assert keys_ms.read(ctx) is None
    # no dist.key_words gauge set (an engine that sets none)
    registry.reset()
    assert key_words.read(ctx) is None
    # no device trace: nothing, as the CPU rehearsal reports
    bare = {"trace": None, "units": ctx["units"], "rounds": ctx["rounds"],
            "peaks": ctx["peaks"]}
    for reader in (keys_ms, key_words, sorted_member_wide_roofline):
        assert reader.read(dict(bare)) is None, reader.__name__


def test_wide_bytes():
    assert roofline_wide.sorted_member_wide_bytes(1024, 4096) == 4 * (
        3 * 1024 + 2 * 4096
    )
    assert roofline_wide.sorted_member_wide_bytes(1, 1) > roofline.sorted_member_bytes(1, 1)


def test_lubm5_data_is_past_the_one_word_ids():
    from repro.core.distributed import ONE_WORD_IDS

    cfg = harness.load_json(os.path.join(harness.ROOT, "bench/configs/lubm5.json"))
    sizes = []
    for seed in (3, 2**31 + 99):
        facts = harness.generate(cfg, seed)
        assert max(int(r.max()) for r in facts.values()) >= ONE_WORD_IDS
        sizes.append({p: len(r) for p, r in facts.items()})
    assert sizes[0] == sizes[1]
    assert sum(sizes[0].values()) == 679_479
