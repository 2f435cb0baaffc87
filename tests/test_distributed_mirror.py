"""The sharded engine's host mirror of counts and watermarks, its one
device->host read a round, and the benchmark's traced path over it.

Runs on whatever mesh the session has (1 CPU device locally; the
four-device mesh is checked in ``test_distributed_multishard``)."""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
from jax.sharding import Mesh  # noqa: E402

from repro.core import flat_seminaive  # noqa: E402
from repro.core.distributed import DistributedEngine  # noqa: E402
from repro.core.generators import chain, lubm_like  # noqa: E402
from repro.obs import MetricsRegistry, set_registry  # noqa: E402


@pytest.fixture
def registry():
    r = MetricsRegistry()
    prev = set_registry(r)
    yield r
    set_registry(prev)


def _kb(name):
    if name == "chain":
        program, dataset, _ = chain(12)
    else:
        program, dataset, _ = lubm_like(
            n_dept=2, n_students=15, n_courses=3, seed=1
        )
        program = DistributedEngine.supported_program(program)
    return program, dataset


def _engine(program, capacity=1 << 11):
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    return DistributedEngine(program, mesh, capacity=capacity)


def _update(dataset):
    """A batch that deletes two facts of the largest predicate and adds
    one fact over constants no fact uses yet."""
    pred = max(sorted(dataset), key=lambda p: len(dataset[p]))
    rows = np.asarray(dataset[pred], np.int64).reshape(len(dataset[pred]), -1)
    top = max(int(np.asarray(r).max()) for r in dataset.values() if len(r))
    new = np.arange(top + 1, top + 1 + rows.shape[1], dtype=np.int64)[None]
    return pred, {pred: new}, {pred: rows[:2]}


def _assert_mirror_exact(eng):
    for p in eng._preds:
        cnt, lo = eng._mirror[p]
        assert cnt.dtype == lo.dtype == np.int32, p
        assert cnt.shape == lo.shape == (eng.n_shards,), p
        np.testing.assert_array_equal(cnt, np.asarray(eng._state[p][1]), p)
        np.testing.assert_array_equal(lo, np.asarray(eng._state[p][2]), p)
        assert eng._counts[p] == int(cnt.sum()), p


def _facts(rows_by_pred):
    return {
        p: {tuple(map(int, r)) for r in np.asarray(rows)}
        for p, rows in rows_by_pred.items() if len(rows)
    }


@pytest.mark.parametrize("name", ["lubm", "chain"])
def test_mirror_is_exact_after_materialise_and_apply(name, registry):
    program, dataset = _kb(name)
    eng = _engine(program)
    got = eng.materialise(dataset)
    _assert_mirror_exact(eng)
    assert _facts(got) == _facts(flat_seminaive(program, dataset))

    pred, adds, dels = _update(dataset)
    st = eng.apply(additions=adds, deletions=dels)
    assert st.n_del_explicit == 2 and st.n_add_explicit == 1
    _assert_mirror_exact(eng)
    kept = {p: np.asarray(r, np.int64) for p, r in dataset.items()}
    gone = {tuple(r) for r in dels[pred].tolist()}
    kept[pred] = np.asarray(
        [r for r in kept[pred].reshape(len(kept[pred]), -1).tolist()
         if tuple(r) not in gone] + adds[pred].tolist(),
        np.int64,
    )
    eng.check_integrity(flat_seminaive(program, kept))

    # a deletion alone, then an addition alone: the delete and the
    # merge each set the mirror on their own
    eng.apply(deletions=adds)
    _assert_mirror_exact(eng)
    eng.apply(additions=dels)
    _assert_mirror_exact(eng)
    dataset = {p: np.asarray(r, np.int64) for p, r in dataset.items()}
    eng.check_integrity(flat_seminaive(program, dataset))


def test_one_read_a_round_and_one_for_the_pull(registry):
    program, dataset = _kb("lubm")
    eng = _engine(program)
    eng.materialise(dataset)  # builds every variant
    waits = []
    fetch = eng._fetch

    def counted(x):
        waits.append(len(x) if isinstance(x, (list, tuple)) else 1)
        return fetch(x)

    eng._fetch = counted
    eng.materialise(dataset)
    live = sum(1 for p in eng._preds if eng._counts[p])
    # every round's packed block, then all non-empty buffers at once
    assert waits == [1] * eng.stats.rounds + [live]
    assert eng.stats.host_syncs == eng.stats.rounds + 1


def test_fetch_reads_a_sequence_in_one_wait(registry):
    program, _ = _kb("chain")
    eng = _engine(program)
    xs = [jax.numpy.arange(4), jax.numpy.ones((2, 3), jax.numpy.int32)]
    got = eng._fetch(xs)
    assert isinstance(got, list) and len(got) == 2
    np.testing.assert_array_equal(got[0], np.arange(4))
    np.testing.assert_array_equal(got[1], np.ones((2, 3), np.int32))
    one = eng._fetch(xs[0])
    assert isinstance(one, np.ndarray)
    assert eng.stats.host_syncs == 2


def test_provenance_growth_reads_the_mirror(registry):
    from repro.obs.provenance import get_journal

    program, dataset = _kb("chain")
    eng = _engine(program)
    journal = get_journal()
    was = journal.enabled
    journal.enabled = True
    journal.clear()
    try:
        eng.materialise(dataset)
        grown = sum(r.n_new for r in journal.records if r.kind == "apply")
    finally:
        journal.enabled = was
        journal.clear()
    explicit = sum(int(r.shape[0]) for r in eng.explicit.values())
    assert grown == sum(eng._counts.values()) - explicit
    assert eng.stats.host_syncs == eng.stats.rounds + 1


def test_traced_rehearsal_reads_every_per_layer_metric(
    registry, monkeypatch, tmp_path
):
    """The path a ``--trace 1`` run takes on the chip, on the CPU: the
    harness wraps ``_prepare`` and ``_run_round`` by attribute, then every
    per-layer reader of every cell reads the run's own profiler trace,
    with a synthetic device-op list standing in for the TPU plane the
    CPU does not have."""
    from bench import harness, tracereduce
    from bench.tests.tiny import CELLS, run, tiny_cell

    assert callable(DistributedEngine._prepare)
    assert callable(DistributedEngine._run_round)
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    r = run(tiny_cell("lubm1.materialise"), trace=True)
    assert r["correct"] and r["failed"] == 0
    per_unit = r["metrics"]["rounds.materialise"]["value"]
    assert per_unit == pytest.approx(21.0)

    trace = tracereduce.from_xplane(tracereduce.find_xplane(str(tmp_path)))
    lo, hi = trace.window
    step = (hi - lo) // 8
    ops = [(lo + k * step, lo + k * step + step // 2, "%fusion.1 = s32[] fusion()")
           for k in range(8)]
    ops.append((lo + step // 2, lo + step, "%_sorted_member_jit.1 = s32[8,128] "
                "custom-call(s32[8,128]{1,0} %p0, s32[16,128]{1,0} %p1)"))
    trace = dataclasses.replace(
        trace, ops={0: sorted(ops)},
        modules={0: [(s, e, "jit_body_round") for s, e, _ in ops[:8]]},
    )
    ctx = {
        "trace": trace,
        "units": r["attempted"],
        "rounds": round(per_unit * r["attempted"]),
        "peaks": harness.load_peaks()["devices"]["TPU v5 lite"],
    }
    names = sorted({m["name"] for c in CELLS
                    for m in harness.load_cell(c)["per_layer"]})
    got = {name: harness.read_metric(name, ctx) for name in names}
    assert got["host_syncs_per_round.materialise"] <= 1.1
    assert got["sync_ms_per_round.materialise"] == pytest.approx(0.0, abs=1e-3)
    # the engine's spans were read from the run's trace
    for name in ("prepare_ms", "schedule_ms_per_round", "launch_ms_per_round",
                 "pull_ms", "gc_ms", "idle_unattributed_pct",
                 "round_program_ms", "device_idle_pct", "host_ms_per_round",
                 "sorted_member_roofline"):
        assert got[name + ".materialise"] is not None, name
