"""The sharded engine's spans on the profiler's clock, its count of
device->host reads, and the names of its round programs.

Runs on whatever mesh the session has (1 CPU device locally)."""

import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
from jax.sharding import Mesh  # noqa: E402

from repro.core.distributed import DistributedEngine  # noqa: E402
from repro.core.generators import chain, lubm_like  # noqa: E402
from repro.obs import MetricsRegistry, set_registry  # noqa: E402

#: the host round driver's leaf spans, all inside ``dist.materialise``
#: (``dist.sync`` is left: a materialisation reads nothing but each
#: round's packed block, in ``dist.wait``, and the pull)
LEAVES = (
    "dist.prepare", "dist.schedule", "dist.launch", "dist.wait",
    "dist.pull",
)


@pytest.fixture
def registry():
    r = MetricsRegistry()
    prev = set_registry(r)
    yield r
    set_registry(prev)


def _engine(capacity=1 << 11):
    program, dataset, _ = lubm_like(n_dept=2, n_students=15, n_courses=3, seed=1)
    program = DistributedEngine.supported_program(program)
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    return DistributedEngine(program, mesh, capacity=capacity), dataset


def test_materialise_spans_nest_under_materialise(registry, profile_host_events):
    eng, dataset = _engine()
    eng.materialise(dataset)  # builds every variant outside the session
    events = profile_host_events(lambda: eng.materialise(dataset))
    dist = [ev for ev in events if ev[0].startswith("dist.")]
    (mat,) = [ev for ev in dist if ev[0] == "dist.materialise"]
    assert mat[3]["n_strata"] == eng.stats.n_strata
    names = {ev[0] for ev in dist}
    assert set(LEAVES) <= names
    for name, start, end, _ in dist:
        assert mat[1] <= start and end <= mat[2], name
    rounds = [ev for ev in dist if ev[0] == "dist.round"]
    assert len(rounds) == eng.stats.rounds
    # every round launches, waits and reads its counts back
    for kind in ("dist.launch", "dist.wait"):
        assert sum(ev[0] == kind for ev in dist) == eng.stats.rounds
    assert all("rule_ids" in ev[3] and "new_facts" in ev[3] for ev in rounds)


def test_host_syncs_count_every_read_of_the_helper(registry):
    eng, dataset = _engine()
    calls = [0]
    fetch = eng._fetch

    def counted(x):
        calls[0] += 1
        return fetch(x)

    eng._fetch = counted
    eng.materialise(dataset)
    assert eng.stats.host_syncs == calls[0]
    # one wait a round, on its packed block, and one for the pull
    assert calls[0] <= eng.stats.rounds + 2
    assert registry.snapshot("dist.")["dist.host_syncs"] == calls[0]


class _NumpyReads:
    """numpy as the engine module sees it, counting device arrays that
    are read to the host outside ``DistributedEngine._fetch``."""

    def __init__(self):
        self.inside = False
        self.outside = 0

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, x, *a, **kw):
        if isinstance(x, jax.Array) and not self.inside:
            self.outside += 1
        return np.asarray(x, *a, **kw)

    array = asarray


def test_every_device_read_goes_through_the_helper(registry, monkeypatch):
    from repro.core import distributed

    program, dataset, _ = chain(12)
    eng = DistributedEngine(
        program, Mesh(np.asarray(jax.devices()), ("data",)), capacity=1 << 11
    )
    reads = _NumpyReads()
    monkeypatch.setattr(distributed, "np", reads)
    fetch = eng._fetch

    def inside(x):
        reads.inside = True
        try:
            return fetch(x)
        finally:
            reads.inside = False

    eng._fetch = inside
    eng.materialise(dataset)
    eng.apply(
        additions={"edge": np.array([[20, 21]])},
        deletions={"edge": dataset["edge"][:2]},
    )
    eng.to_dict()
    assert reads.outside == 0
    assert eng.stats.host_syncs > 0


def _shapes(eng, groups):
    """Abstract arguments: ``groups`` of 3 (rows, count, watermark) or 2
    (rows, count) arrays per predicate."""
    n, cap = eng.n_shards, eng.capacity
    out = []
    for width in groups:
        for p in eng._preds:
            out.append(jax.ShapeDtypeStruct((n, cap, eng._arities[p]), np.int32))
            out.extend(jax.ShapeDtypeStruct((n,), np.int32) for _ in range(width - 1))
    return out


def test_round_programs_are_named_by_kind(registry):
    eng, dataset = _engine()
    eng.materialise(dataset)
    fn, shapes = eng.abstract_round(eng._preds, eng._arities)
    lowered = fn.lower(*shapes)
    assert lowered.as_text().startswith("module @jit_body_round")
    # the phase scopes reach the ops' metadata
    scopes = set(re.findall(
        r'op_name="([^"]*)"', lowered.as_text(dialect="hlo", debug_info=True)
    ))
    assert any(re.search(r"(^|/)join/r\d+/", n) for n in scopes)
    assert any(re.search(r"(^|/)merge/dedup/", n) for n in scopes)
    if eng.n_shards > 1:
        assert any(re.search(r"(^|/)exchange/all_to_all", n) for n in scopes)
    merge = eng._build_merge().fn
    assert merge.lower(*_shapes(eng, (3, 2))).as_text().startswith(
        "module @jit_body_merge"
    )
    delete = eng._build_delete().fn
    assert delete.lower(*_shapes(eng, (3, 2))).as_text().startswith(
        "module @jit_body_delete"
    )
