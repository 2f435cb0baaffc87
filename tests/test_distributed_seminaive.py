"""Distributed semi-naive + incremental delta exchange: parity against
the host engines, work-skipping evidence (the acceptance criteria of the
delta-restricted rounds), exchange regrow, and differential ``apply``
against a host IncrementalStore.

Runs on whatever mesh the session has (1 CPU device locally; the CI
multi-device matrix forces 4, exercising real ``all_to_all``)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
from jax.sharding import Mesh  # noqa: E402

from repro.core import CMatEngine, flat_seminaive  # noqa: E402
from repro.core.distributed import DistributedEngine  # noqa: E402
from repro.core.generators import chain, lubm_like, random_kb  # noqa: E402
from repro.incremental import IncrementalStore  # noqa: E402


def make_mesh():
    devs = np.asarray(jax.devices())
    return Mesh(devs, ("data",))


def as_sets(facts):
    return {
        p: frozenset(map(tuple, np.asarray(r).astype(np.int64).tolist()))
        for p, r in facts.items()
        if np.asarray(r).shape[0]
    }


def two_atom(program):
    rules = [r for r in program if len(r.body) <= 2]
    return type(program)(rules)


def supported(program):
    """The engine's own fragment filter (shared with serve/benches)."""
    return DistributedEngine.supported_program(program)


def subtract(dataset, dels):
    out = {}
    for pred, rows in dataset.items():
        rows = np.asarray(rows, dtype=np.int64).reshape(len(rows), -1)
        drop = {
            tuple(r)
            for r in np.asarray(
                dels.get(pred, np.zeros((0, rows.shape[1])))
            ).astype(np.int64).reshape(-1, rows.shape[1]).tolist()
        }
        keep = [r for r in rows.tolist() if tuple(r) not in drop]
        if keep:
            out[pred] = np.asarray(keep, dtype=np.int64)
    return out


def union(dataset, adds):
    out = {p: np.asarray(r, dtype=np.int64) for p, r in dataset.items()}
    for pred, rows in adds.items():
        rows = np.asarray(rows, dtype=np.int64).reshape(len(rows), -1)
        prev = out.get(pred)
        merged = rows if prev is None else np.concatenate([prev, rows])
        out[pred] = np.unique(merged, axis=0)
    return out


def pick_batch(dataset, k, seed=0):
    rng = np.random.default_rng(seed)
    pool = [
        (p, tuple(int(v) for v in row))
        for p, rows in dataset.items()
        for row in np.asarray(rows).reshape(len(rows), -1)
    ]
    rng.shuffle(pool)
    out: dict[str, list] = {}
    for p, row in pool[:k]:
        out.setdefault(p, []).append(row)
    return {p: np.asarray(r, dtype=np.int64) for p, r in out.items()}


KBS = [
    ("chain", lambda: chain(15)),
    ("lubm", lambda: lubm_like(n_dept=3, n_students=40, n_courses=6, seed=0)),
]


# --------------------------------------------------------------------- #
# semi-naive parity + work skipping (the tentpole acceptance criteria)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("name,gen", KBS)
def test_seminaive_parity_and_skips_work(name, gen):
    """Delta-restricted rounds reach the same fixpoint as FlatEngine and
    CMatEngine, skip (rule, pivot) pairs without a probe, and join
    strictly fewer rows than the naive distributed path."""
    program, dataset, _ = gen()
    program = two_atom(program)
    want = as_sets(flat_seminaive(program, dataset))
    cmat = CMatEngine(program)
    cmat.load(dataset)
    cmat.materialise()
    assert as_sets(cmat.materialisation()) == want

    sn = DistributedEngine(program, make_mesh(), capacity=1 << 12)
    got = as_sets(sn.materialise(dataset))
    assert got == want

    nv = DistributedEngine(
        program, make_mesh(), capacity=1 << 12,
        seminaive=False, planner_exchange_keys=False,
    )
    assert as_sets(nv.materialise(dataset)) == want

    assert sn.stats.rows_joined < nv.stats.rows_joined
    assert sn.stats.rule_applications_skipped > 0
    if name == "lubm":
        # acceptance: the lubm preset demonstrably skips work
        assert sn.stats.per_stratum  # stratified fixpoint ran
        assert sn.stats.n_strata > 1


def test_round_deltas_strictly_shrink_on_acyclic_data():
    """On transitive closure over an acyclic chain, per-round deltas
    shrink monotonically — the delta restriction is doing its job."""
    program, dataset, _ = chain(20)
    eng = DistributedEngine(program, make_mesh(), capacity=1 << 11)
    eng.materialise(dataset)
    news = [r["new_facts"] for r in eng.stats.per_round]
    # drop the trailing empty fixpoint round(s)
    while news and news[-1] == 0:
        news.pop()
    assert len(news) >= 3
    assert all(a > b for a, b in zip(news, news[1:])), news


def test_planner_exchange_keys_skip_aligned_sides():
    """chain TC: ``edge(y, z)`` stores y first, so the planner-keyed join
    never re-exchanges the edge side (visible whenever the mesh has >1
    shard; on 1 shard no exchange is scheduled at all)."""
    program, dataset, _ = chain(12)
    mesh = make_mesh()
    eng = DistributedEngine(program, mesh, capacity=1 << 11)
    eng.materialise(dataset)
    if mesh.shape["data"] > 1:
        assert eng.stats.exchanges_skipped > 0
        assert eng.stats.exchanges > 0
    else:
        assert eng.stats.exchanges == 0


def test_merge_block_exact_fill_keeps_last_row():
    """Appending exactly up to capacity must not lose the row written to
    the final slot: parked non-fresh writes are dropped out of bounds,
    never scattered onto slot cap-1 (duplicate-index scatter order is
    undefined)."""
    import jax.numpy as jnp

    program, dataset, _ = chain(3)
    eng = DistributedEngine(program, make_mesh(), capacity=8)
    trows = jnp.asarray(
        np.concatenate(
            [np.arange(12).reshape(6, 2), np.full((2, 2), -1)]
        ).astype(np.int32)
    )
    # candidates: fresh, fresh, duplicate (parked) — 6 + 2 == capacity
    cand = jnp.asarray(np.asarray([[50, 50], [9, 9], [50, 50]], np.int32))
    valid = jnp.asarray([True, True, True])
    nrows, ncnt, n_fresh, overflow = eng._merge_block(
        trows, jnp.int32(6), cand, valid
    )
    got = np.asarray(nrows).tolist()
    assert int(ncnt) == 8 and int(n_fresh) == 2 and int(overflow) == 0
    assert [9, 9] in got and [50, 50] in got


def test_exchange_regrow_instead_of_abort():
    """A join bigger than join_capacity regrows padding and retries the
    round (counted in stats) instead of raising mid-fixpoint."""
    program, dataset, _ = chain(30)
    eng = DistributedEngine(
        program, make_mesh(), capacity=1 << 10, join_capacity=8
    )
    got = as_sets(eng.materialise(dataset))
    assert got == as_sets(flat_seminaive(program, dataset))
    assert eng.stats.exchange_regrows > 0
    # variants traced at superseded padding factors are evicted, not
    # stranded (long-running update loops would leak executables)
    stale = [
        k for k in eng._variants
        if isinstance(k[-1], int) and k[-1] != eng._factor
    ]
    assert not stale


def test_constants_out_of_packing_range_are_rejected():
    """Ids live in int32 below BIG, the sort sentinel: a negative id, or
    one at or past ``MAX_DIST_CONST`` (int32 max), must raise instead of
    silently corrupting joins."""
    program, dataset, _ = chain(5)
    for bad_id in (-1, 2**31 - 1):
        eng = DistributedEngine(program, make_mesh(), capacity=1 << 9)
        bad = dict(dataset)
        bad["edge"] = np.asarray([[bad_id, 1]], np.int64)
        with pytest.raises(ValueError, match="constants"):
            eng.materialise(bad)
        eng2 = DistributedEngine(program, make_mesh(), capacity=1 << 9)
        eng2.materialise(dataset)
        with pytest.raises(ValueError, match="constants"):
            eng2.apply(additions={"edge": np.asarray([[1, bad_id]], np.int64)})


# --------------------------------------------------------------------- #
# incremental deltas through the exchange
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("name,gen", KBS)
def test_apply_differential_vs_host_incremental(name, gen):
    """apply(adds, dels) lands on the host IncrementalStore's exact fact
    set (differential check_integrity) and round-trips back."""
    program, dataset, _ = gen()
    program = two_atom(program)
    dist = DistributedEngine(program, make_mesh(), capacity=1 << 12)
    dist.materialise(dataset)
    original = dist.to_dict()
    inc = IncrementalStore(program)
    inc.load(dataset)
    dist.check_integrity(inc)

    dels = pick_batch(dataset, 5, seed=1)
    arity_of = {
        p: np.asarray(r).reshape(len(r), -1).shape[1]
        for p, r in dataset.items()
    }
    adds = {
        p: (np.arange(2 * arity_of[p]).reshape(2, arity_of[p]) + 900).astype(
            np.int64
        )
        for p in list(dataset)[:2]
    }
    st = dist.apply(additions=adds, deletions=dels)
    inc.apply(additions=adds, deletions=dels)
    inc.check_integrity()
    dist.check_integrity(inc)
    assert st.epoch == 1
    assert st.n_del_explicit > 0 and st.n_add_explicit > 0

    # inverse batch restores the original materialisation bit for bit
    dist.apply(additions=dels, deletions=adds)
    inc.apply(additions=dels, deletions=adds)
    dist.check_integrity(inc)
    assert as_sets(dist.to_dict()) == as_sets(original)


def test_apply_delete_all_drains_the_shards():
    program, dataset, _ = chain(12)
    dist = DistributedEngine(program, make_mesh(), capacity=1 << 11)
    dist.materialise(dataset)
    st = dist.apply(deletions=dataset)
    assert dist.to_dict() == {}
    assert st.n_deleted > 0 and st.n_rederived == 0
    dist.apply(additions=dataset)
    assert as_sets(dist.to_dict()) == as_sets(
        flat_seminaive(program, dataset)
    )


def test_apply_requires_materialise_first():
    program, dataset, _ = chain(4)
    eng = DistributedEngine(program, make_mesh())
    with pytest.raises(RuntimeError, match="materialise"):
        eng.apply(additions=dataset)


def test_random_batches_match_rematerialisation():
    """Randomised add/delete batches applied sequentially: the sharded
    store equals a from-scratch re-materialisation of the updated EDB
    and stays in lockstep with the host IncrementalStore."""
    rng = np.random.default_rng(7)
    program, dataset = random_kb(
        rng, n_constants=8, n_facts=18, n_rules=4
    )
    program = supported(program)
    if not len(program.rules):
        pytest.skip("random draw produced no supported rules")
    dist = DistributedEngine(program, make_mesh(), capacity=1 << 11)
    dist.materialise(dataset)
    inc = IncrementalStore(program)
    inc.load(dataset)
    explicit = {p: np.asarray(r, np.int64) for p, r in dataset.items()}
    for trial in range(6):
        dels = {
            p: rows[
                rng.choice(
                    rows.shape[0],
                    size=int(rng.integers(1, rows.shape[0] + 1)),
                    replace=False,
                )
            ]
            for p, rows in explicit.items()
            if rows.shape[0] and rng.random() < 0.7
        }
        adds = {
            p: rng.integers(20, 26, size=(2, rows.shape[1])).astype(np.int64)
            for p, rows in dataset.items()
            if rng.random() < 0.5
        }
        dist.apply(additions=adds, deletions=dels)
        inc.apply(additions=adds, deletions=dels)
        explicit = union(subtract(explicit, dels), adds)
        want = as_sets(flat_seminaive(program, explicit))
        assert as_sets(dist.to_dict()) == want, f"trial {trial}"
        dist.check_integrity(inc)


# --------------------------------------------------------------------- #
# hypothesis round-trip (random batches on a fixed recursive program)
# --------------------------------------------------------------------- #
try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as hst

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - hypothesis is in requirements-dev
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    from repro.core import parse_program

    HYP_PROGRAM = parse_program(
        """
        edge(x, y) -> path(x, y)
        path(x, y), edge(y, z) -> path(x, z)
        edge(x, y) -> node(x)
        edge(x, y) -> node(y)
        """
    )

    @hst.composite
    def hyp_edges(draw):
        n = draw(hst.integers(min_value=2, max_value=8))
        rows = draw(
            hst.lists(
                hst.tuples(
                    hst.integers(min_value=0, max_value=6),
                    hst.integers(min_value=0, max_value=6),
                ),
                min_size=n,
                max_size=n,
            )
        )
        return np.unique(np.asarray(rows, dtype=np.int64), axis=0)

    @settings(
        max_examples=8,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    @given(data=hst.data(), edges=hyp_edges())
    def test_hypothesis_apply_round_trip(data, edges):
        """apply(adds, dels); apply(dels, adds) round-trips the sharded
        store bit-identically, with each intermediate state equal to a
        re-materialisation of the updated EDB."""
        dataset = {"edge": edges}
        dist = DistributedEngine(
            HYP_PROGRAM, make_mesh(), capacity=1 << 10
        )
        dist.materialise(dataset)
        original = dist.to_dict()

        k = data.draw(
            hst.integers(min_value=0, max_value=edges.shape[0])
        )
        dels = {"edge": edges[:k]} if k else {}
        n_add = data.draw(hst.integers(min_value=0, max_value=3))
        adds = {}
        if n_add:
            rows = data.draw(
                hst.lists(
                    hst.tuples(
                        hst.integers(min_value=100, max_value=104),
                        hst.integers(min_value=100, max_value=104),
                    ),
                    min_size=n_add,
                    max_size=n_add,
                )
            )
            adds = {"edge": np.unique(np.asarray(rows, np.int64), axis=0)}

        dist.apply(additions=adds, deletions=dels)
        want_mid = as_sets(
            flat_seminaive(
                HYP_PROGRAM, union(subtract(dataset, dels), adds)
            )
        )
        assert as_sets(dist.to_dict()) == want_mid

        dist.apply(additions=dels, deletions=adds)
        assert as_sets(dist.to_dict()) == as_sets(original)
