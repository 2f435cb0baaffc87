"""The sharded engine at ids past ``2**15``, where a binary fact's key
takes two int32 words: ``materialise`` against ``FlatEngine`` and
``apply`` against ``IncrementalStore`` on KBs whose ids are remapped into
``[2**15, 2**31 - 2]``, with either membership (the Pallas kernel in
interpret mode), on the test suite's mesh (four CPU devices:
``test_distributed_wide_ids_multishard``)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
from jax.sharding import Mesh  # noqa: E402

from repro.core import flat_seminaive  # noqa: E402
from repro.core.datalog import Atom, Program, Rule  # noqa: E402
from repro.core.distributed import (  # noqa: E402
    MAX_DIST_CONST,
    ONE_WORD_IDS,
    DistributedEngine,
)
from repro.core.generators import chain, lubm_like  # noqa: E402
from repro.incremental import IncrementalStore  # noqa: E402

#: ids every remapped KB holds: the first that needs two words, both
#: sides of the 16-bit half, and the top of the range
EDGE_IDS = (2**15, 2**16 - 1, 2**16, 2**16 + 1, 2**31 - 3, 2**31 - 2)

KBS = [
    ("chain", lambda: chain(12)),
    ("lubm", lambda: lubm_like(n_dept=2, n_students=15, n_courses=3, seed=1)),
]


def wide_map(ids, seed):
    """A seeded injective map of ``ids`` into ``[2**15, 2**31 - 2]`` that
    uses every id of :data:`EDGE_IDS` and scrambles the ids' order."""
    rng = np.random.default_rng(seed)
    draws = rng.integers(ONE_WORD_IDS, MAX_DIST_CONST, size=2 * len(ids))
    pool = list(dict.fromkeys([*EDGE_IDS, *map(int, draws)]))[: len(ids)]
    return dict(zip(ids, map(int, rng.permutation(pool))))


def remap(facts, mapping):
    lookup = np.vectorize(mapping.__getitem__, otypes=[np.int64])
    return {
        p: lookup(np.asarray(r, np.int64)) if np.asarray(r).size
        else np.asarray(r, np.int64)
        for p, r in facts.items()
    }


def ids_of(*kbs):
    return sorted({
        int(v) for kb in kbs for r in kb.values() for v in np.asarray(r).ravel()
    })


def as_sets(facts):
    return {
        p: frozenset(map(tuple, np.asarray(r).astype(np.int64).tolist()))
        for p, r in facts.items()
        if np.asarray(r).shape[0]
    }


def make_engine(program, pallas, capacity=1 << 10):
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    return DistributedEngine(
        program, mesh, capacity=capacity, use_pallas_kernels=pallas
    )


def wide_kb(gen, seed=5):
    program, dataset, _ = gen()
    program = DistributedEngine.supported_program(program)
    mapping = wide_map(ids_of(dataset), seed)
    assert set(EDGE_IDS) <= set(mapping.values())
    return program, remap(dataset, mapping)


@pytest.mark.parametrize("pallas", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("name,gen", KBS)
def test_materialise_at_wide_ids_matches_flat(name, gen, pallas):
    program, dataset = wide_kb(gen)
    eng = make_engine(program, pallas)
    got = eng.materialise(dataset)
    assert eng.stats.key_words == 2
    assert as_sets(got) == as_sets(flat_seminaive(program, dataset))


@pytest.mark.parametrize("pallas", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("name,gen", KBS)
def test_apply_at_wide_ids_matches_incremental(name, gen, pallas):
    program, dataset, _ = gen()
    program = DistributedEngine.supported_program(program)
    rng = np.random.default_rng(11)
    # deletions from the KB, additions over fresh ids, all remapped
    dels = {}
    for p in sorted(dataset)[:3]:
        rows = np.asarray(dataset[p], np.int64).reshape(len(dataset[p]), -1)
        dels[p] = rows[rng.choice(len(rows), size=min(3, len(rows)), replace=False)]
    top = max(ids_of(dataset)) + 1
    adds = {}
    for p in sorted(dataset)[:2]:
        arity = np.asarray(dataset[p]).reshape(len(dataset[p]), -1).shape[1]
        adds[p] = (top + np.arange(2 * arity, dtype=np.int64)).reshape(2, arity)
    mapping = wide_map(ids_of(dataset, adds), 7)
    dataset, dels, adds = (remap(x, mapping) for x in (dataset, dels, adds))

    dist = make_engine(program, pallas)
    dist.materialise(dataset)
    original = dist.to_dict()
    inc = IncrementalStore(program)
    inc.load(dataset)
    dist.check_integrity(inc)

    st = dist.apply(additions=adds, deletions=dels)
    inc.apply(additions=adds, deletions=dels)
    assert st.key_words == 2
    assert st.n_del_explicit > 0 and st.n_add_explicit > 0
    dist.check_integrity(inc)

    dist.apply(additions=dels, deletions=adds)
    inc.apply(additions=dels, deletions=adds)
    dist.check_integrity(inc)
    assert as_sets(dist.to_dict()) == as_sets(original)


@pytest.mark.parametrize("pallas", [False, True], ids=["jnp", "pallas"])
def test_one_word_engine_widens_when_an_apply_crosses_the_wall(pallas):
    program, dataset, _ = chain(12)
    dist = make_engine(program, pallas)
    dist.materialise(dataset)
    assert dist.stats.key_words == 1
    one_word = set(dist._variants)
    inc = IncrementalStore(program)
    inc.load(dataset)

    adds = {"edge": np.asarray(
        [[12, ONE_WORD_IDS], [ONE_WORD_IDS, 2**31 - 3], [2**31 - 3, 2**16 + 1]],
        np.int64,
    )}
    dels = {"edge": np.asarray([[5, 6]], np.int64)}
    st = dist.apply(additions=adds, deletions=dels)
    inc.apply(additions=adds, deletions=dels)
    assert st.key_words == 2
    dist.check_integrity(inc)
    # the state was rows all along: the same buffers, new variants
    wide = set(dist._variants) - one_word
    layout = (dist._preds, 2)  # a variant key's layout: predicates, words
    assert wide and all(layout in key or layout in key[1] for key in wide)
    # and it stays wide for the next batch, which holds only small ids
    st = dist.apply(deletions={"edge": np.asarray([[1, 2]], np.int64)})
    inc.apply(deletions={"edge": np.asarray([[1, 2]], np.int64)})
    assert st.key_words == 2
    dist.check_integrity(inc)


def test_a_rule_constant_past_the_wall_widens_the_keys():
    program = Program([
        Rule(body=(Atom("edge", ("x", "y")),), head=Atom("path", ("x", "y"))),
        Rule(
            body=(Atom("path", ("x", "y")), Atom("edge", ("y", "z"))),
            head=Atom("path", ("x", "z")),
        ),
        Rule(body=(Atom("edge", ("x", "y")),), head=Atom("tag", ("x", 2**20))),
    ])
    _, dataset, _ = chain(10)
    eng = make_engine(program, False)
    got = eng.materialise(dataset)
    assert eng.stats.key_words == 2
    assert as_sets(got) == as_sets(flat_seminaive(program, dataset))
