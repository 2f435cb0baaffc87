"""A run whose timed path is broken underneath must come out not
correct; so must the control (the reference cut one round short).

The faults a one-chip materialise cell can have: a step that returns
its state unchanged, half of the batch left out, and an answer altered
where it is produced.  (No exchange between chips exists on one chip.)
"""

from __future__ import annotations

import numpy as np
import pytest

from bench import control, harness
from bench.tests.tiny import CELLS, run, tiny_cell
from repro.core.distributed import DistributedEngine


def _half(batch):
    return {p: np.asarray(r)[: len(r) // 2] for p, r in (batch or {}).items()}


class Unchanged(DistributedEngine):
    """No round runs: materialise returns its input."""

    def _stratum_fixpoint(self, *a, **kw):
        return 0, True


class HalfBatch(DistributedEngine):
    """Half of every predicate's rows left out of each batch."""

    def materialise(self, dataset, max_rounds=64):
        return super().materialise(_half(dataset), max_rounds=max_rounds)


class Altered(DistributedEngine):
    """One fact of the answer altered where it is produced."""

    @staticmethod
    def _alter(kb):
        pred = max(kb, key=lambda p: len(kb[p]))
        rows = np.array(kb[pred])
        rows[0, -1] += 1
        return dict(kb, **{pred: rows})

    def materialise(self, dataset, max_rounds=64):
        return self._alter(super().materialise(dataset, max_rounds=max_rounds))


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [Unchanged, HalfBatch, Altered])
def test_fault_is_not_correct(fault, name):
    r = run(tiny_cell(name), engine_cls=fault)
    assert r["correct"] is False
    assert r["failed"] >= 1
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


@pytest.mark.parametrize("seed", [2**31 + 11, 5_000_000_017])
@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, seed):
    cell = tiny_cell(name)
    r = run(cell, seed=seed,
            engine_cls=control.control_engine(harness.rules(cell["config"])))
    assert r["correct"] is False
    assert r["checks"]["missing_facts"]["value"] > 0
