"""Cells of ``BENCHMARK.json`` cut to a size the CPU tests can hold."""

from __future__ import annotations

import copy

from bench import harness

CELLS = [w["name"] for w in harness.load_json(
    f"{harness.ROOT}/BENCHMARK.json")["workloads"]]

#: per generator, the parameters that shrink it, and a capacity
TINY = {
    "uba": ({
        "universities": 12, "departments": 2, "groups_per_dept": [2, 3],
        "FullProfessor_per_dept": [2, 3], "AssociateProfessor_per_dept": [1, 2],
        "AssistantProfessor_per_dept": [1, 1], "Lecturer_per_dept": [1, 1],
        "undergrads_per_faculty": [2, 3], "grads_per_faculty": [1, 2],
        "pubs_per_FullProfessor": [2, 3], "pubs_per_AssociateProfessor": [1, 2],
        "pubs_per_AssistantProfessor": [1, 1], "pubs_per_Lecturer": [0, 1],
        "research_topics": 3,
    }, 1 << 10),
}


def tiny_cell(name: str) -> dict:
    """The cell ``name`` at a tiny size."""
    cell = copy.deepcopy(harness.load_cell(name))
    cfg = cell["config"]
    params, cfg["capacity"] = TINY[cfg["generator"]]
    cfg["params"] = {**cfg["params"], **params}
    return cell


def run(cell, seed=2**31 + 11, seconds=0.3, trace=False, engine_cls=None):
    import time

    return harness.run_cell(
        cell, seed, seconds, trace, t_start=time.perf_counter(),
        require_tpu=False, engine_cls=engine_cls,
    )
