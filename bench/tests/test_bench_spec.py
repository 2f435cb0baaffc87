"""``BENCHMARK.json`` against the benchmark's contract, every piece it
names found by name under ``bench/``, and the reference and the traffic
generator on their own."""

from __future__ import annotations

import os
import re

import numpy as np
import pytest

from bench import harness, loadgen, reference

SPEC = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and not re.search(r"[\n\t]", s)


def test_top_level_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    with open(os.path.join(harness.ROOT, "BENCHMARK.json"), "rb") as fh:
        assert len(fh.read()) <= 64 * 1024


@pytest.mark.parametrize("section", list(KEYS))
def test_entries(section):
    names = [e["name"] for e in SPEC[section]]
    assert len(names) == len(set(names))
    for e in SPEC[section]:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") else set()
        assert KEYS[section] <= set(e) <= KEYS[section] | extra, e
        assert NAME.fullmatch(e["name"])
        for key in ("why", "layer", "source"):
            if key in e:
                assert _text(e[key]), (e["name"], key)
        if "unit" in e:
            assert UNIT.fullmatch(e["unit"]) and e["better"] in ("lower", "higher")


def test_cells_configs_and_mixes_exist():
    configs = {c["name"]: c for c in SPEC["configs"]}
    used = set()
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4)
        used.add(w["config"])
        cfg = harness.load_json(os.path.join(harness.ROOT, configs[w["config"]]["file"]))
        assert cfg["reduced"] == configs[w["config"]]["reduced"]
        assert os.path.isfile(os.path.join(harness.BENCH_DIR, "kbgen", cfg["generator"] + ".py"))
        loadgen.check_mix(harness.load_json(
            os.path.join(harness.BENCH_DIR, "traffic", w["traffic"] + ".json")))
    assert used == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_metrics_cover_every_cell():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        base = m["name"].split(".", 1)[0]
        assert any(
            os.path.isfile(os.path.join(harness.BENCH_DIR, "metrics", s + ".py"))
            for s in (m["name"], base)
        ), m["name"]
        if m["unit"] == "%" and "roofline" in m["name"]:
            assert m["name"].split(".")[0].endswith("_roofline")
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"])
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2 and cell["per_layer"]
        for m in cell["per_layer"]:
            assert m["moves"] in names


def test_reference_agrees_with_a_plain_loop():
    rules = reference.parse_rules([
        "e(x, y) -> p(x, y)", "p(x, y), e(y, z) -> p(x, z)", "p(x, x) -> cyc(x)",
    ])
    rng = np.random.default_rng(3)
    edges = np.unique(rng.integers(0, 12, (30, 2)), axis=0)
    got, rounds = reference.materialise({"e": edges}, rules)
    closure = {tuple(r) for r in edges.tolist()}
    while True:
        more = {(a, d) for a, b in closure for c, d in closure if b == c} - closure
        if not more:
            break
        closure |= more
    assert {tuple(r) for r in got["p"].tolist()} == closure
    assert {r[0] for r in got["cyc"].tolist()} == {a for a, b in closure if a == b}
    short, _ = reference.materialise({"e": edges}, rules, max_rounds=rounds - 1)
    assert reference.compare(short, got)[0] > 0
    assert reference.compare(got, got) == (0, 0)


@pytest.mark.parametrize("config", sorted({c["file"] for c in SPEC["configs"]}))
def test_config_data_sizes_do_not_depend_on_the_seed(config):
    cfg = harness.load_json(os.path.join(harness.ROOT, config))
    one, two = (harness.generate(cfg, s) for s in (1, 2**33 + 5))
    assert {p: len(r) for p, r in one.items()} == {p: len(r) for p, r in two.items()}
    assert any((one[p] != two[p]).any() for p in one)  # the seed draws who is related
    ids = max(int(r.max()) for r in one.values())
    assert ids < cfg["params"]["max_constants"]


@pytest.mark.parametrize("program", sorted({
    harness.load_json(os.path.join(harness.ROOT, c["file"]))["program"]
    for c in SPEC["configs"]
}))
def test_program_is_inside_the_engine_fragment(program):
    from repro.core.datalog import parse_program
    from repro.core.distributed import DistributedEngine

    lines = harness.rules({"program": program})
    parsed = parse_program("\n".join(lines))
    assert len(DistributedEngine.supported_program(parsed)) == len(parsed)
    assert len(reference.parse_rules(lines)) == len(parsed)
