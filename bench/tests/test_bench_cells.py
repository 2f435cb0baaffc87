"""Each cell rehearsed on the CPU at a tiny size through the harness's
own functions, and the checks the harness makes before it measures."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.tiny import CELLS, run, tiny_cell


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal(name):
    cell = tiny_cell(name)
    r = run(cell)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {m["name"] for m in cell["end_to_end"]} - {
        "device_peak_mib"  # the CPU backend reports no peak
    }
    assert list(r)[-1] == "checks"
    assert r["checks"] == {
        "missing_facts": {"value": 0, "limit": 0},
        "extra_facts": {"value": 0, "limit": 0},
    }


def test_traced_rehearsal_reports_counters():
    r = run(tiny_cell("lubm1.materialise"), trace=True)
    assert r["correct"]
    # the CPU trace has no TPU plane: device readers return nothing
    assert set(r["metrics"]) == {"rounds.materialise"}
    assert r["metrics"]["rounds.materialise"]["value"] == pytest.approx(21.0)
    assert r["device"]["window_s"] > 0 and r["device"]["busy_s"] is None


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_device_checks():
    peaks = harness.load_peaks()
    ok = harness.device_info([_Dev("tpu", "TPU v5 lite")], 1, peaks)
    assert ok == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    with pytest.raises(harness.BenchError, match="no TPU"):
        harness.device_info([_Dev("cpu", "cpu")], 1, peaks)
    with pytest.raises(harness.BenchError, match="not in bench/peaks.json"):
        harness.device_info([_Dev("tpu", "TPU v99")], 1, peaks)
    with pytest.raises(harness.BenchError, match="asks for 4 chips"):
        harness.device_info([_Dev("tpu", "TPU v5 lite")], 4, peaks)


def test_run_refuses_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"),
         "--workload", "lubm1.materialise", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU found" in p.stderr


def test_modules_touch_no_device_on_import():
    code = (
        "import sys; sys.path[:0] = [%r, %r]\n"
        "import bench.harness, bench.tracereduce, bench.reference, "
        "bench.loadgen, bench.roofline, bench.control\n"
        "import glob, os\n"
        "for f in glob.glob(os.path.join(%r, 'metrics', '*.py')):\n"
        "    bench.harness._load_module(f, 'm_' + os.path.basename(f)[:-3])\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
    ) % (harness.ROOT, os.path.join(harness.ROOT, "src"), harness.BENCH_DIR)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr
