"""Every cell end to end on the CPU at a tiny preset (``cpu_cell.py``),
and the real command without a TPU. Control flow only: a number from
these runs is never a device metric."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, CHECKOUT)

from benchmark import harness, run      # noqa: E402

MANIFEST = harness.load_json(CHECKOUT, "BENCHMARK.json")
CELLS = [c["name"] for c in MANIFEST["workloads"]]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def run_cpu_cell(workload, trace, seconds=2, bench_dir=BENCH_DIR,
                 env=ENV):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "cpu_cell.py"),
         "--workload", workload, "--trace", str(trace),
         "--seconds", str(seconds), "--bench-dir", bench_dir],
        cwd=os.path.dirname(bench_dir), env=env, capture_output=True,
        text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_end_to_end(workload):
    result, out = run_cpu_cell(workload, trace=0)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True, out[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    cell = run.resolve(BENCH_DIR, workload)
    assert set(result["metrics"]) == {m["name"]
                                      for m in cell["end_to_end"]}
    for name, metric in result["metrics"].items():
        assert metric["value"] > 0, name
        assert metric["unit"]
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] >= cell["chips"]
    # the reference agreed to float32 rounding, far inside the chip's
    # bf16 tolerance: the equations are the program's
    diffs = [float(line.split("|diff| ")[1].split()[0])
             for line in out.splitlines() if line.startswith("check ")]
    assert diffs and max(diffs) < 1e-4, out[-3000:]


def test_traced_run_reports_span_metrics():
    """On the CPU there is no device plane, so the readers that need
    one return nothing and are left out; the span readers report."""
    result, _ = run_cpu_cell("lm110m_s512_train", trace=1, seconds=4)
    listed = {m["name"] for m in run.resolve(
        BENCH_DIR, "lm110m_s512_train")["per_layer"]}
    assert {"step_ms", "dispatch_gap_share"} <= set(result["metrics"])
    assert set(result["metrics"]) <= listed
    assert "busy_s" not in result["device"]


def test_without_a_tpu_the_command_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         "lm110m_s512_train", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=CHECKOUT, env=ENV, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 2
    assert "correct" not in proc.stdout
    assert "tpu" in proc.stderr.lower()


def test_outside_the_repository_the_command_fails(tmp_path):
    """A directory that holds only BENCHMARK.json and ``benchmark/``:
    non-zero exit and no result."""
    import shutil
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "lm110m_s512_train", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, env=ENV, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
