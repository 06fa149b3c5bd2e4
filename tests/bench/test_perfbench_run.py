"""A whole run of the harness at a tiny size on the CPU (kernels in the
Pallas interpreter), driven from the test; and the command refusing to
report anything without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest
from perfbench_tiny import LIMITS, ROOT, make

from perfbench import control, harness


@pytest.fixture(autouse=True)
def no_cache(monkeypatch):
    # the persistent cache is the chip's; tests compile for the CPU
    monkeypatch.setattr(harness, "enable_cache", lambda jax: None)


def test_tiny_cell_runs_and_is_correct(tmp_path):
    bd, bench = make(tmp_path)
    res = harness.run("tiny.cell", 2 ** 31 + 12345, 0.5, False,
                      bench_dir=bd, benchmark=bench, require_tpu=False)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"ns_per_day", "setup_s"}
    assert res["metrics"]["ns_per_day"]["value"] > 0
    assert list(res)[-1] == "checks"
    checks = res["checks"]
    assert checks["compiles_in_window"]["value"] == 0
    assert checks["steps_missing"]["value"] == 0
    for k, limit in LIMITS.items():
        assert checks[k]["value"] <= limit


def test_traced_run_reports_counter_metrics(tmp_path):
    bd, bench = make(tmp_path)
    res = harness.run("tiny.cell", 5, 0.5, True, bench_dir=bd,
                      benchmark=bench, require_tpu=False)
    assert res["correct"]
    m = res["metrics"]
    # the CPU trace has no device plane: trace metrics are left out, the
    # decomposition's counters are there
    assert "device_idle_share" not in m
    assert "stage_ms.inference" not in m
    assert m["ghost_per_local"]["value"] > 0
    assert 0 < m["dd_row_fill"]["value"] <= 100
    assert res["device"]["busy_s"] == 0.0


def test_control_fails_the_limits_the_program_meets(tmp_path):
    bd, bench = make(tmp_path)
    r = control.readings("tiny.cell", [3], 0.3, bench_dir=bd,
                         benchmark=bench, require_tpu=False)
    low, high = r["program_max"], r["control_min"]
    for name, limit in LIMITS.items():
        assert low[name] <= limit < high[name]


def _run_cmd(cwd, env):
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "protein_hybrid.skin_reuse", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run_cmd(ROOT, env)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_command_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = _run_cmd(tmp_path, env)
    assert proc.returncode != 0 and '"correct"' not in proc.stdout
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
