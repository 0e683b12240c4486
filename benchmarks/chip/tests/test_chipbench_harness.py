"""The command refuses what is not a TPU, and a checkout without the
program."""
import os
import shutil
import subprocess
import sys
import time

import pytest

from chipbench import harness, spec

RUN = spec.BENCH_DIR / "run.py"


def run_cmd(cwd, script):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, str(script), "--workload", "gesture.window_t256",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_harness_refuses_a_cpu_device():
    with pytest.raises(harness.NoChip):
        harness.run("gesture.window_t256", 1, 1.0, False,
                    t_process=time.perf_counter())


def test_command_exits_nonzero_without_a_result_on_cpu():
    out = run_cmd(spec.ROOT, RUN)
    assert out.returncode == 1, out.stderr[-2000:]
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_command_fails_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = run_cmd(tmp_path, tmp_path / "benchmarks" / "chip" / "run.py")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
