"""The run command refuses to run where it cannot measure.

Run by path: ``python -m pytest bench/tests``.
"""
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ["--workload", "det320-backlog", "--seed", str(2 ** 33 + 1),
        "--seconds", "1", "--trace", "0"]


def run_in(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    res = run_in(ROOT)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "no TPU" in res.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    """Without the program beside it, a run fails even past the look for
    a chip: it finds no system to build."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    assert run_in(tmp_path).returncode != 0
    code = ("import sys; sys.path.insert(0, '.'); from bench import run; "
            "from bench.core import spec; from bench.core.peaks import PEAKS; "
            "run.run_cell(spec.benchmark(), 'det320-backlog', 1, 1.0, False, "
            "require_tpu=False, peaks=PEAKS['TPU v5 lite'])")
    res = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "No module named 'repro'" in res.stderr
