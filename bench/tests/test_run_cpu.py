"""The command refuses a machine without the chips its cell asks for, and
a checkout that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

from common import BENCH, ROOT

ARGS = ["--workload", "paper-mlp.online-b5", "--seed", "1", "--seconds",
        "1", "--trace", "0"]
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def test_cpu_is_refused_with_no_result():
    r = subprocess.run([sys.executable, os.path.join("bench", "run.py"),
                        *ARGS], cwd=ROOT, env=ENV, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs 1 TPU" in r.stderr


def test_a_checkout_of_only_the_benchmark_fails(tmp_path):
    """Past the look for a chip, a checkout without the program fails
    before it prints anything."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    code = ("import sys; sys.path.insert(0, 'bench'); sys.path.insert(0, "
            "'src'); import run; print(run.run_cell('paper-mlp.online-b5', "
            "1, 1.0, False, require_compiled=False))")
    r = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=ENV,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "No module named 'repro'" in r.stderr
