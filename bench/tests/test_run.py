"""The command refuses to measure without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ARGS = ["--workload", "lstm_wt2_fsd8.chat", "--seed", "3000000007", "--seconds", "1",
        "--trace", "0"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_no_tpu_exits_nonzero_without_a_result():
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"), *ARGS],
                       capture_output=True, text=True, env=_env(), cwd=ROOT, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_bare_checkout_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys; sys.path.insert(0, '.'); import bench.run as R; "
            f"R.main({ARGS!r}, {{'allow_cpu': True}})")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env=_env(), cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
