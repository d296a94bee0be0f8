"""``run.py`` needs a chip: with none it exits nonzero and prints no
result, and so it does in a checkout that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

from chipbench import ROOT

ARGS = ["--workload", "h2o-4b.chat-poisson", "--seed", "3", "--seconds",
        "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py"] + ARGS,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=240)


def test_exits_nonzero_without_a_chip():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no chip" in p.stderr


def test_exits_nonzero_with_the_benchmark_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
