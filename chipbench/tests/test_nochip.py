"""Without a TPU the benchmark prints no result and exits non-zero, also
from a directory that holds only BENCHMARK.json and chipbench/."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "minicpm3-4b.train.s2048", "--seed", "2147483999",
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.parametrize("bare", [False, True])
def test_refuses_without_a_tpu(tmp_path, bare):
    cwd = ROOT
    if bare:
        shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
        shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        cwd = tmp_path
    p = _run(cwd)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    if not bare:
        assert "no TPU" in p.stderr
