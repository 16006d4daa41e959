"""The command refuses to run anywhere but on a TPU, and without the
program beside it: it exits non-zero and prints no result line."""
import os
import shutil
import subprocess
import sys

import pytest

from lsmbench import spec

ARGS = ["-m", "lsmbench", "--workload", "leveling.ycsb-c", "--seed",
        "4294967297", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_no_tpu_no_result():
    out = _run(spec.ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


@pytest.mark.parametrize("missing", ["src", "BENCHMARK.json"])
def test_incomplete_checkout_no_result(tmp_path, missing):
    shutil.copytree(spec.ROOT / "lsmbench", tmp_path / "lsmbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    if missing != "BENCHMARK.json":
        shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
