"""The command refuses a host with no TPU, and a checkout without the
program, with a non-zero exit and no result on standard output."""
import os
import shutil
import subprocess
import sys

import benchtest
from jsdoop_bench import spec

ARGS = ["--workload", "paper-lstm.ws32", "--seed", str(2**31 + 5),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_host_without_tpu():
    r = _run(spec.ROOT)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "no TPU" in r.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(benchtest.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert r.stdout == ""
    assert "repro" in r.stderr
