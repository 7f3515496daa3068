"""End-to-end integration: drivers, examples and a small-mesh dry-run.

The 512-device production dry-run runs out of process (XLA_FLAGS must be set
before jax init); here we exercise the identical lower+compile+analyze path
on a small faked mesh in a subprocess.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
ENV = {**os.environ, "PYTHONPATH": str(ROOT / "src")}


def _run(args, timeout=480, env=None):
    return subprocess.run([sys.executable] + args, cwd=ROOT, env=env or ENV,
                          capture_output=True, text=True, timeout=timeout)


def test_train_driver_paper_mode():
    r = _run(["-m", "repro.launch.train", "--paper", "--workers", "2",
              "--versions", "2"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done v2" in r.stdout


def test_train_driver_arch_mode():
    r = _run(["-m", "repro.launch.train", "--arch", "deepseek-moe-16b",
              "--steps", "3", "--batch", "4", "--seq", "32"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "loss" in r.stdout


def test_serve_driver():
    r = _run(["-m", "repro.launch.serve", "--arch", "whisper-base",
              "--requests", "2", "--batch", "2", "--prompt", "8",
              "--tokens", "4"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "tok/s" in r.stdout


def test_quickstart_example():
    r = _run(["examples/quickstart.py"], timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "BIT-IDENTICAL" in r.stdout


def test_classroom_example():
    r = _run(["examples/classroom_simulation.py"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "training completed despite churn" in r.stdout


def test_dryrun_small_mesh_subprocess(tmp_path):
    """The dry-run path on a faked 4x4 mesh: must lower, compile and emit
    roofline terms for a dense and an SSM arch."""
    out = tmp_path / "rec.jsonl"
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import json, jax
from repro.launch import dryrun as DR
from jax.sharding import AxisType
mesh = jax.make_mesh((4, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
for arch, shape in [("stablelm-1.6b", "train_4k"),
                    ("falcon-mamba-7b", "decode_32k")]:
    rec = DR.lower_one(arch, shape, mesh)
    with open({str(out)!r}, "a") as f:
        f.write(json.dumps(rec) + "\\n")
print("DRYRUN_OK")
"""
    r = _run(["-c", code], timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DRYRUN_OK" in r.stdout
    recs = [json.loads(l) for l in out.read_text().splitlines()]
    assert len(recs) == 2
    for rec in recs:
        assert rec["flops_per_device"] > 0
        assert rec["bottleneck"] in ("compute", "memory", "collective")
        assert rec["memory"]["temp_size_in_bytes"] >= 0
    train = recs[0]
    # useful fraction must be sane (remat <=1, >0.05)
    assert 0.05 < train["useful_fraction"] <= 1.2, train["useful_fraction"]
