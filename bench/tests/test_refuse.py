"""No chip, no result: the harness refuses rather than measure a CPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from harness import spec

ROOT = Path(__file__).resolve().parents[2]
CELL = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"]
ARGS = ["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"]


def bench(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    out = bench(ROOT)
    assert out.returncode == 2 and out.stdout == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = bench(tmp_path)
    assert out.returncode != 0 and out.stdout == ""


def test_unknown_device_kind_has_no_peaks():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v4")
