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


def _with(**keys):
    from test_extend import NEW_CONFIG
    return {**NEW_CONFIG, "name": "refused", **keys}


@pytest.mark.parametrize("config,reason", [
    (_with(program_config="phi3.5-moe-42b-a6.6b"),
     "sets moe = MoEConfig(num_experts=16, top_k=2, capacity_factor=1.25), "
     "which the file's program object does not state"),
    (_with(program={"experts": 4}), "program field 'experts' is not a ModelConfig field"),
    (_with(program={"moe": {"num_experts": 4, "top_k": 2, "capacity": 2.0}}),
     "program.moe: MoEConfig has no field ['capacity']"),
    (_with(program={"d_model": 64}), "program field 'd_model' is set by the published keys"),
    (_with(program={"layer_pattern": [{"window": "wide"}]}),
     "program.layer_pattern[0].window: wants int, got 'wide'"),
    (_with(program_keys={"d_ff": "moe_intermediate_size"}),
     "no published key ['moe_intermediate_size']"),
    (_with(reference="nowhere"), "no plain reference"),
    (_with(costs="nowhere"), "no cost module"),
], ids=["registry-mechanism", "unknown-field", "unknown-nested-field",
        "published-field", "wrong-type", "program-keys", "reference", "costs"])
def test_a_file_the_harness_cannot_follow_is_refused(tmp_path, config, reason):
    """Exit 2, nothing on stdout, and the cause named: before any weight is
    drawn, on the CPU as on the chip."""
    from test_extend import NEW_MIX, add_cell, copy_bench, rehearse
    root = copy_bench(tmp_path)
    cell = add_cell(root, config, "short", NEW_MIX)
    out = rehearse(root, cell, 3)
    assert out.returncode == 2 and out.stdout == "", out.stderr[-3000:]
    assert reason in out.stderr
