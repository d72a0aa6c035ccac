"""A later change extends the benchmark by files and entries alone: a new
configuration, a traffic mix with bursty arrivals and a per-layer metric,
added to a copy of the benchmark, rehearse without any file that was there
being edited; so does a block with routed experts, with its own reference
and cost module."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

NEW_CONFIG = {
    "name": "tinyllama-1.1b", "source": "https://huggingface.co/TinyLlama/TinyLlama-1.1B-intermediate-step-1431k-3T",
    "family": "lm", "program_config": "tinyllama-1.1b",
    "num_hidden_layers": 22, "hidden_size": 2048, "intermediate_size": 5632,
    "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 64,
    "vocab_size": 32000, "rope_theta": 10000, "rms_norm_eps": 1e-05,
    "reduced": [], "weights": {"dtype": "bfloat16"},
    "memory": {"hbm_bytes": 16909336064, "reserve_bytes": 2500000000},
    "check": {"max_logit_gap": 1.0}, "rehearsal": {"max_logit_gap": 0.05}}

NEW_MIX = {
    "loop": "open", "rate_per_s": 3.0, "ramp_s": 1, "trace_s": 1,
    # on/off bursts at the same mean rate: a mix of its own, in data alone
    "arrivals": {"block": 8, "profile": [[0.5, 3.0], [1.0, 0.0]]},
    "prompt": {"median": 64, "sigma": 0.5, "min": 8, "max": 200},
    "output": {"median": 16, "sigma": 0.3, "min": 4, "max": 40},
    "sizes": {"seed": 7, "count": 256},
    "serving": {"slots": 4, "max_len": 256, "page_size": 16, "prefill_chunk": 64,
                "max_prefill_jobs": 2, "prefix_cache": "on"},
    "check": {"sample_tokens": 32, "sample_requests": 4},
    "rehearsal": {"serving": {}}}

NEW_METRIC = '''"""Decode steps in the window (scheduler)."""


def read(run):
    return run.stats["decode_steps"] or None
'''


MOE_CONFIG = {
    "name": "tiny-moe", "source": "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct",
    "family": "lm", "program_config": "phi3.5-moe-42b-a6.6b",
    "reference": "lm-moe", "costs": "lm-moe",
    # the experts are narrower than the dense FFN: the program's d_ff is theirs
    "program_keys": {"d_ff": "moe_intermediate_size"},
    # capacity_factor E / k: every expert may take every token, none is dropped
    "program": {"moe": {"num_experts": 8, "top_k": 2, "capacity_factor": 4.0}},
    "num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 64, "num_experts": 8, "num_experts_per_tok": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "rope_theta": 10000, "rms_norm_eps": 1e-06,
    "reduced": ["num_hidden_layers", "hidden_size", "intermediate_size",
                "moe_intermediate_size", "num_experts", "num_attention_heads",
                "num_key_value_heads", "vocab_size"],
    "weights": {"dtype": "bfloat16"},
    "memory": {"hbm_bytes": 16909336064, "reserve_bytes": 2500000000,
               "pool_copy_share": 0.0},
    "check": {"max_logit_gap": 1.0},
    "rehearsal": {"max_logit_gap": 0.05,
                  "arch": {"num_experts": 4, "moe_intermediate_size": 32},
                  "program": {"moe": {"num_experts": 4, "top_k": 2,
                                      "capacity_factor": 2.0}}}}


def digest(root: Path):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def copy_bench(tmp_path: Path) -> Path:
    """A checkout of the benchmark alone, with the program beside it."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    (tmp_path / "src").symlink_to(ROOT / "src")
    return tmp_path


def add_cell(root: Path, config: dict, traffic: str, mix: dict) -> str:
    """The configuration's and the mix's files and their entries; returns
    the new cell's name."""
    b = root / "bench"
    (b / "configs" / f"{config['name']}.json").write_text(json.dumps(config))
    (b / "traffic" / f"{traffic}.json").write_text(json.dumps(mix))
    bm = json.loads((root / "BENCHMARK.json").read_text())
    cell = f"{config['name']}.{traffic}"
    bm["configs"].append({"name": config["name"], "source": config["source"],
                          "file": f"bench/configs/{config['name']}.json",
                          "reduced": config["reduced"],
                          "why": "a further configuration"})
    bm["workloads"].append({"name": cell, "config": config["name"],
                            "traffic": traffic, "chips": 1,
                            "why": "a further cell"})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return cell


def rehearse(root: Path, cell: str, seed: int, *extra: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         str(seed), "--seconds", "2", "--rehearse", *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)


def result(out) -> dict:
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_new_cell_by_new_files_only(tmp_path):
    root = copy_bench(tmp_path)
    before = digest(root / "bench")

    cell = add_cell(root, NEW_CONFIG, "short", NEW_MIX)
    (root / "bench" / "metrics" / "sched.decode_steps.short.py").write_text(NEW_METRIC)
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["per_layer"].append({"name": "sched.decode_steps.short", "unit": "steps",
                            "better": "lower", "source": "program_counter",
                            "layer": "scheduler", "moves": "setup_s",
                            "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    line = result(rehearse(root, cell, 4, "--trace", "1"))
    assert line["correct"], line
    assert line["metrics"]["sched.decode_steps.short"]["value"] > 0
    after = digest(root / "bench")
    assert {k: after[k] for k in before} == before


def test_moe_config_by_new_files_only(tmp_path):
    """A block with routed experts enters by new files alone: a
    configuration whose ``program`` object states the experts, its own
    reference and cost module, and its entries.  The served path runs the
    program's MoE layer and agrees with the reference; the fp8 control, put
    in its place, does not."""
    root = copy_bench(tmp_path)
    before = digest(root / "bench")
    cell = add_cell(root, MOE_CONFIG, "short", NEW_MIX)
    data = Path(__file__).parent / "data"
    shutil.copy(data / "lm-moe.reference.py", root / "bench" / "configs" / "lm-moe.py")
    (root / "bench" / "costs").mkdir(exist_ok=True)
    shutil.copy(data / "lm-moe.costs.py", root / "bench" / "costs" / "lm-moe.py")

    limit = MOE_CONFIG["rehearsal"]["max_logit_gap"]
    line = result(rehearse(root, cell, 2 ** 31 + 6))
    assert line["correct"], line
    assert line["check"]["max_logit_gap"]["value"] <= limit

    line = result(rehearse(root, cell, 2 ** 31 + 6, "--control"))
    assert not line["correct"], line
    assert line["check"]["control_max_logit_gap"]["value"] > limit

    after = digest(root / "bench")
    assert {k: after[k] for k in before} == before
