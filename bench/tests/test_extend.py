"""A later change extends the benchmark by files and entries alone: a new
configuration, a traffic mix with bursty arrivals and a per-layer metric,
added to a copy of the benchmark, rehearse without any file that was there
being edited."""
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


def digest(root: Path):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_cell_by_new_files_only(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    (tmp_path / "src").symlink_to(ROOT / "src")
    before = digest(tmp_path / "bench")

    b = tmp_path / "bench"
    (b / "configs" / "tinyllama-1.1b.json").write_text(json.dumps(NEW_CONFIG))
    (b / "traffic" / "short.json").write_text(json.dumps(NEW_MIX))
    (b / "metrics" / "sched.decode_steps.short.py").write_text(NEW_METRIC)
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "tinyllama-1.1b", "source": NEW_CONFIG["source"],
                          "file": "bench/configs/tinyllama-1.1b.json", "reduced": [],
                          "why": "a further configuration"})
    bm["workloads"].append({"name": "tinyllama-1.1b.short", "config": "tinyllama-1.1b",
                            "traffic": "short", "chips": 1, "why": "a further cell"})
    bm["per_layer"].append({"name": "sched.decode_steps.short", "unit": "steps",
                            "better": "lower", "source": "program_counter",
                            "layer": "scheduler", "moves": "setup_s",
                            "workloads": ["tinyllama-1.1b.short"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tinyllama-1.1b.short",
         "--seed", "4", "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"], line
    assert line["metrics"]["sched.decode_steps.short"]["value"] > 0
    after = digest(tmp_path / "bench")
    assert {k: after[k] for k in before} == before
