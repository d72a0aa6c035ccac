"""Decode-path benchmark: eager vs per-token jit vs fused generation.

Measures, per reduced config on CPU:
  * tokens/s of the three SplitBrainEngine decode paths
      eager — the per-layer Python reference loop (hundreds of op
              dispatches per token),
      jit   — one jitted scan-over-layers dispatch per token,
      fused — ONE dispatch for the whole generation (multi-token lax.scan),
  * XLA dispatches per token (eager: counted by patching the primitive
    dispatch entry point; jit/fused: structural — 1 per token / 1 per
    generation),
  * the per-token boundary bytes, asserted identical between the eager
    runtime meter and the jit trace-time replay (eq. 7-10 stay exact).

Plus the ServeEngine fused-prefill/fused-loop path vs its stepwise
reference on one production config.

Emits BENCH_decode.json so future PRs have a tokens/s trajectory:

  PYTHONPATH=src python benchmarks/decode_bench.py [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.serve.engine import ServeEngine
from repro.serve.splitbrain_engine import SplitBrainEngine, traffic_model_for


def _count_eager_dispatches(fn) -> Optional[int]:
    """Count un-jitted primitive executions during fn() by patching JAX's
    eager dispatch entry point.  Returns None if the internal API moved."""
    try:
        from jax._src import dispatch as _dsp
        orig = _dsp.apply_primitive
    except (ImportError, AttributeError):
        fn()
        return None
    count = 0

    def counting(*args, **kwargs):
        nonlocal count
        count += 1
        return orig(*args, **kwargs)

    _dsp.apply_primitive = counting
    try:
        fn()
    finally:
        _dsp.apply_primitive = orig
    return count


def _bench_splitbrain(arch: str, batch: int, max_new: int,
                      quantize: bool) -> List[Dict[str, Any]]:
    cfg = get_config(arch).reduced(vocab_size=128)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, cfg.vocab_size, (batch, 4)).astype(np.int32)
    max_len = prompts.shape[1] + max_new + 1
    rows = []

    # ---- eager reference: per-layer Python loop --------------------------
    eng_e = SplitBrainEngine(cfg, params, max_len=max_len, quantize=quantize,
                             jit=False)
    eng_e.generate(prompts, max_new=2)  # warm op caches
    disp = _count_eager_dispatches(
        lambda: eng_e.decode_token_eager(eng_e.init_cache(batch),
                                         jnp.zeros((batch,), jnp.int32)))
    eng_e.meter.reset()
    eng_e.decode_token_eager(eng_e.init_cache(batch),
                             jnp.zeros((batch,), jnp.int32))
    eager_traffic = eng_e.measured_bytes_per_token(batch)
    out_e = eng_e.generate(prompts, max_new=max_new)
    rows.append({"config": cfg.name, "engine": "splitbrain", "mode": "eager",
                 "batch": batch, "new_tokens": max_new,
                 "tokens_per_s": out_e["tokens_per_s"],
                 "dispatches_per_token": disp})

    # ---- per-token jit: one scan-over-layers dispatch per token ----------
    eng_j = SplitBrainEngine(cfg, params, max_len=max_len, quantize=quantize,
                             jit=True)
    tok = jnp.asarray(prompts[:, 0])
    _, _, _ = eng_j.decode_token(eng_j.init_cache(batch), tok)  # compile
    eng_j.meter.reset()
    eng_j.decode_token(eng_j.init_cache(batch), tok)
    jit_traffic = eng_j.measured_bytes_per_token(batch)
    # eq. 7-10 equality must survive the refactor, byte for byte
    assert jit_traffic["total"] == traffic_model_for(cfg).bytes_per_token()
    cache = eng_j.init_cache(batch)
    t0 = time.perf_counter()
    for _ in range(max_new):
        tok, _, cache = eng_j.decode_token(cache, tok)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    rows.append({"config": cfg.name, "engine": "splitbrain", "mode": "jit",
                 "batch": batch, "new_tokens": max_new,
                 "tokens_per_s": batch * max_new / dt,
                 "dispatches_per_token": 1})

    # ---- fused: ONE dispatch for the whole generation --------------------
    eng_j.generate(prompts, max_new=max_new)  # compile
    out_f = eng_j.generate(prompts, max_new=max_new)
    rows.append({"config": cfg.name, "engine": "splitbrain", "mode": "fused",
                 "batch": batch, "new_tokens": max_new,
                 "tokens_per_s": out_f["tokens_per_s"],
                 "dispatches_per_token": 1.0 / (prompts.shape[1] - 1 + max_new)})

    traffic_identical = eager_traffic == jit_traffic
    for r in rows:
        r["bytes_per_token"] = jit_traffic["total"]
        r["traffic_identical_eager_vs_jit"] = traffic_identical
    return rows


def _bench_serve(arch: str, batch: int, max_new: int) -> List[Dict[str, Any]]:
    cfg = get_config(arch).reduced()
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_len=4 + max_new + 1)
    prompts = np.random.default_rng(0).integers(
        1, cfg.vocab_size, (batch, 4)).astype(np.int32)
    rows = []
    for mode, fused in (("stepwise", False), ("fused", True)):
        eng.generate(prompts, max_new=max_new, fused=fused)  # compile
        out = eng.generate(prompts, max_new=max_new, fused=fused)
        rows.append({"config": cfg.name, "engine": "serve", "mode": mode,
                     "batch": batch, "new_tokens": max_new,
                     "tokens_per_s": out["tokens_per_s"],
                     "dispatches_per_token":
                         1 if not fused else 1.0 / max_new})
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="one config, few tokens (CI smoke)")
    ap.add_argument("--tokens", type=int, default=None,
                    help="generated tokens per measurement")
    ap.add_argument("--out", default="BENCH_decode.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    max_new = args.tokens or (8 if args.quick else 32)
    batch = 2
    sb_archs = ["tinyllama-1.1b"] if args.quick else \
        ["tinyllama-1.1b", "llama2-7b"]

    results: List[Dict[str, Any]] = []
    for arch in sb_archs:
        results += _bench_splitbrain(arch, batch, max_new, quantize=False)
    if not args.quick:
        results += _bench_serve("granite-8b", batch, max_new)

    summary: Dict[str, Any] = {}
    for arch in {r["config"] for r in results if r["engine"] == "splitbrain"}:
        by_mode = {r["mode"]: r for r in results if r["config"] == arch
                   and r["engine"] == "splitbrain"}
        summary[arch] = {
            "fused_vs_eager_speedup": round(
                by_mode["fused"]["tokens_per_s"]
                / by_mode["eager"]["tokens_per_s"], 2),
            "jit_vs_eager_speedup": round(
                by_mode["jit"]["tokens_per_s"]
                / by_mode["eager"]["tokens_per_s"], 2),
            "traffic_identical": by_mode["jit"]["traffic_identical_eager_vs_jit"],
        }

    report = {
        "schema": "decode_bench/v1",
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "quick": args.quick,
        "results": results,
        "summary": summary,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps(report["summary"], indent=2))
    print(f"wrote {args.out}")

    ok = all(s["fused_vs_eager_speedup"] >= 5.0 and s["traffic_identical"]
             for s in summary.values())
    if not ok:
        print("FAIL: fused decode < 5x eager or traffic mismatch",
              file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
