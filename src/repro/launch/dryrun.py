import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware:
  * the sharding rules are coherent (pjit partitions every op),
  * the program fits (memory_analysis),
  * and it emits the roofline terms (cost_analysis + HLO collective parse).

Usage:
  python -m repro.launch.dryrun --arch gemma2-27b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod]    # every valid cell
"""
import argparse
import dataclasses
import gzip
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config, registry
from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed import runtime
from repro.launch import hlo_analysis as hlo
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.train import optimizer as opt_mod
from repro.train import step as step_mod

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")


def cell_skip_reason(cfg: ModelConfig, shape: ShapeConfig) -> Optional[str]:
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return ("full-attention arch: 500k decode needs sub-quadratic "
                "attention (DESIGN.md §7)")
    return None


OPT_NOTES = """--variant opt applies (per shape kind; EXPERIMENTS.md §Perf):
  decode : H2 shard_map LSE flash-decode over the seq-sharded cache +
           aligned cache writes (kills the cache all-gather/scatter);
           H3 paper-technique W4A8 device weights (s8-direct MXU dots).
  train/prefill : H1 chunked matmul-form WKV for rwkv (rwkv_chunk=64);
           H4 ZeRO-3 per-layer weight gather (MoE experts excluded) +
           residual-stream batch pinning; G1 grouped-einsum attention
           (in ref.mha_chunked, always on after the G1 commit — the
           original baselines are preserved in experiments/dryrun_baseline/).
"""


def apply_variant(cfg: ModelConfig, shape: ShapeConfig, variant: str) -> ModelConfig:
    if variant == "baseline":
        return cfg
    assert variant == "opt", variant
    if cfg.family == "rwkv":
        cfg = dataclasses.replace(cfg, rwkv_chunk=64)
    if cfg.family == "hymba":
        cfg = dataclasses.replace(cfg, ssm_scan="associative")
    if shape.kind in ("train", "prefill"):
        par = dataclasses.replace(cfg.parallel, gather_fsdp_weights=True)
        cfg = dataclasses.replace(cfg, parallel=par)
    if shape.kind == "decode":
        par = dataclasses.replace(cfg.parallel, decode_attn="shard_map")
        ita = dataclasses.replace(cfg.ita, quantize_weights=True)
        cfg = dataclasses.replace(cfg, parallel=par, ita=ita)
    return cfg


def adapt_parallel(cfg: ModelConfig, shape: ShapeConfig, mesh) -> ModelConfig:
    """Per-cell parallelism fixes: drop batch axes that don't divide."""
    par = cfg.parallel
    sizes = [mesh.shape[a] for a in par.batch_axes if a in mesh.axis_names]
    total = 1
    for s in sizes:
        total *= s
    if shape.global_batch % max(total, 1) != 0:
        # keep the largest prefix of batch axes that divides
        axes = []
        prod = 1
        for a in par.batch_axes:
            if a in mesh.axis_names and shape.global_batch % (prod * mesh.shape[a]) == 0:
                axes.append(a)
                prod *= mesh.shape[a]
        par = dataclasses.replace(par, batch_axes=tuple(axes))
    return dataclasses.replace(cfg, parallel=par)


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch  # decode: one token


def model_bytes(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Minimum-necessary global HBM traffic for one step (the memory-roofline
    floor).  bf16 weights/activations; fp32 optimizer state.

      train   : weights read fwd+bwd (2x2P) + grads written (2P) + AdamW
                moments read+written (2 x 8P fp32, or 2P int8-quantized)
      prefill : weights read once (2P) + KV cache written once
      decode  : active weights read once per step (2P_act; batch amortizes)
                + the whole KV cache / recurrent state read once
    """
    P_tot = cfg.param_count()
    P_act = cfg.active_param_count()
    B, T = shape.global_batch, shape.seq_len
    kv_bytes_full = 0.0
    n_groups = cfg.num_layers
    window = None
    if cfg.layer_pattern:
        windows = [s.window for s in cfg.layer_pattern]
        per_layer = []
        for i in range(cfg.num_layers):
            w = windows[i % len(windows)]
            s_len = min(T, w) if w else T
            per_layer.append(s_len)
        kv_bytes_full = sum(2 * s_len * cfg.kv_dim * 2 * B for s_len in per_layer)
    if cfg.family == "rwkv":
        hd = 64
        kv_bytes_full = cfg.num_layers * B * (cfg.d_model // hd) * hd * hd * 4
    if cfg.family == "hymba":
        ssm_state = (cfg.ssm.state_dim if cfg.ssm else 16)
        kv_bytes_full += cfg.num_layers * B * cfg.d_model * ssm_state * 4
    if shape.kind == "train":
        moments = 4.0 * P_tot if cfg.param_count() > 5e10 else 32.0 * P_tot
        return 6.0 * P_tot + moments  # 2P fwd + 2P bwd + 2P grads (+opt)
    if shape.kind == "prefill":
        return 2.0 * P_tot + kv_bytes_full
    # decode: every live weight streams once; whole cache read once
    return 2.0 * P_act + kv_bytes_full


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               variant: str = "baseline") -> Dict[str, Any]:
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    reason = cell_skip_reason(cfg, shape)
    meta: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "chips": chips, "variant": variant,
    }
    if reason:
        return dict(meta, status="skipped", reason=reason)

    cfg = apply_variant(cfg, shape, variant)
    cfg = adapt_parallel(cfg, shape, mesh)
    key = jax.random.PRNGKey(0)
    params_like = jax.eval_shape(lambda k: api.init_params(cfg, k), key)
    if cfg.ita.quantize_weights and shape.kind == "decode":
        # H3: the serving weights are the LAQ INT4 codes (the "synthesis"
        # output) — shapes only, no allocation
        params_like = jax.eval_shape(
            lambda p: api.quantize_model(p, cfg), params_like)
    B, T = shape.global_batch, shape.seq_len
    specs = api.input_specs(cfg, shape)

    t0 = time.time()
    with runtime.use_mesh(mesh):
        if shape.kind == "train":
            optcfg = opt_mod.AdamWConfig(
                quantize_moments=cfg.param_count() > 5e10)
            opt_like = jax.eval_shape(
                lambda p: opt_mod.init_state(p, optcfg), params_like)
            step = step_mod.make_train_step(cfg, optcfg, mesh, params_like,
                                            opt_like, donate=True)
            batch = {k: v for k, v in specs.items()}
            batch["mask"] = jax.ShapeDtypeStruct((B, T), jnp.float32)
            lowered = step.lower(params_like, opt_like, batch)
        elif shape.kind == "prefill":
            step = step_mod.make_prefill_step(cfg, mesh)(params_like)
            lowered = step.lower(params_like, specs)
        else:  # decode
            frontend = specs.get("frontend")
            cache_like = jax.eval_shape(
                lambda p, f: api.init_cache(cfg, B, T, frontend=f, params=p),
                params_like, frontend)
            step = step_mod.make_serve_step(cfg, mesh, params_like, cache_like,
                                            donate=True)
            lowered = step.lower(params_like, cache_like, specs["tokens"])
        t_lower = time.time() - t0

        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            mem[attr] = getattr(ma, attr, None)
    except Exception as e:  # CPU backend may not implement it
        mem["error"] = str(e)

    hlo_text = compiled.as_text()
    if os.environ.get("REPRO_DRYRUN_SAVE_HLO"):
        with open(os.environ["REPRO_DRYRUN_SAVE_HLO"], "w") as f:
            f.write(hlo_text)
    hlo_dir = os.environ.get("REPRO_HLO_DIR")
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        suffix = "" if variant == "baseline" else f"__{variant}"
        tag = (f"{arch}__{shape_name}__"
               f"{'pod2' if multi_pod else 'pod1'}{suffix}.hlo.gz")
        with gzip.open(os.path.join(hlo_dir, tag), "wt") as f:
            f.write(hlo_text)
    totals = hlo.analyze(hlo_text)
    roof = hlo.Roofline(
        hlo_flops=totals.flops_per_chip * chips,
        hlo_bytes=totals.mem_bytes_per_chip * chips,
        coll_bytes_per_chip=totals.coll_bytes_per_chip,
        chips=chips,
        model_flops=model_flops(cfg, shape),
        model_bytes=model_bytes(cfg, shape),
    )
    return dict(
        meta, status="ok",
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory=mem,
        collectives={"by_kind": totals.coll_by_kind,
                     "op_counts_weighted": totals.coll_counts,
                     "total_per_chip": totals.coll_bytes_per_chip},
        mem_by_kind_per_chip=totals.mem_by_kind,
        cost_analysis_raw={"flops": cost.get("flops"),
                           "bytes accessed": cost.get("bytes accessed")},
        roofline=roof.as_dict(),
        hlo_size=len(hlo_text),
    )


def run_cells(cells, multi_pod: bool, out_dir: str,
              variant: str = "baseline") -> int:
    os.makedirs(out_dir, exist_ok=True)
    failures = 0
    for arch, shape_name in cells:
        tag = f"{arch}__{shape_name}__{'pod2' if multi_pod else 'pod1'}"
        if variant != "baseline":
            tag += f"__{variant}"
        path = os.path.join(out_dir, tag + ".json")
        try:
            res = lower_cell(arch, shape_name, multi_pod, variant)
        except Exception:
            res = {"arch": arch, "shape": shape_name, "status": "error",
                   "traceback": traceback.format_exc()}
            failures += 1
        with open(path, "w") as f:
            json.dump(res, f, indent=2, default=str)
        status = res["status"]
        extra = ""
        if status == "ok":
            r = res["roofline"]
            extra = (f" bottleneck={r['bottleneck']}"
                     f" frac={r['roofline_frac']:.3f}"
                     f" compile={res['compile_s']}s")
        elif status == "error":
            extra = " " + res["traceback"].strip().splitlines()[-1]
        print(f"[{status:7s}] {tag}{extra}", flush=True)
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(RESULTS_DIR))
    ap.add_argument("--variant", default="baseline",
                    choices=("baseline", "opt"), help=OPT_NOTES)
    args = ap.parse_args()

    if args.all:
        cells = [(a, s) for a in registry.ASSIGNED for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    failures = run_cells(cells, args.multi_pod, args.out, args.variant)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
