"""Serving driver: batched greedy decoding for any --arch.

  PYTHONPATH=src python -m repro.launch.serve --arch hymba-1.5b --smoke \
      --batch 4 --prompt-len 8 --max-new 16

  # continuous batching: N concurrent requests over a slot-based KV cache
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --smoke \
      --continuous --requests 8 --slots 4 --max-new 16

  # paged KV cache + chunked prefill: KV lives in a shared page pool,
  # prompts stream in fixed-width chunks between decode steps
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --smoke \
      --continuous --page-size 8 --prefill-chunk 8

  # shared-prefix KV reuse: prompts sharing page-aligned prefixes with
  # earlier requests skip re-prefilling them (ref-counted CoW pages)
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --smoke \
      --continuous --page-size 8 --prefill-chunk 8 --prefix-cache on

  # quantized KV pages: int8 (or fp8) codes + per-page scales, dequant
  # fused into the decode kernel's page fetch (~2x resident tokens/byte)
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --smoke \
      --continuous --page-size 8 --kv-dtype int8

  # online semantics: SLA classes, deadlines, SLA-aware preemption
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --smoke \
      --continuous --page-size 8 --priority 0,0,0,1 --deadline-s 5 \
      --preemption on

  # tensor-parallel serving over a (1, tp) device mesh (DESIGN.md §11);
  # on a CPU-only host, force visible devices first:
  XLA_FLAGS=--xla_force_host_platform_device_count=2 JAX_PLATFORMS=cpu \
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --smoke \
      --continuous --page-size 8 --tp 2

  # chaos: seeded device-fault injection against the recovery seam
  # (DESIGN.md §12) — NaN-corrupt half the decoding slots for two
  # iterations, then lose the device wholesale; the run must still finish
  # every request, and --recovery-log captures the quarantine/recover
  # event stream as JSON
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --smoke \
      --continuous --page-size 8 --prefill-chunk 8 --prefix-cache on \
      --chaos-seed 0 --recovery-log recovery_events.json \
      --chaos-plan "step_corrupt_at=4,step_corrupt_iters=2,device_loss_at=10"
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.models import api
from repro.serve import pages
from repro.serve.engine import ServeEngine
from repro.serve.faults import FaultInjector, FaultPlan
from repro.serve.scheduler import ContinuousBatchingScheduler, Request


def _parse_chaos_plan(spec: str, ap: argparse.ArgumentParser) -> FaultPlan:
    """``key=val,key=val`` over FaultPlan's fields, coerced per field type
    (tuple fields take ``+``-separated uids, e.g. ``step_corrupt_uids=1+3``).
    """
    fields = {f.name: f for f in dataclasses.fields(FaultPlan)}
    kwargs = {}
    for item in spec.split(","):
        item = item.strip()
        if not item:
            continue
        key, sep, val = item.partition("=")
        key, val = key.strip(), val.strip()
        if not sep or key not in fields:
            ap.error(f"--chaos-plan: unknown or malformed entry {item!r} "
                     f"(fields: {', '.join(sorted(fields))})")
        ftype = str(fields[key].type)
        try:
            if "Tuple" in ftype:
                kwargs[key] = tuple(int(v) for v in val.split("+") if v)
            elif ftype == "float":
                kwargs[key] = float(val)
            else:
                kwargs[key] = int(val)
        except ValueError:
            ap.error(f"--chaos-plan: bad value {val!r} for {key} ({ftype})")
    if not kwargs:
        ap.error("--chaos-plan named no fault points")
    return FaultPlan(**kwargs)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="serve --requests ragged prompts via the "
                         "slot-based continuous-batching scheduler")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=None,
                    help="enable the paged KV cache with this page size "
                         "(tokens per page; must divide max_len)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page-pool capacity (default: dense-equivalent)")
    ap.add_argument("--paged-attn", choices=("inplace", "gather"),
                    default="inplace",
                    help="paged decode discipline: 'inplace' computes "
                         "attention directly through the page table "
                         "(gather-free, no dense-view transient); 'gather' "
                         "keeps the dense-view fallback/oracle")
    ap.add_argument("--kv-dtype", choices=("bf16", "int8", "fp8"),
                    default="bf16",
                    help="page-pool storage format (DESIGN.md §13): int8/fp8 "
                         "pages quantize on write with per-page per-kv-head "
                         "scales and dequantize inside the decode kernel's "
                         "page fetch (~2x more resident tokens per pool "
                         "byte); requires --page-size")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill width (interleaves prompt chunks "
                         "with decode steps; must divide max_len)")
    ap.add_argument("--prefix-cache", choices=("on", "off"), default="off",
                    help="shared-prefix KV reuse: admission radix-matches "
                         "each prompt against previously served page-"
                         "aligned prefixes and maps the shared pages "
                         "(refcounted, copy-on-write) instead of "
                         "re-prefilling them; requires --page-size, "
                         "no-ops for families with recurrent/ring state")
    ap.add_argument("--priority", default=None,
                    help="comma-separated SLA classes cycled over the "
                         "request stream (higher wins admission and may "
                         "preempt lower), e.g. '0,0,0,1'")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline in seconds from serve-loop "
                         "start; a request not finished by then terminates "
                         "as TIMEOUT (slot and pages freed)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: serve over a "
                         "(data=1, model=tp) mesh — params column-cut, page "
                         "pool cut on KV heads, token-identical to --tp 1 "
                         "(needs >= tp visible devices; see module docstring "
                         "for forcing host devices)")
    ap.add_argument("--chaos-plan", default=None,
                    help="seeded fault injection: comma-separated "
                         "FaultPlan fields (repro/serve/faults.py), e.g. "
                         "'step_corrupt_at=4,step_corrupt_iters=2,"
                         "device_loss_at=10'; device faults exercise "
                         "quarantine + host-authoritative recovery")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="PRNG seed for --chaos-plan: same (plan, seed) -> "
                         "same fault sequence")
    ap.add_argument("--recovery-log", default=None,
                    help="write the scheduler's quarantine/recover event "
                         "stream to this path as JSON")
    ap.add_argument("--preemption", choices=("on", "off"), default="off",
                    help="SLA-aware preemption: when a higher-priority "
                         "request cannot be admitted, evict a lower-"
                         "priority victim (publishing its full pages to "
                         "the prefix cache first) and re-queue it with "
                         "bounded exponential backoff")
    args = ap.parse_args(argv)
    if args.num_pages is not None and args.page_size is None:
        ap.error("--num-pages requires --page-size (the paged KV cache)")
    if args.prefix_cache == "on" and args.page_size is None:
        ap.error("--prefix-cache on requires --page-size (the prefix index "
                 "shares pool pages)")
    if args.kv_dtype != "bf16" and args.page_size is None:
        ap.error("--kv-dtype int8/fp8 requires --page-size (quantization "
                 "scales live per pool page)")
    if not args.continuous and (args.page_size is not None
                                or args.num_pages is not None
                                or args.prefill_chunk is not None):
        ap.error("--page-size/--num-pages/--prefill-chunk only apply to "
                 "the --continuous serve loop")
    if not args.continuous and (args.priority is not None
                                or args.deadline_s is not None
                                or args.preemption == "on"):
        ap.error("--priority/--deadline-s/--preemption only apply to the "
                 "--continuous serve loop")
    if not args.continuous and (args.chaos_plan is not None
                                or args.recovery_log is not None):
        ap.error("--chaos-plan/--recovery-log only apply to the "
                 "--continuous serve loop")
    faults = None
    if args.chaos_plan is not None:
        faults = FaultInjector(_parse_chaos_plan(args.chaos_plan, ap),
                               seed=args.chaos_seed)
    priorities = [0]
    if args.priority is not None:
        try:
            priorities = [int(p) for p in args.priority.split(",") if p != ""]
        except ValueError:
            ap.error(f"--priority must be comma-separated integers, "
                     f"got {args.priority!r}")
        if not priorities:
            ap.error("--priority must name at least one SLA class")

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    params = api.init_params(cfg, jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)

    if args.tp < 1 or jax.device_count() < args.tp:
        ap.error(f"--tp {args.tp} needs >= {args.tp} devices, have "
                 f"{jax.device_count()} (see module docstring for "
                 f"forcing host devices)")
    # exactly tp devices: --tp 1 is one device however many the host has
    mesh = make_test_mesh(jax.devices()[:args.tp], shape=(1, args.tp))

    if args.continuous:
        # pages AND prefill chunks must both tile the cache
        max_len = pages.round_len(args.prompt_len + args.max_new + 1,
                                  args.page_size, args.prefill_chunk)
        eng = ServeEngine(cfg, params, mesh=mesh, max_len=max_len,
                          page_size=args.page_size, num_pages=args.num_pages,
                          paged_attn=args.paged_attn,
                          prefix_cache=args.prefix_cache,
                          kv_dtype=args.kv_dtype)
        lo = min(2, args.prompt_len)
        reqs = [Request(uid=i,
                        prompt=rng.integers(
                            1, cfg.vocab_size,
                            (int(rng.integers(lo, args.prompt_len + 1)),)
                        ).astype(np.int32),
                        max_new=args.max_new,
                        priority=priorities[i % len(priorities)],
                        deadline_s=args.deadline_s)
                for i in range(args.requests)]
        sched = ContinuousBatchingScheduler(
            eng, max_slots=args.slots, eos_id=args.eos_id,
            prefill_chunk=args.prefill_chunk,
            preemption=args.preemption == "on", faults=faults)
        out = sched.run(reqs)
        report = {
            "arch": cfg.name,
            "tp": args.tp,
            "requests": args.requests,
            "slots": args.slots,
            "steps": out["steps"],
            "decoded_tokens": out["decoded_tokens"],
            "tokens_per_s": round(out["tokens_per_s"], 2),
            "requests_per_s": round(out["requests_per_s"], 2),
            "gen_len": [r.gen_len for r in out["results"]],
            "cached_prompt_tokens": out["cached_prompt_tokens"],
            "rejected": [(r.uid, r.reason) for r in out["rejected"]],
            "by_state": out["by_state"],
            "preemptions": out["preemptions"],
        }
        if args.page_size:
            report["cache"] = eng.cache_stats(sched.cache)
        if faults is not None:
            fired: dict = {}
            for name, *_ in faults.events:
                fired[name] = fired.get(name, 0) + 1
            report["chaos"] = {
                "seed": args.chaos_seed,
                "fired": fired,
                "quarantines": out["quarantines"],
                "failed": out["failed"],
                "recoveries": out["recoveries"],
                "last_recovery_s": round(out["last_recovery_s"], 4),
            }
        if args.recovery_log is not None:
            Path(args.recovery_log).write_text(
                json.dumps(sched.recovery_log, indent=2) + "\n")
        print(json.dumps(report))
        return out

    prompts = rng.integers(1, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    frontend = (jnp.asarray(rng.standard_normal(
        (args.batch, cfg.frontend_tokens, cfg.d_model)), jnp.float32)
        if cfg.frontend_tokens else None)

    eng = ServeEngine(cfg, params, mesh=mesh,
                      max_len=args.prompt_len + args.max_new + 1)
    out = eng.generate(prompts, max_new=args.max_new, frontend=frontend,
                       eos_id=args.eos_id)
    print(json.dumps({
        "arch": cfg.name,
        "tp": args.tp,
        "batch": args.batch,
        "generated": out["tokens"][:2, :8].tolist(),
        "gen_len": out["gen_len"].tolist(),
        "tokens_per_s": round(out["tokens_per_s"], 2),
    }))
    return out


if __name__ == "__main__":
    main()
