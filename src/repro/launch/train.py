"""Training driver: end-to-end loop with checkpointing, restart, preemption
handling, and deterministic data.

At production scale this is launched once per host with the same arguments
(jax.distributed initializes from the TPU env); on CPU it runs reduced
configs for the e2e examples and integration tests:

  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
      --smoke --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.manager import CheckpointManager
from repro.configs import get_config
from repro.data.pipeline import DataConfig, DataLoader
from repro.distributed import runtime
from repro.launch.mesh import make_test_mesh
from repro.models import api
from repro.train import optimizer as opt_mod
from repro.train import step as step_mod


def build(cfg, optcfg, mesh, key):
    with runtime.use_mesh(mesh):
        params = api.init_params(cfg, key)
        opt_state = opt_mod.init_state(params, optcfg)
    step = step_mod.make_train_step(cfg, optcfg, mesh, params, opt_state)
    return params, opt_state, step


def main(argv=None) -> Dict[str, Any]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU scale)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    optcfg = opt_mod.AdamWConfig(lr=args.lr, warmup_steps=10,
                                 total_steps=args.steps)
    mesh = make_test_mesh()
    key = jax.random.PRNGKey(args.seed)
    params, opt_state, train_step = build(cfg, optcfg, mesh, key)

    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch, seed=args.seed,
                      frontend_tokens=cfg.frontend_tokens, d_model=cfg.d_model)
    loader = DataLoader(dcfg)

    mgr: Optional[CheckpointManager] = None
    start_step = 0
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep=2, async_save=True)
        if args.resume and mgr.latest_step() is not None:
            state_like = {"params": params, "opt": opt_state}
            restored, meta = mgr.restore(state_like)
            params, opt_state = restored["params"], restored["opt"]
            start_step = int(meta["step"]) + 1
            loader.load_state_dict({"step": start_step})
            print(f"resumed from step {meta['step']}")
        mgr.save_on_signal(lambda: (int(loader.step),
                                    {"params": params, "opt": opt_state}))

    losses = []
    step_times = []
    with runtime.use_mesh(mesh):
        for i in range(start_step, args.steps):
            batch_np = next(loader)
            batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            batch["mask"] = jnp.ones_like(batch["labels"], jnp.float32)
            t0 = time.time()
            params, opt_state, metrics = train_step(params, opt_state, batch)
            loss = float(metrics["loss"])
            step_times.append(time.time() - t0)  # straggler watch (see below)
            losses.append(loss)
            if i % args.log_every == 0 or i == args.steps - 1:
                # straggler mitigation signal: flag steps >2x the median
                med = float(np.median(step_times)) if step_times else 0.0
                slow = sum(1 for t in step_times if t > 2 * med)
                print(f"step {i:5d} loss {loss:.4f} lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"med_step {med*1e3:.0f}ms stragglers {slow}")
            if mgr and (i + 1) % args.ckpt_every == 0:
                mgr.save(i, {"params": params, "opt": opt_state},
                         metadata={"step": i, "loss": loss,
                                   "mesh": list(mesh.devices.shape)})
    if mgr:
        mgr.wait()
    result = {"first_loss": losses[0] if losses else None,
              "last_loss": losses[-1] if losses else None,
              "steps": len(losses)}
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
