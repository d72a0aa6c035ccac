#!/usr/bin/env python3
"""Smoke test of the main serving path on a TPU, at published widths.

    python chip_smoke.py              # one chip
    python chip_smoke.py --tp 4       # four chips: tp=4 against tp=1

One process serves stablelm-1.6b (24 layers, d_model 2048, 32 heads of 64,
d_ff 5632, vocab 100352; random weights from ``--seed``) through
``ServeEngine`` + ``ContinuousBatchingScheduler``: paged KV pool (page size
16), chunked prefill (chunk 128), prefix cache on, 8 slots, 8 requests of
128-512 prompt tokens (two share a 256-token prefix) and 32-64 new tokens.

It fails unless every request ends DONE with no rejection, quarantine,
failure or recovery, nothing compiles after warm-up, the second of the two
prefix-sharing requests is served its 256 shared tokens from the cache, the
compiled decode and prefill-chunk steps hold Pallas kernels
(``tpu_custom_call``), and the paged decode kernel agrees with its jnp
oracle on a pool and page table taken from the run.

``--tp 4`` runs only the tensor-parallel comparison: the same serving run
on a (1, 4) mesh (head-cut page pool, per-shard paged kernel) and on one
device, in this process, reporting greedy token identity and the largest
difference of next-token logits over a common prompt batch.

Each phase prints one JSON line.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or outside a checkout of the repository, it exits non-zero
before any phase runs.  The compile cache is ``JAX_COMPILATION_CACHE_DIR``
if set, else ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH = "stablelm-1.6b"
PAGE_SIZE, CHUNK, SLOTS, MAX_LEN = 16, 128, 8, 1024
SHARED_PREFIX = 256
# the paged kernel and its oracle read the same bf16 pages and accumulate
# in f32 (the oracle under "highest" matmul precision); they differ only in
# summation order and in the Mosaic vs XLA lowering of exp and the
# reductions, i.e. by f32 rounding (~1e-6 at these magnitudes).  A wrong
# page, a missed position or a bad mask moves outputs by O(0.1).
KERNEL_ATOL = 1e-4


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


class CompileLog:
    """Backend compiles (persistent-cache reads included) and their seconds,
    plus persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, key: str, secs: float, **_) -> None:
        if key == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def _event(self, key: str, **_) -> None:
        if key == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.count, self.seconds, self.cache_hits

    def since(self, mark):
        return {"compiles": self.count - mark[0],
                "compile_s": self.seconds - mark[1],
                "cache_hits": self.cache_hits - mark[2]}


def make_requests(vocab: int, seed: int):
    """8 requests, prompts of 128-512 tokens, 32-64 new tokens.  uid 0 and
    uid 1 share a 256-token prefix: uid 0 is admitted first and publishes
    its pages, so uid 1 must be served those 256 tokens from the cache."""
    import numpy as np

    from repro.serve.scheduler import Request
    rng = np.random.default_rng(seed)
    tok = lambda n: rng.integers(1, vocab, (n,)).astype(np.int32)
    prefix = tok(SHARED_PREFIX)
    prompts = [np.concatenate([prefix, tok(64)]),
               np.concatenate([prefix, tok(128)])]
    prompts += [tok(n) for n in (128, 200, 448, 512, 160, 300)]
    return [Request(uid=i, prompt=p, max_new=int(rng.integers(32, 65)))
            for i, p in enumerate(prompts)]


def build_engine(cfg, params, devices):
    from repro.launch.mesh import make_test_mesh
    from repro.serve.engine import ServeEngine
    mesh = make_test_mesh(devices, shape=(1, len(devices)))
    return ServeEngine(cfg, params, mesh=mesh, max_len=MAX_LEN,
                       page_size=PAGE_SIZE, prefix_cache="on")


def serve(eng, reqs, log: CompileLog, snapshot_uid=None):
    """Warm up, then serve ``reqs``; returns (run stats, warm-up compile
    record, steady-state compile record, snapshot).  The snapshot holds the
    page table, lengths and layer-0 K/V pools right after ``snapshot_uid``'s
    8th decode step, copied to the host.  Layer 0 is cut out on the device
    by a program compiled with the warm-up, so the run copies 1/24 of the
    pool and compiles nothing."""
    import dataclasses

    import jax
    import numpy as np

    from repro.serve.scheduler import ContinuousBatchingScheduler
    # one prefill job at a time: the prefix publisher's pages are in the
    # index before the sharer is admitted
    sched = ContinuousBatchingScheduler(eng, max_slots=SLOTS,
                                        prefill_chunk=CHUNK,
                                        max_prefill_jobs=1)
    m0 = log.mark()
    t0 = time.perf_counter()
    sched.warmup()
    layer0 = jax.jit(lambda leaf: leaf[0, 0])
    if snapshot_uid is not None:
        jax.block_until_ready(layer0(sched.cache["k"][0]))
    warm = {**log.since(m0), "warmup_s": time.perf_counter() - t0}
    snap, seen = {}, [0]

    def take_snapshot(_tok):
        seen[0] += 1
        if seen[0] == 8:
            pool = sched.cache
            snap["table"] = np.array(eng._pager.table())
            snap["len"] = np.asarray(pool["len"])
            snap["k"] = np.asarray(layer0(pool["k"][0]))
            snap["v"] = np.asarray(layer0(pool["v"][0]))

    if snapshot_uid is not None:
        reqs = [dataclasses.replace(r, stream=take_snapshot)
                if r.uid == snapshot_uid else r for r in reqs]
    m1 = log.mark()
    out = sched.run(reqs)
    steady = log.since(m1)
    return sched, out, warm, steady, snap


def check_run(out, steady, n_reqs):
    by_state = out["by_state"]
    check(by_state == {"DONE": n_reqs}, f"requests not all DONE: {by_state}")
    check(not out["rejected"], f"rejections: {out['rejected']}")
    for key in ("quarantines", "failed", "recoveries", "preemptions"):
        check(out[key] == 0, f"{key} = {out[key]} (must be 0)")
    check(steady["compiles"] == 0,
          f"{steady['compiles']} compiles after warm-up (must be 0)")


def init_params(cfg, seed: int):
    import jax

    from repro.models import api
    t0 = time.perf_counter()
    params = jax.jit(lambda k: api.init_params(cfg, k))(
        jax.random.PRNGKey(seed))           # lands on jax.devices()[0]
    params = jax.block_until_ready(params)
    n_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    return params, {"param_bytes": n_bytes,
                    "init_s": time.perf_counter() - t0}


def one_chip(cfg, seed: int, log: CompileLog) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import paged_attention, ref
    dev = jax.devices()[0]
    params, info = init_params(cfg, seed)
    emit("params", arch=cfg.name, layers=cfg.num_layers,
         d_model=cfg.d_model, heads=cfg.num_heads, head_dim=cfg.head_dim,
         d_ff=cfg.d_ff, vocab=cfg.vocab_size, dtype=cfg.dtype, **info)

    eng = build_engine(cfg, params, [dev])
    check(eng.cfg.kernels == "pallas",
          f"engine on {dev.platform} chose kernels={eng.cfg.kernels!r}")
    reqs = make_requests(cfg.vocab_size, seed)
    sched, out, warm, steady, snap = serve(eng, reqs, log, snapshot_uid=2)
    emit("warmup", **warm)
    check_run(out, steady, len(reqs))
    cached = {r.uid: r.cached_tokens for r in out["results"]}
    check(cached[1] == SHARED_PREFIX,
          f"prefix sharer was served {cached[1]} cached tokens, "
          f"want {SHARED_PREFIX}")
    stats = jax.devices()[0].memory_stats() or {}
    emit("serve", kernels=eng.cfg.kernels, requests=len(reqs),
         by_state=out["by_state"], rejected=len(out["rejected"]),
         quarantines=out["quarantines"], failed=out["failed"],
         recoveries=out["recoveries"], steps=out["steps"],
         decoded_tokens=out["decoded_tokens"],
         prefill_tokens=out["prefill_tokens"], cached_tokens=cached,
         steady_state_compiles=steady["compiles"], wall_s=out["wall_s"],
         tokens_per_s=out["tokens_per_s"],
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_limit=stats.get("bytes_limit"),
         cache=eng.cache_stats(sched.cache))

    # the compiled steady-state programs must hold the Pallas kernels
    n = SLOTS
    zeros = jnp.zeros((n,), jnp.int32)
    off = jnp.zeros((n,), bool)
    decode_hlo = eng._paged_step.lower(
        eng.params, sched.cache, eng._pager.table(), zeros, off,
        off).compile().as_text()
    chunk_hlo = eng._chunk_jit[CHUNK].lower(
        eng.params, eng.new_request_cache(), jnp.zeros((1, CHUNK), jnp.int32),
        jnp.int32(CHUNK)).compile().as_text()
    kernels = {"decode_step": decode_hlo.count("tpu_custom_call"),
               "prefill_chunk": chunk_hlo.count("tpu_custom_call")}
    emit("hlo", tpu_custom_call=kernels)
    check(all(kernels.values()), f"a step holds no Pallas kernel: {kernels}")

    # paged kernel vs its oracle on the run's own pool and page table
    check(bool(snap), "no snapshot was taken during the run")
    rng = np.random.default_rng(seed)
    B = snap["table"].shape[0]
    q = jnp.asarray(rng.standard_normal(
        (B, cfg.num_heads, 1, cfg.resolved_head_dim)), jnp.float32)
    # the snapshot is layer 0 of the stacked pool: a one-layer stack
    args = (q, jnp.asarray(snap["k"])[None], jnp.asarray(snap["v"])[None],
            jnp.asarray(snap["table"]), jnp.asarray(snap["len"]))
    got = paged_attention.paged_decode_attention(
        *args, layer=0, interpret=eng.cfg.kernels != "pallas")
    with jax.default_matmul_precision("highest"):
        want = ref.paged_decode_attention(*args, layer=0)
    diff = float(jnp.max(jnp.abs(got - want)))
    emit("kernel_vs_oracle", layer=0, slot_lens=snap["len"].tolist(),
         pool_dtype=str(snap["k"].dtype), max_abs_diff=diff, atol=KERNEL_ATOL,
         max_abs_out=float(jnp.max(jnp.abs(want))))
    check(diff <= KERNEL_ATOL, f"paged kernel vs oracle: {diff} > {KERNEL_ATOL}")


def four_chips(cfg, seed: int, tp: int, log: CompileLog) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import api
    devs = jax.devices()
    check(len(devs) >= tp, f"--tp {tp} needs {tp} devices, found {len(devs)}")
    params, info = init_params(cfg, seed)
    emit("params", arch=cfg.name, **info)
    reqs = make_requests(cfg.vocab_size, seed)
    batch = jnp.asarray(np.stack([r.prompt[:CHUNK] for r in reqs]))
    runs = {}
    for n in (1, tp):
        eng = build_engine(cfg, params, devs[:n])
        sched, out, warm, steady, _ = serve(eng, reqs, log)
        check_run(out, steady, len(reqs))
        fwd = jax.jit(lambda p, t: api.forward(p, t, eng.cfg)[0][:, -1])
        with jax.set_mesh(eng.mesh):
            logits = np.asarray(fwd(eng.params, batch))
        runs[n] = {"tokens": {r.uid: r.tokens.tolist()
                              for r in out["results"]},
                   "logits": logits}
        emit("serve", tp=n, kernels=eng.cfg.kernels,
             kv_shards=eng.cache_stats(sched.cache).get("kv_shards", 1),
             decoded_tokens=out["decoded_tokens"], wall_s=out["wall_s"],
             steady_state_compiles=steady["compiles"], **warm)
        # free this run's pool and param copy before the next engine
        del eng, sched, out
        gc.collect()
    a, b = runs[1], runs[tp]
    identical = a["tokens"] == b["tokens"]
    differ = [u for u in a["tokens"] if a["tokens"][u] != b["tokens"][u]]
    diff = float(np.max(np.abs(a["logits"] - b["logits"])))
    emit("tp_identity", tp=tp, token_identical=identical,
         differing_uids=differ, next_token_logits_max_abs_diff=diff,
         logits_batch=list(batch.shape))
    check(identical, f"tp={tp} tokens differ from tp=1 for uids {differ}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree of the comparison run "
                         "(default 1: the one-chip smoke test)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the prompts")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"{ROOT} is not a checkout of the repository (no src/repro)")
    sys.path.insert(0, str(ROOT / "src"))

    import dataclasses

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU found: jax.devices()[0].platform is "
             f"{devs[0].platform!r}")
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    emit("device", **device, compile_cache=enable_compile_cache())
    log = CompileLog()
    cfg = get_config(ARCH)
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    t0 = time.perf_counter()
    if args.tp == 1:
        one_chip(cfg, args.seed, log)
    else:
        four_chips(cfg, args.seed, args.tp, log)
    emit("total", seconds=time.perf_counter() - t0, **log.since((0, 0.0, 0)))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
