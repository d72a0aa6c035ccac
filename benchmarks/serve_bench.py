"""Continuous-batching serve benchmark: paged (in-place vs gather) vs dense
slot cache, vs the sequential-fused baseline.

Replays the same Poisson-arrival request trace (ragged prompt lengths AND
ragged ``max_new``) through four serving disciplines:

  sequential   — the PR-1 baseline: requests served one at a time, each as
                 a fused prefill + one-dispatch decode loop (fast per
                 request, but concurrent arrivals queue behind the running
                 one),
  continuous   — serve/scheduler.py over the DENSE slot cache: every slot
                 pins max_len positions whether the request uses them or
                 not,
  paged_gather — the scheduler over the paged slot cache with the PR-3
                 reference decode discipline: gather the dense view
                 through the page table, run the family decode step,
                 scatter one token back — an O(max_slots x max_len)
                 dense-view TRANSIENT per step,
  paged        — the same pool with the gather-free in-place discipline
                 (DESIGN.md §6): attention walks pool[table] page-block-
                 wise, ZERO transient bytes, O(live tokens) KV reads.

A fifth, separately-traced discipline exercises shared-prefix KV reuse
(DESIGN.md §7): a shared-system-prompt workload (>= 50% prompt overlap)
replayed through the paged scheduler with ``prefix_cache`` off vs on.
Gates: per-request token identity, prefill tokens/s uplift >= 1.3x (the
cache maps the shared pages and computes only the unmatched tails),
reduced KV pages stored (cumulative pool draws — the shared prefix is
stored once, not per request; the instantaneous peak is reported but not
gated because the cache also unthrottles admission and so legitimately
raises concurrency), zero steady-state recompiles, and eq. 7-10 traffic
exactness under the cached-token accounting.

A sixth discipline measures the ONLINE serving semantics (DESIGN.md §8)
under overload: a priority-split Poisson trace (~25% high-priority) is
served unloaded (arrivals well under the measured service rate), then at
2x overload with SLA-aware preemption on, and per-priority TTFT
percentiles are compared.  Gates: high-priority p95 TTFT under 2x overload
stays within 1.5x of its unloaded value (preemption evicts low-priority
victims, publishing their full pages first so resume is near-free); a
cancelled mid-decode request returns its pages within ONE scheduler
iteration (asserted with a live probe); zero steady-state recompiles and
meter-exact traffic with preemption ON (every token that crossed — prefill,
decode, re-prefill after eviction — at exactly eq. 7-10 bytes).

A seventh discipline benches tensor-parallel serving (DESIGN.md §11): the
same persistent masked decode step over a forced-host-device ``(1, tp)``
mesh, in fresh subprocesses (the device count is a process-level XLA
flag).  Gates: tp=2 greedy tokens IDENTICAL to tp=1, byte-exact traffic on
both (the per-shard entries sum to the single-device analytical model),
zero steady-state recompiles, the pool actually cut on KV heads
(kv_shards == tp), and — on hosts with >= 2 cores — decode tokens/s at
tp=2 >= the gate x tp=1 (a 1-core host can't parallelize anything, so
only the structural gates apply there).

An eighth discipline gates crash tolerance (DESIGN.md §12): the same
shared-prefix trace is replayed through the paged + prefix scheduler with
a seeded fault plan combining per-slot NaN logit corruption, a raised
decode step and wholesale device loss.  Gates: every request still
reaches DONE token-identical to the uninterrupted run (the device is
stateless — recovery replays from the host-authoritative copy), each
fault class actually fired, the page pool returns to baseline, recovery
completes under a wall-clock bound, and a SECOND identical chaos cycle
compiles nothing (device loss kills buffers, not compiled programs).

A ninth discipline gates the quantized KV page pool (DESIGN.md §13): the
same ragged trace is served from a bf16 pool vs an int8 pool of identical
page geometry (1-byte codes + per-page, per-kv-head f32 scales beside the
page table; quantize-on-write, dequant fused into the decode kernel's page
fetch).  Gates: >= 1.8x resident tokens at fixed pool bytes (the per-token
STORAGE figure from cache_stats, timing-free), bounded greedy-token
divergence vs the bf16 run (quantization legitimately flips near-tie
argmaxes; it must stay a small fraction), eq. 7-10 traffic byte-IDENTICAL
to the bf16 run (quantization changes host-local storage, never boundary
bytes), the host KV-read channel shrunk by >= 1.5x, and zero steady-state
recompiles.

The discipline list itself is pinned to the serve-discipline registry
(repro/serve/disciplines.py): a report that misses a registered
discipline FAILS, so the bench, the README table, and benchmarks/tables.py
cannot silently drift apart.

Measures tokens/s, requests/s (wall AND busy — arrival sleeps are reported
separately so idle-heavy traces can't inflate apparent efficiency), mean
per-request latency, the paged-memory claim (peak resident KV bytes of the
PERSISTENT cache state vs the dense slot cache, gated >= 2x on the ragged
workload), and the per-step copy the in-place kernel eliminates:
``gather_transient_bytes_per_step`` (gated == 0 for paged in-place) plus
the metered host KV-read bytes per discipline (live pages only on the
in-place path).  Also asserts the structural invariants:

  * zero recompiles after warmup for ALL slot-cache disciplines — counted
    with the XLA backend-compile listener (serve/slots.py::CompileCounter),
  * interface-traffic exactness — measured meter bytes over each continuous
    run == (sum over requests of T0-1+gen) * the analytical eq. 7-10
    bytes/token, for the dense AND both paged disciplines,
  * paged in-place throughput >= paged gather (the copy was pure waste),
    and paged within 10% of the dense scheduler.

Emits BENCH_serve.json so future PRs have a throughput trajectory:

  PYTHONPATH=src python benchmarks/serve_bench.py [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.serve import pages
from repro.serve import slots
from repro.serve.disciplines import NAMES as DISCIPLINE_NAMES
from repro.serve.engine import ServeEngine
from repro.serve.faults import FaultInjector, FaultPlan
from repro.serve.scheduler import ContinuousBatchingScheduler, Request
from repro.serve.splitbrain_engine import traffic_model_for


def _workload(cfg, n_requests: int, max_new: int, mean_gap_s: float,
              seed: int = 0) -> List[Request]:
    """Poisson arrivals; prompt lengths uniform in [2, 16] and max_new
    uniform in [min(4, max_new), max_new] — the raggedness the paged pool
    exploits."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(mean_gap_s, n_requests)
    arrivals = np.cumsum(gaps) - gaps[0]
    lo = min(4, max_new)
    return [
        Request(uid=i,
                prompt=rng.integers(1, cfg.vocab_size,
                                    (int(rng.integers(2, 17)),)
                                    ).astype(np.int32),
                max_new=int(rng.integers(lo, max_new + 1)),
                arrival_s=float(arrivals[i]))
        for i in range(n_requests)
    ]


def _run_sequential(eng: ServeEngine, reqs: List[Request]) -> Dict[str, Any]:
    """One at a time, in arrival order, each request fully fused."""
    t_start = time.perf_counter()
    latency, decoded = [], 0
    for r in sorted(reqs, key=lambda r: (r.arrival_s, r.uid)):
        now = time.perf_counter() - t_start
        if now < r.arrival_s:
            time.sleep(r.arrival_s - now)
            now = r.arrival_s
        out = eng.generate(r.prompt[None, :], max_new=r.max_new)
        decoded += int(out["gen_len"].sum())
        latency.append(time.perf_counter() - t_start - r.arrival_s)
    wall = time.perf_counter() - t_start
    return {"wall_s": wall, "decoded_tokens": decoded,
            "tokens_per_s": decoded / wall,
            "requests_per_s": len(reqs) / wall,
            "mean_latency_s": float(np.mean(latency))}


def _pctiles(xs: List[float]) -> Dict[str, float]:
    """p50/p95 summary of a per-request latency series (serve_bench/v4)."""
    if not xs:
        return {"p50": 0.0, "p95": 0.0}
    return {"p50": float(np.percentile(xs, 50)),
            "p95": float(np.percentile(xs, 95))}


def _run_continuous(eng: ServeEngine, reqs: List[Request], max_slots: int,
                    prefill_chunk: Optional[int] = None) -> Dict[str, Any]:
    sched = ContinuousBatchingScheduler(eng, max_slots=max_slots,
                                        prefill_chunk=prefill_chunk)
    # the host meter carries heterogeneous channels (prefix savings, CoW
    # copies): count ONLY the decode KV-read channel, or a prefix run
    # would book its SAVED prefill bytes as extra reads
    kv0 = eng.meter.host_channel_bytes("kv_cache_read")
    out = sched.run(list(reqs), realtime=True)
    assert not out["rejected"], out["rejected"]
    lat = [res.finished_s - req.arrival_s
           for res, req in zip(out["results"],
                               sorted(reqs, key=lambda r: r.uid))]
    return {"wall_s": out["wall_s"],
            "busy_s": out["busy_s"],
            "decoded_tokens": out["decoded_tokens"],
            "prefill_tokens": out["prefill_tokens"],
            "cached_prompt_tokens": out["cached_prompt_tokens"],
            "tokens_per_s": out["tokens_per_s"],
            "tokens_per_s_busy": out["tokens_per_s_busy"],
            "requests_per_s": out["requests_per_s"],
            "requests_per_s_busy": out["requests_per_s_busy"],
            "mean_latency_s": float(np.mean(lat)),
            "latency_s": _pctiles(lat),
            "ttft_s": _pctiles([r.ttft_s for r in out["results"]]),
            "queue_wait_s": _pctiles([r.queue_wait_s
                                      for r in out["results"]]),
            "steps": out["steps"],
            # the per-step dense-view copy the in-place kernel eliminates,
            # and the discipline's modeled host KV reads over the run
            # (replayed accounting — kv_read_bytes_step — not a hw counter)
            "gather_transient_bytes_per_step":
                eng.gather_transient_bytes_per_step(),
            "kv_read_bytes":
                eng.meter.host_channel_bytes("kv_cache_read") - kv0,
            "cache": eng.cache_stats(sched.cache),
            "results": out["results"]}


def _check_traffic(eng: ServeEngine, reqs: List[Request], cfg,
                   cached_tokens: int = 0) -> Dict[str, Any]:
    """eq. 7-10 exactness: measured boundary bytes == analytical bytes per
    ACTIVE token.  Prefix-cached prompt tokens never cross the boundary
    (their K/V is shared, not recomputed), so they subtract from the
    analytical count — the same rule the scheduler's meter replay uses."""
    n_tok = sum(len(r.prompt) - 1 + r.max_new for r in reqs) - cached_tokens
    analytic = n_tok * traffic_model_for(cfg).bytes_per_token()
    measured = eng.measured_bytes()["total"]
    return {"measured": measured, "analytical": analytic,
            "cached_tokens": cached_tokens,
            "exact": measured == analytic}


def bench_arch(arch: str, n_requests: int, max_new: int, max_slots: int,
               mean_gap_s: float, overrides: Dict[str, Any],
               page_size: int = 8, prefill_chunk: int = 8,
               repeats: int = 1) -> Dict[str, Any]:
    cfg = get_config(arch).reduced(**overrides)
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    # room for the longest request, rounded so pages AND prefill chunks
    # both tile the cache exactly
    max_len = pages.round_len(16 - 1 + max_new, page_size, prefill_chunk)
    slot_pages = max_len // page_size
    # pool sized at HALF the dense token capacity (raggedness means most
    # slots never approach max_len), floored so one worst-case request
    # always fits even with --slots 1
    num_pages = max(max_slots * slot_pages // 2, slot_pages) + 1
    dense = ServeEngine(cfg, params, max_len=max_len)
    gather = ServeEngine(cfg, params, max_len=max_len, page_size=page_size,
                         num_pages=num_pages, paged_attn="gather")
    paged = ServeEngine(cfg, params, max_len=max_len, page_size=page_size,
                        num_pages=num_pages)          # in-place (default)
    reqs = _workload(cfg, n_requests, max_new, mean_gap_s)

    # a family with no sequence-scaling leaves (rwkv) demotes BOTH paged
    # engines to the identical dense fallback: measuring "gather" there
    # would just re-time the same discipline and publish a noise ratio
    will_page = paged.will_page()

    # warm every bucket all disciplines touch (compiles excluded from timing)
    warm = [dataclasses.replace(r, uid=-1 - i, arrival_s=0.0)
            for i, r in enumerate(reqs)]
    _run_sequential(dense, warm)
    _run_continuous(dense, warm, max_slots)
    if will_page:
        _run_continuous(gather, warm, max_slots, prefill_chunk)
    _run_continuous(paged, warm, max_slots, prefill_chunk)

    # each discipline is measured ``repeats`` times and the best steady-state
    # run is reported (sub-second walls make single runs noisy on a shared
    # machine); the structural invariants — zero recompiles, byte-exact
    # traffic — must hold on EVERY repeat, not just the best one.
    counter = slots.CompileCounter.instance()
    seq = max((_run_sequential(dense, reqs) for _ in range(repeats)),
              key=lambda r: r["requests_per_s"])

    def measure(eng, chunk):
        best, recompiles, traffic = None, 0, None
        for _ in range(repeats):
            c0 = counter.count
            eng.meter.reset()
            r = _run_continuous(eng, reqs, max_slots, chunk)
            r.pop("results")
            recompiles += counter.count - c0
            traffic = _check_traffic(eng, reqs, cfg)
            assert traffic["exact"], traffic
            if best is None or r["requests_per_s"] > best["requests_per_s"]:
                best = r
        return best, recompiles, traffic

    cont, dense_recompiles, dense_traffic = measure(dense, None)
    if will_page:
        gat, gather_recompiles, gather_traffic = measure(gather,
                                                         prefill_chunk)
    else:
        gat, gather_recompiles, gather_traffic = None, 0, {"exact": True}
    pag, paged_recompiles, paged_traffic = measure(paged, prefill_chunk)

    # structural gates on the eliminated copy (checked via the FAIL/exit-1
    # path in main(), not asserts, so a regression still writes the
    # artifact): the in-place discipline must have NO dense-view transient
    # and must read fewer host KV bytes than gather on a ragged workload
    transient_ok = (pag["gather_transient_bytes_per_step"] == 0
                    and (not will_page
                         or gat["gather_transient_bytes_per_step"] > 0))
    reads_ok = (not will_page
                or pag["kv_read_bytes"] < gat["kv_read_bytes"])

    dense_bytes = cont["cache"]["cache_bytes"]
    paged_peak = pag["cache"]["peak_kv_bytes_in_use"]
    return {
        "config": cfg.name,
        "n_requests": n_requests,
        "max_new": max_new,
        "max_slots": max_slots,
        "max_len": max_len,
        "mean_gap_s": mean_gap_s,
        "page_size": page_size,
        "num_pages": num_pages,
        "prefill_chunk": prefill_chunk,
        "sequential": seq,
        "continuous": cont,
        "paged_gather": gat,
        "paged": pag,
        "requests_per_s_speedup": cont["requests_per_s"] / seq["requests_per_s"],
        "tokens_per_s_speedup": cont["tokens_per_s"] / seq["tokens_per_s"],
        "paged_vs_dense_requests_per_s":
            pag["requests_per_s"] / cont["requests_per_s"],
        "paged_inplace_vs_gather_tokens_per_s":
            (pag["tokens_per_s_busy"] / gat["tokens_per_s_busy"]
             if will_page else None),
        "paged_transient_eliminated": transient_ok,
        "paged_inplace_reads_less": reads_ok,
        "dense_cache_bytes": dense_bytes,
        "paged_pool_bytes": pag["cache"]["cache_bytes"],
        "paged_peak_bytes_in_use": paged_peak,
        "paged_memory_saving": dense_bytes / paged_peak,
        "steady_state_recompiles": dense_recompiles,
        "paged_steady_state_recompiles": paged_recompiles,
        "gather_steady_state_recompiles": gather_recompiles,
        "compile_counter_available": counter.available,
        "traffic_dense": dense_traffic,
        "traffic_paged": paged_traffic,
        "traffic_exact": (dense_traffic["exact"] and paged_traffic["exact"]
                          and gather_traffic["exact"]),
        "jit_caches": {"dense": dense.jit_cache_sizes(),
                       "paged": paged.jit_cache_sizes()},
    }


def _prefix_workload(cfg, n_requests: int, max_new: int, mean_gap_s: float,
                     prefix_len: int, tail_max: int,
                     seed: int = 0) -> List[Request]:
    """Shared-system-prompt traffic: every request opens with the SAME
    ``prefix_len``-token prompt and diverges into a short unique tail —
    the workload shape that dominates production serving (system prompts,
    few-shot templates) and that the prefix cache exists for.  With
    ``tail_max <= prefix_len`` the pairwise prompt overlap is >= 50%."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, cfg.vocab_size, (prefix_len,)).astype(np.int32)
    gaps = rng.exponential(mean_gap_s, n_requests)
    arrivals = np.cumsum(gaps) - gaps[0]
    return [
        Request(uid=i,
                prompt=np.concatenate(
                    [shared,
                     rng.integers(1, cfg.vocab_size,
                                  (int(rng.integers(1, tail_max + 1)),)
                                  ).astype(np.int32)]),
                max_new=max_new,
                arrival_s=float(arrivals[i]))
        for i in range(n_requests)
    ]


def bench_prefix(arch: str, n_requests: int, max_slots: int,
                 mean_gap_s: float, overrides: Dict[str, Any],
                 page_size: int = 8, prefill_chunk: int = 8,
                 prefix_len: int = 32, tail_max: int = 8,
                 max_new: int = 4, repeats: int = 1) -> Dict[str, Any]:
    """The shared-prefix serve discipline: the SAME shared-system-prompt
    trace through the paged scheduler with the prefix cache off vs on.

    Gates (via main()'s FAIL path): token identity on == off per request,
    prefill tokens/s uplift >= the gate at >= 50% prompt overlap, reduced
    peak resident KV pages, zero steady-state recompiles either way, and
    eq. 7-10 traffic exactness under the cached-token accounting."""
    cfg = get_config(arch).reduced(**overrides)
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    max_len = pages.round_len(prefix_len + tail_max + max_new,
                              page_size, prefill_chunk)
    slot_pages = max_len // page_size
    num_pages = max_slots * slot_pages + 1     # roomy: sharing is the story
    engines = {
        "off": ServeEngine(cfg, params, max_len=max_len, page_size=page_size,
                           num_pages=num_pages, prefix_cache="off"),
        "on": ServeEngine(cfg, params, max_len=max_len, page_size=page_size,
                          num_pages=num_pages, prefix_cache="on"),
    }
    reqs = _prefix_workload(cfg, n_requests, max_new, mean_gap_s,
                            prefix_len, tail_max)
    body_tokens = sum(len(r.prompt) - 1 for r in reqs)
    overlap = prefix_len * n_requests / sum(len(r.prompt) for r in reqs)

    warm = [dataclasses.replace(r, uid=-1 - i, arrival_s=0.0)
            for i, r in enumerate(reqs)]
    for eng in engines.values():
        ContinuousBatchingScheduler(eng, max_slots=max_slots,
                                    prefill_chunk=prefill_chunk).warmup()
        _run_continuous(eng, warm, max_slots, prefill_chunk)

    counter = slots.CompileCounter.instance()
    out: Dict[str, Any] = {}
    tokens_by_uid: Dict[str, Any] = {}
    for name, eng in engines.items():
        best, recompiles, traffic = None, 0, None
        for _ in range(repeats):
            c0 = counter.count
            eng.meter.reset()
            r = _run_continuous(eng, reqs, max_slots, prefill_chunk)
            results = r.pop("results")
            recompiles += counter.count - c0
            traffic = _check_traffic(eng, reqs, cfg,
                                     cached_tokens=r["cached_prompt_tokens"])
            assert traffic["exact"], traffic
            # prefill throughput: submitted prompt tokens per busy second —
            # the cache serves the same prompts while COMPUTING only the
            # unmatched tails, so the uplift shows up here
            r["prefill_tokens_per_s_busy"] = body_tokens / r["busy_s"]
            if best is None or (r["prefill_tokens_per_s_busy"]
                                > best["prefill_tokens_per_s_busy"]):
                best = r
            tokens_by_uid[name] = {res.uid: res.tokens for res in results}
        best["steady_state_recompiles"] = recompiles
        best["traffic"] = traffic
        out[name] = best

    token_identical = all(
        np.array_equal(tokens_by_uid["on"][uid], toks)
        for uid, toks in tokens_by_uid["off"].items())
    on, off = out["on"], out["off"]
    return {
        "config": cfg.name,
        "n_requests": n_requests,
        "max_slots": max_slots,
        "max_len": max_len,
        "page_size": page_size,
        "num_pages": num_pages,
        "prefill_chunk": prefill_chunk,
        "prefix_len": prefix_len,
        "tail_max": tail_max,
        "max_new": max_new,
        "prefix_overlap": overlap,
        "submitted_prefill_tokens": body_tokens,
        "off": off,
        "on": on,
        "token_identical": token_identical,
        "cached_prompt_tokens": on["cached_prompt_tokens"],
        "prefill_tokens_per_s_uplift":
            on["prefill_tokens_per_s_busy"] / off["prefill_tokens_per_s_busy"],
        # the resident-KV claim, measured timing-free: cumulative pages
        # DRAWN over the run — the shared prefix is stored once instead of
        # per request.  (peak_pages_in_use is reported per side in "cache"
        # but not gated: the cache also RAISES achievable concurrency by
        # unthrottling admission, which legitimately lifts the
        # instantaneous peak while every request's own footprint shrinks.)
        "kv_pages_stored_reduction":
            off["cache"]["pages_allocated"]
            / max(on["cache"]["pages_allocated"], 1),
        "zero_steady_state_recompiles":
            on["steady_state_recompiles"] == 0
            and off["steady_state_recompiles"] == 0,
        "traffic_exact": (on["traffic"]["exact"] and off["traffic"]["exact"]),
    }


def _priority_workload(cfg, n_requests: int, max_new: int, mean_gap_s: float,
                       high_frac: float = 0.25,
                       seed: int = 0) -> List[Request]:
    """The ragged Poisson trace with an SLA split: every 1/high_frac-th
    request is priority 1 (interactive tier), the rest priority 0 (batch
    tier) — the mix the overload discipline protects."""
    reqs = _workload(cfg, n_requests, max_new, mean_gap_s, seed=seed)
    period = max(int(round(1.0 / high_frac)), 1)
    return [dataclasses.replace(r, priority=1 if i % period == 0 else 0)
            for i, r in enumerate(reqs)]


def _run_online(eng: ServeEngine, reqs: List[Request], max_slots: int,
                prefill_chunk: Optional[int],
                preemption: bool) -> Dict[str, Any]:
    """One realtime pass with the online scheduler; returns per-priority
    TTFT/latency percentiles plus the loop counters."""
    sched = ContinuousBatchingScheduler(eng, max_slots=max_slots,
                                        prefill_chunk=prefill_chunk,
                                        preemption=preemption)
    out = sched.run(list(reqs), realtime=True)
    assert not out["rejected"], out["rejected"]
    prio = {r.uid: r.priority for r in reqs}
    ttft_by: Dict[int, List[float]] = {}
    lat_by: Dict[int, List[float]] = {}
    arrival = {r.uid: r.arrival_s for r in reqs}
    for res in out["results"]:
        ttft_by.setdefault(prio[res.uid], []).append(res.ttft_s)
        lat_by.setdefault(prio[res.uid], []).append(
            res.finished_s - arrival[res.uid])
    return {"wall_s": out["wall_s"],
            "busy_s": out["busy_s"],
            "decoded_tokens": out["decoded_tokens"],
            "prefill_tokens": out["prefill_tokens"],
            "cached_prompt_tokens": out["cached_prompt_tokens"],
            "preemptions": out["preemptions"],
            "by_state": out["by_state"],
            "tokens_per_s": out["tokens_per_s"],
            "requests_per_s": out["requests_per_s"],
            "ttft_s_by_priority": {str(p): _pctiles(v)
                                   for p, v in sorted(ttft_by.items())},
            "latency_s_by_priority": {str(p): _pctiles(v)
                                      for p, v in sorted(lat_by.items())}}


def _cancel_probe(eng: ServeEngine, cfg, prefill_chunk: int) -> bool:
    """Live assertion of the cancellation SLO: drive a mid-decode request
    through the open-loop api, cancel it, and check the pool occupancy is
    back to baseline after ONE ``step()``."""
    rng = np.random.default_rng(11)
    sched = ContinuousBatchingScheduler(eng, max_slots=2,
                                        prefill_chunk=prefill_chunk)
    sched.begin()
    base = eng.cache_stats(sched.cache).get("pages_in_use", 0)
    prompt = rng.integers(1, cfg.vocab_size, (12,)).astype(np.int32)
    sched.submit(Request(uid=0, prompt=prompt, max_new=eng.max_len - 12))
    for _ in range(16):
        sched.step()
        if sched.decoding_uids():
            break
    mid = eng.cache_stats(sched.cache).get("pages_in_use", 0)
    sched.cancel(0)
    fin = sched.step()           # ONE iteration
    after = eng.cache_stats(sched.cache).get("pages_in_use", 0)
    sched.poll()                 # flush the meter replay
    return (len(fin) == 1 and fin[0].state == "CANCELLED"
            and mid > base and after == base)


def bench_overload(arch: str, n_requests: int, max_slots: int,
                   overrides: Dict[str, Any], page_size: int = 8,
                   prefill_chunk: int = 8, max_new: int = 16,
                   high_frac: float = 0.25) -> Dict[str, Any]:
    """The online-serving discipline: priority-split traffic unloaded vs at
    2x overload with SLA-aware preemption, plus the cancellation probe.

    Gates (via main()'s FAIL path): high-priority p95 TTFT under overload
    <= 1.5x its unloaded value, cancelled pages returned within one
    iteration, zero steady-state recompiles, meter-exact traffic with
    preemption ON."""
    cfg = get_config(arch).reduced(**overrides)
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    max_len = pages.round_len(16 - 1 + max_new, page_size, prefill_chunk)
    slot_pages = max_len // page_size
    # tight pool (half the dense capacity): overload pressure must be real
    num_pages = max(max_slots * slot_pages // 2, slot_pages) + 1
    eng = ServeEngine(cfg, params, max_len=max_len, page_size=page_size,
                      num_pages=num_pages, prefix_cache="on")
    bpt = traffic_model_for(cfg).bytes_per_token()

    # calibrate the service rate with a saturated closed run (also the warm
    # pass: compiles every steady-state program, including the preemption
    # paths — publish, seed, re-prefill — via the prefix-armed warmup)
    ContinuousBatchingScheduler(eng, max_slots=max_slots,
                                prefill_chunk=prefill_chunk).warmup()
    warm_reqs = _priority_workload(cfg, n_requests, max_new, 0.0,
                                   high_frac, seed=1)
    warm_reqs = [dataclasses.replace(r, uid=-1 - i, arrival_s=0.0)
                 for i, r in enumerate(warm_reqs)]
    warm = _run_online(eng, warm_reqs, max_slots, prefill_chunk,
                       preemption=True)
    svc = warm["busy_s"] / n_requests      # seconds of service per request

    counter = slots.CompileCounter.instance()
    c0 = counter.count

    def run(mean_gap_s, preemption, seed):
        reqs = _priority_workload(cfg, n_requests, max_new, mean_gap_s,
                                  high_frac, seed=seed)
        eng.meter.reset()
        r = _run_online(eng, reqs, max_slots, prefill_chunk, preemption)
        # meter exactness under eviction/resume: every token the loop
        # counted as crossing — prefill, decode, re-prefill after eviction
        # — was metered at exactly eq. 7-10 bytes, nothing more
        measured = eng.measured_bytes()["total"]
        analytic = (r["prefill_tokens"] + r["decoded_tokens"]) * bpt
        r["traffic"] = {"measured": measured, "analytical": analytic,
                        "exact": measured == analytic}
        return r

    unloaded = run(4.0 * svc, preemption=True, seed=2)
    overload = run(0.5 * svc, preemption=True, seed=2)
    baseline = run(0.5 * svc, preemption=False, seed=2)
    recompiles = counter.count - c0
    cancel_ok = _cancel_probe(eng, cfg, prefill_chunk)

    hi = str(1)
    ratio = (overload["ttft_s_by_priority"][hi]["p95"]
             / max(unloaded["ttft_s_by_priority"][hi]["p95"], 1e-9))
    return {
        "config": cfg.name,
        "n_requests": n_requests,
        "max_slots": max_slots,
        "max_len": max_len,
        "page_size": page_size,
        "num_pages": num_pages,
        "prefill_chunk": prefill_chunk,
        "max_new": max_new,
        "high_priority_frac": high_frac,
        "svc_s_per_request": svc,
        "unloaded_gap_s": 4.0 * svc,
        "overload_gap_s": 0.5 * svc,
        "unloaded": unloaded,
        "overload": overload,
        "overload_no_preemption": baseline,
        "high_prio_p95_ttft_ratio": ratio,
        "preemptions": overload["preemptions"],
        "cancel_pages_freed_one_iteration": cancel_ok,
        "steady_state_recompiles": recompiles,
        "traffic_exact": (unloaded["traffic"]["exact"]
                          and overload["traffic"]["exact"]
                          and baseline["traffic"]["exact"]),
    }


# The tensor-parallel worker: ONE (tp) configuration per fresh subprocess —
# the forced host device count is a process-level XLA flag, so tp=1 and
# tp=2 cannot share a process.  Prints one "TPBENCH {json}" line.
_TP_WORKER = r"""
import dataclasses, json, sys, time
import numpy as np
import jax

from repro.configs import get_config
from repro.launch.mesh import make_test_mesh
from repro.models import api
from repro.serve import slots as slots_mod
from repro.serve.engine import ServeEngine
from repro.serve.splitbrain_engine import traffic_model_for

spec = json.loads(sys.argv[1])
tp = spec["tp"]
assert jax.device_count() >= tp, jax.devices()
cfg = get_config(spec["arch"]).reduced(**spec["overrides"])
cfg = dataclasses.replace(
    cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
params = api.init_params(cfg, jax.random.PRNGKey(0))
mesh = (make_test_mesh(shape=(1, tp)) if tp > 1
        else make_test_mesh(devices=jax.devices()[:1]))
eng = ServeEngine(cfg, params, mesh=mesh, max_len=spec["max_len"],
                  page_size=spec["page_size"], paged_attn="inplace")

rng = np.random.default_rng(0)
B, steps = spec["slots"], spec["steps"]
prompts = [rng.integers(1, cfg.vocab_size, (int(rng.integers(2, 17)),)
                        ).astype(np.int32) for _ in range(B)]
cache = eng.init_slot_cache(B)
toks = np.zeros((B,), np.int32)
for i, p in enumerate(prompts):
    assert eng.reserve_slot(i, len(p), steps + 2)
    c1, t = eng.prefill_slot(p)
    cache = eng.insert_slot(cache, c1, i)
    eng.meter_tokens(len(p) - 1)   # prefill crossings (T0-1 convention)
    toks[i] = t
active = np.ones((B,), bool)
counter = slots_mod.CompileCounter.instance()
outs, t0, c0 = [], None, None
for k in range(steps):
    if k == 2:              # steps 0-1 may compile; steady state after that
        c0 = counter.count
        t0 = time.perf_counter()
    nxt, ok, cache = eng.decode_slots(cache, toks, active)
    assert bool(np.asarray(ok).all()), "finite-logits sentinel"
    eng.meter_tokens(B)
    toks = np.asarray(nxt)  # host sync every step, like the serve loop
    outs.append(toks.tolist())
dt = time.perf_counter() - t0
measured = eng.measured_bytes()["total"]
analytic = ((sum(len(p) - 1 for p in prompts) + B * steps)
            * traffic_model_for(cfg).bytes_per_token())
print("TPBENCH " + json.dumps({
    "tp": tp,
    "devices": jax.device_count(),
    "tokens": outs,
    "decode_tokens_per_s": B * (steps - 2) / dt,
    "measured_bytes": measured,
    "analytic_bytes": analytic,
    "traffic_exact": measured == analytic,
    "steady_state_recompiles": counter.count - c0,
    "compile_counter_available": counter.available,
    "kv_shards": eng.cache_stats(cache).get("kv_shards", 1),
    "traffic_shards": eng.traffic_shards,
}))
"""


def _tp_worker(tp: int, spec: Dict[str, Any],
               timeout: int = 1800) -> Dict[str, Any]:
    """Run one TP configuration in a subprocess with ``tp`` forced host
    devices (mirrors tests/conftest.py::run_multidev)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={tp} "
                        + env.get("XLA_FLAGS", ""))
    env["JAX_PLATFORMS"] = "cpu"     # the TPU probe can hang headless runs
    src = str(Path(__file__).resolve().parent.parent / "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (env.get("PYTHONPATH", ""), src) if p)
    proc = subprocess.run(
        [sys.executable, "-c", _TP_WORKER, json.dumps({**spec, "tp": tp})],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert proc.returncode == 0, (tp, proc.stdout[-2000:],
                                  proc.stderr[-2000:])
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("TPBENCH ")][-1]
    return json.loads(line[len("TPBENCH "):])


def bench_tp(arch: str, max_new: int, max_slots: int,
             overrides: Dict[str, Any], page_size: int = 8,
             tp: int = 2) -> Dict[str, Any]:
    """The tensor-parallel serve discipline: the slot-decode workload at
    tp=1 vs tp=``tp`` on forced host devices, in fresh subprocesses.

    Gates (via main()'s FAIL path): greedy token identity, byte-exact
    traffic on BOTH sides with equal totals (per-shard meter entries sum to
    the single-device analytical model), zero steady-state recompiles, the
    pool cut on KV heads (kv_shards == tp); the decode tokens/s speedup is
    additionally gated on hosts with >= 2 cores.

    The workers run on forced CPU devices, so a parent on any other backend
    would report their CPU numbers beside its own: refused there."""
    if jax.default_backend() != "cpu":
        raise SystemExit(
            f"serve_bench: the tp discipline runs its workers on forced CPU "
            f"devices and cannot measure the {jax.default_backend()!r} "
            f"backend this process is on; run it with JAX_PLATFORMS=cpu")
    cfg = get_config(arch).reduced(**overrides)
    spec = {
        "arch": arch,
        "overrides": overrides,
        "max_len": pages.round_len(16 + max_new + 1, page_size, None),
        "page_size": page_size,
        "slots": max_slots,
        "steps": max_new,
    }
    w1 = _tp_worker(1, spec)
    wN = _tp_worker(tp, spec)
    return {
        "config": cfg.name,
        "tp": tp,
        "slots": max_slots,
        "steps": max_new,
        "page_size": page_size,
        "max_len": spec["max_len"],
        "host_cpus": os.cpu_count() or 1,
        "tp1": w1,
        "tpN": wN,
        "token_identical": w1["tokens"] == wN["tokens"],
        "traffic_exact": (w1["traffic_exact"] and wN["traffic_exact"]
                          and w1["measured_bytes"] == wN["measured_bytes"]),
        "kv_shards": wN["kv_shards"],
        "traffic_shards": wN["traffic_shards"],
        "zero_steady_state_recompiles":
            (w1["steady_state_recompiles"] == 0
             and wN["steady_state_recompiles"] == 0),
        "decode_tokens_per_s_speedup":
            wN["decode_tokens_per_s"] / w1["decode_tokens_per_s"],
    }


def _chaos_stats(out: Dict[str, Any]) -> Dict[str, Any]:
    """The JSON-safe slice of a scheduler run the chaos report keeps."""
    return {k: out[k] for k in
            ("wall_s", "busy_s", "steps", "iterations", "decoded_tokens",
             "prefill_tokens", "cached_prompt_tokens", "by_state",
             "quarantines", "failed", "recoveries", "last_recovery_s")}


def bench_chaos(arch: str, n_requests: int, max_slots: int,
                overrides: Dict[str, Any], page_size: int = 8,
                prefill_chunk: int = 8, prefix_len: int = 16,
                tail_max: int = 8, max_new: int = 8, seed: int = 0,
                recovery_s_bound: float = 5.0) -> Dict[str, Any]:
    """The crash-tolerance serve discipline (DESIGN.md §12): one shared-
    prefix trace served three times on the SAME paged + prefix engine —
    uninterrupted (the reference), then through two identical chaos cycles
    whose seeded plan combines all three device-level injection points
    (per-slot NaN corruption, a raised decode step, wholesale device loss).

    Gates (via main()'s FAIL path): every request still reaches DONE with
    tokens IDENTICAL to the uninterrupted run (recovery replays from the
    host-authoritative state, greedy decode makes that bitwise-checkable);
    each fault class actually fired (a chaos bench that injects nothing
    proves nothing); the page pool returns to (0 in-use, 0 reserved,
    0 drawn-held) after the run; recovery completes under the bound; and
    the SECOND chaos cycle — recovery paths already warm — compiles
    NOTHING (rebuild() keeps the jit caches: device loss kills buffers,
    not compiled host programs)."""
    cfg = get_config(arch).reduced(**overrides)
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    max_len = pages.round_len(prefix_len + tail_max + max_new,
                              page_size, prefill_chunk)
    slot_pages = max_len // page_size
    num_pages = max_slots * slot_pages + 1
    eng = ServeEngine(cfg, params, max_len=max_len, page_size=page_size,
                      num_pages=num_pages, prefix_cache="on")
    reqs = _prefix_workload(cfg, n_requests, max_new, 0.0,
                            prefix_len, tail_max)
    plan = FaultPlan(step_corrupt_at=4, step_corrupt_iters=2,
                     step_corrupt_frac=0.5,
                     step_error_at=8, step_error_count=1,
                     device_loss_at=14)

    def run_once(faults):
        sched = ContinuousBatchingScheduler(eng, max_slots=max_slots,
                                            prefill_chunk=prefill_chunk,
                                            faults=faults)
        out = sched.run(list(reqs))
        assert not out["rejected"], out["rejected"]
        out["recovery_log"] = list(sched.recovery_log)
        return out

    ref = run_once(None)
    ref_tokens = {r.uid: r.tokens for r in ref.pop("results")}
    # cycle 1 warms every recovery-path shape; cycle 2 (same plan, same
    # seed -> same fault sequence) is the measured one and must not compile
    run_once(FaultInjector(plan, seed=seed))
    counter = slots.CompileCounter.instance()
    c0 = counter.count
    inj = FaultInjector(plan, seed=seed)
    out = run_once(inj)
    recompiles = counter.count - c0
    results = out.pop("results")
    pool = eng._pager.pool
    pool_state = (pool.pages_in_use, pool.total_reserved, pool.total_drawn)
    token_identical = (
        len(results) == len(ref_tokens)
        and all(np.array_equal(r.tokens, ref_tokens[r.uid])
                for r in results))
    fired = {k: inj.fired(k)
             for k in ("step_corrupt", "step_error", "device_loss")}
    return {
        "config": cfg.name,
        "n_requests": n_requests,
        "max_slots": max_slots,
        "max_len": max_len,
        "page_size": page_size,
        "num_pages": num_pages,
        "prefill_chunk": prefill_chunk,
        "max_new": max_new,
        "seed": seed,
        "plan": dataclasses.asdict(plan),
        "reference": _chaos_stats(ref),
        "chaos": _chaos_stats(out),
        "recovery_log": out["recovery_log"],
        "fired": fired,
        "all_faults_fired": all(v > 0 for v in fired.values()),
        "token_identical": token_identical,
        "all_done": out["by_state"] == {"DONE": len(reqs)},
        "quarantines": out["quarantines"],
        "failed": out["failed"],
        "recoveries": out["recoveries"],
        "last_recovery_s": out["last_recovery_s"],
        "recovery_s_bound": recovery_s_bound,
        "recovery_bounded": 0.0 < out["last_recovery_s"] <= recovery_s_bound,
        "pool_state_after": pool_state,
        "pool_baseline_restored": pool_state == (0, 0, 0),
        "steady_state_recompiles": recompiles,
        "zero_steady_state_recompiles": recompiles == 0,
    }


def bench_kv_quant(arch: str, n_requests: int, max_new: int, max_slots: int,
                   mean_gap_s: float, overrides: Dict[str, Any],
                   page_size: int = 8, prefill_chunk: int = 8,
                   kv_dtype: str = "int8") -> Dict[str, Any]:
    """The quantized-KV-pages serve discipline (DESIGN.md §13): the ragged
    trace through the in-place paged scheduler with a bf16 pool vs a
    ``kv_dtype`` pool of the same page geometry.

    Gates (via main()'s FAIL path): resident tokens per pool byte up by
    >= the gate (pure storage accounting, timing-free), per-step greedy
    argmax flip rate vs the bf16 run within budget, eq. 7-10 boundary
    bytes byte-IDENTICAL between the two pools, host KV reads shrunk
    >= 1.5x, zero steady-state recompiles on the quantized engine."""
    cfg = get_config(arch).reduced(**overrides)
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    max_len = pages.round_len(16 - 1 + max_new, page_size, prefill_chunk)
    slot_pages = max_len // page_size
    num_pages = max(max_slots * slot_pages // 2, slot_pages) + 1
    engines = {
        "bf16": ServeEngine(cfg, params, max_len=max_len,
                            page_size=page_size, num_pages=num_pages),
        kv_dtype: ServeEngine(cfg, params, max_len=max_len,
                              page_size=page_size, num_pages=num_pages,
                              kv_dtype=kv_dtype),
    }
    reqs = _workload(cfg, n_requests, max_new, mean_gap_s)
    warm = [dataclasses.replace(r, uid=-1 - i, arrival_s=0.0)
            for i, r in enumerate(reqs)]
    for eng in engines.values():
        _run_continuous(eng, warm, max_slots, prefill_chunk)

    counter = slots.CompileCounter.instance()
    out: Dict[str, Any] = {}
    tokens_by_uid: Dict[str, Any] = {}
    for name, eng in engines.items():
        c0 = counter.count
        eng.meter.reset()
        r = _run_continuous(eng, reqs, max_slots, prefill_chunk)
        results = r.pop("results")
        r["steady_state_recompiles"] = counter.count - c0
        r["traffic"] = _check_traffic(eng, reqs, cfg)
        assert r["traffic"]["exact"], r["traffic"]
        r["measured_boundary_bytes"] = eng.measured_bytes()["total"]
        tokens_by_uid[name] = {res.uid: res.tokens for res in results}
        out[name] = r

    base, quant = out["bf16"], out[kv_dtype]
    # greedy-token divergence, two figures.  token_divergence_frac counts
    # every differing aligned token (informational): after ONE near-tie
    # argmax flip the remaining greedy path legitimately differs, so a
    # single flip late in a long trace cascades through the tail.  The
    # GATED figure is token_flip_rate: first-flip EVENTS per aligned token
    # compared (tokens up to and including each sequence's first mismatch)
    # — the per-step probability that quantization flips the argmax, which
    # is what the KV representation actually controls.
    total = diverged = flips = compared = 0
    for uid, toks in tokens_by_uid["bf16"].items():
        q = tokens_by_uid[kv_dtype][uid]
        n = min(len(toks), len(q))
        total += max(len(toks), len(q))
        neq = np.asarray(toks[:n]) != np.asarray(q[:n])
        diverged += int(neq.sum()) + max(len(toks), len(q)) - n
        flips += int(neq.any())
        compared += (int(np.argmax(neq)) + 1) if neq.any() else n
    divergence = diverged / max(total, 1)
    flip_rate = flips / max(compared, 1)
    # the capacity claim, timing-free: same pool GEOMETRY (num_pages), so
    # resident tokens per byte scale inversely with the per-token STORAGE
    # figure — bf16 bytes/token over quantized bytes/token IS the uplift
    stored_ratio = (base["cache"]["kv_token_bytes_stored"]
                    / quant["cache"]["kv_token_bytes_stored"])
    read_ratio = base["kv_read_bytes"] / max(quant["kv_read_bytes"], 1)
    return {
        "config": cfg.name,
        "kv_dtype": kv_dtype,
        "n_requests": n_requests,
        "max_new": max_new,
        "max_slots": max_slots,
        "max_len": max_len,
        "page_size": page_size,
        "num_pages": num_pages,
        "prefill_chunk": prefill_chunk,
        "bf16": base,
        "quant": quant,
        "resident_tokens_per_byte_uplift": stored_ratio,
        "pool_bytes_bf16": base["cache"]["pool_bytes"],
        "pool_bytes_quant": quant["cache"]["pool_bytes"],
        "kv_read_bytes_shrink": read_ratio,
        "token_divergence_frac": divergence,
        "token_flip_rate": flip_rate,
        "boundary_bytes_identical":
            base["measured_boundary_bytes"]
            == quant["measured_boundary_bytes"],
        "traffic_exact": (base["traffic"]["exact"]
                          and quant["traffic"]["exact"]),
        "zero_steady_state_recompiles":
            quant["steady_state_recompiles"] == 0
            and base["steady_state_recompiles"] == 0,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small workload, loose gates (CI smoke)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--mean-gap-ms", type=float, default=2.0,
                    help="mean Poisson inter-arrival gap (saturating default)")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    # 64 full-run requests: sub-second walls make the discipline ratios
    # noisy on a shared machine; a longer trace stabilizes the gates
    n_requests = args.requests or (8 if args.quick else 64)
    max_new = args.max_new or (8 if args.quick else 32)
    # d_model=128 keeps the reduced model decode GEMV-bound enough that
    # batching the slots is a real win, CPU or not
    overrides = dict(vocab_size=256, d_model=128, d_ff=384)
    archs = ["llama2-7b"] if args.quick else ["llama2-7b", "rwkv6-7b"]

    results = [bench_arch(a, n_requests, max_new, args.slots,
                          args.mean_gap_ms / 1e3, overrides,
                          page_size=args.page_size,
                          prefill_chunk=args.prefill_chunk,
                          repeats=1 if args.quick else 3) for a in archs]
    # the shared-prefix discipline: same trace with the prefix cache off/on
    prefix_results = [bench_prefix(
        "llama2-7b", max(n_requests // 2, 8), args.slots,
        args.mean_gap_ms / 1e3, overrides, page_size=args.page_size,
        prefill_chunk=args.prefill_chunk,
        repeats=1 if args.quick else 3)]
    # the online-overload discipline: priority-split traffic unloaded vs at
    # 2x overload with SLA-aware preemption, plus the cancellation probe.
    # FEW slots relative to the trace: at 2x the service rate the queue
    # must actually build, or there is no pressure to preempt under
    overload_results = [bench_overload(
        "llama2-7b", max(n_requests // 2, 16),
        max(args.slots // 4, 2), overrides, page_size=args.page_size,
        prefill_chunk=args.prefill_chunk,
        max_new=max(max_new // 2, 8))]
    # the tensor-parallel discipline: tp=1 vs tp=2 in fresh forced-host-
    # device subprocesses (the device count is a process-level XLA flag)
    tp_results = [bench_tp("llama2-7b", max_new, max(args.slots // 2, 4),
                           overrides, page_size=args.page_size)]
    # the chaos-recovery discipline: the same shared-prefix trace with a
    # seeded plan firing all three device-level injection points, gated on
    # token identity vs the uninterrupted run of the same engine.  The
    # recovery bound is wall-clock generous (loaded CI box); every other
    # chaos gate is absolute correctness
    chaos_recovery_s = 5.0
    chaos_results = [bench_chaos(
        "llama2-7b", max(n_requests // 4, 8), max(args.slots // 4, 4),
        overrides, page_size=args.page_size,
        prefill_chunk=args.prefill_chunk, max_new=max(max_new // 2, 8),
        recovery_s_bound=chaos_recovery_s)]
    # the quantized-KV-pages discipline: the ragged trace from a bf16 vs an
    # int8 pool of identical page geometry — capacity and divergence gates
    # are storage/token accounting, so quick mode keeps them in full
    kv_quant_results = [bench_kv_quant(
        "llama2-7b", max(n_requests // 2, 8), max_new, args.slots,
        args.mean_gap_ms / 1e3, overrides, page_size=args.page_size,
        prefill_chunk=args.prefill_chunk)]

    # rwkv keeps dense recurrent state (no-op page table): the memory gate
    # only applies where the pool actually pages KV
    gate = 1.0 if args.quick else 2.0
    mem_gate = 1.0 if args.quick else 2.0
    rps_gate = 0.75 if args.quick else 0.9
    # the in-place discipline does strictly less work than gather (no dense
    # view copy, no scatter), and the gate only applies to configs that
    # actually page, where that structural margin measures >10% (1.14x in
    # the shipped artifact; the oracle's page loop is unrolled precisely so
    # scan dispatch overhead can't eat it) — best-of-repeats absorbs the
    # remaining noise; quick mode (sub-second walls) gets slack instead
    inplace_gate = 0.9 if args.quick else 1.0
    # shared-prefix gates: >= 50% prompt overlap must buy >= 1.3x prefill
    # tokens/s (the cache computes only the unmatched tails) and fewer
    # peak resident KV pages (the shared prefix is stored once); quick
    # mode keeps the structural gates (identity, traffic, recompiles) but
    # relaxes the timing one (sub-second walls are noise-dominated)
    prefix_gate = 1.0 if args.quick else 1.3
    prefix_pages_gate = 1.0 if args.quick else 1.5
    # overload gate: high-priority p95 TTFT at 2x overload within 1.5x of
    # unloaded (the SLA preemption is FOR this); quick mode's sub-second
    # TTFTs are scheduler-noise-dominated, so it gets headroom while the
    # structural gates (cancel SLO, recompiles, traffic) stay strict
    overload_gate = 4.0 if args.quick else 1.5
    # tp timing gate: tp=2 must beat tp=1 decode tokens/s by this factor —
    # but ONLY on a host that can actually run two shards concurrently; on
    # a 1-core box (or in quick mode's sub-second walls) the structural
    # gates (token identity, byte-exact traffic, recompiles, kv_shards)
    # still apply in full while the wall-clock one is moot
    tp_gate = 1.6
    tp_timing_gated = (not args.quick) and (os.cpu_count() or 1) >= 2
    # kv_quant gates: int8 codes + page-amortized scales must buy >= 1.8x
    # resident tokens per pool byte (hd=32 pages at ps=8 measure ~1.94x);
    # the per-step argmax flip rate vs bf16 stays a small fraction
    # (near-tie flips only — one flip cascades the tail, which
    # token_divergence_frac reports but the flip-rate gate does not
    # double-count); the host KV-read channel shrinks >= 1.5x.  All
    # storage/token accounting, so quick mode keeps every kv_quant gate
    # in full
    kv_quant_gate = 1.8
    kv_quant_flip_budget = 0.05
    kv_quant_read_gate = 1.5
    summary = {
        r["config"]: {
            "requests_per_s_speedup": round(r["requests_per_s_speedup"], 2),
            "tokens_per_s_speedup": round(r["tokens_per_s_speedup"], 2),
            "paged_vs_dense_requests_per_s":
                round(r["paged_vs_dense_requests_per_s"], 2),
            "paged_inplace_vs_gather_tokens_per_s":
                (round(r["paged_inplace_vs_gather_tokens_per_s"], 2)
                 if r["paged_inplace_vs_gather_tokens_per_s"] is not None
                 else None),   # None: family never pages (dense fallback)
            "paged_memory_saving": round(r["paged_memory_saving"], 2),
            "gather_transient_bytes_per_step":
                r["paged"]["gather_transient_bytes_per_step"],
            "zero_steady_state_recompiles":
                r["steady_state_recompiles"] == 0
                and r["paged_steady_state_recompiles"] == 0
                and r["gather_steady_state_recompiles"] == 0,
            "traffic_exact": r["traffic_exact"],
        } for r in results
    }
    summary["overload"] = {
        r["config"]: {
            "high_prio_p95_ttft_ratio": round(r["high_prio_p95_ttft_ratio"],
                                              2),
            "preemptions": r["preemptions"],
            "cancel_pages_freed_one_iteration":
                r["cancel_pages_freed_one_iteration"],
            "zero_steady_state_recompiles":
                r["steady_state_recompiles"] == 0,
            "traffic_exact": r["traffic_exact"],
        } for r in overload_results
    }
    summary["tp"] = {
        r["config"]: {
            "tp": r["tp"],
            "decode_tokens_per_s_speedup":
                round(r["decode_tokens_per_s_speedup"], 2),
            "token_identical": r["token_identical"],
            "traffic_exact": r["traffic_exact"],
            "kv_shards": r["kv_shards"],
            "traffic_shards": r["traffic_shards"],
            "zero_steady_state_recompiles":
                r["zero_steady_state_recompiles"],
            "timing_gated": tp_timing_gated,
        } for r in tp_results
    }
    summary["chaos"] = {
        r["config"]: {
            "token_identical": r["token_identical"],
            "all_done": r["all_done"],
            "fired": r["fired"],
            "quarantines": r["quarantines"],
            "failed": r["failed"],
            "recoveries": r["recoveries"],
            "last_recovery_s": round(r["last_recovery_s"], 4),
            "pool_baseline_restored": r["pool_baseline_restored"],
            "zero_steady_state_recompiles":
                r["zero_steady_state_recompiles"],
        } for r in chaos_results
    }
    summary["kv_quant"] = {
        r["config"]: {
            "kv_dtype": r["kv_dtype"],
            "resident_tokens_per_byte_uplift":
                round(r["resident_tokens_per_byte_uplift"], 2),
            "kv_read_bytes_shrink": round(r["kv_read_bytes_shrink"], 2),
            "token_divergence_frac": round(r["token_divergence_frac"], 4),
            "token_flip_rate": round(r["token_flip_rate"], 4),
            "boundary_bytes_identical": r["boundary_bytes_identical"],
            "traffic_exact": r["traffic_exact"],
            "zero_steady_state_recompiles":
                r["zero_steady_state_recompiles"],
        } for r in kv_quant_results
    }
    summary["prefix"] = {
        r["config"]: {
            "prefix_overlap": round(r["prefix_overlap"], 2),
            "prefill_tokens_per_s_uplift":
                round(r["prefill_tokens_per_s_uplift"], 2),
            "kv_pages_stored_reduction":
                round(r["kv_pages_stored_reduction"], 2),
            "cached_prompt_tokens": r["cached_prompt_tokens"],
            "token_identical": r["token_identical"],
            "zero_steady_state_recompiles":
                r["zero_steady_state_recompiles"],
            "traffic_exact": r["traffic_exact"],
            "ttft_p50_on_vs_off": (
                round(r["on"]["ttft_s"]["p50"]
                      / max(r["off"]["ttft_s"]["p50"], 1e-9), 2)),
        } for r in prefix_results
    }
    report = {
        "schema": "serve_bench/v8",
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "quick": args.quick,
        "disciplines": list(DISCIPLINE_NAMES),
        "gate_requests_per_s_speedup": gate,
        "gate_paged_memory_saving": mem_gate,
        "gate_paged_vs_dense_requests_per_s": rps_gate,
        "gate_paged_inplace_vs_gather_tokens_per_s": inplace_gate,
        "gate_paged_transient_bytes": 0,
        "gate_prefix_prefill_uplift": prefix_gate,
        "gate_prefix_pages_reduction": prefix_pages_gate,
        "gate_overload_ttft_ratio": overload_gate,
        "gate_tp_decode_speedup": tp_gate,
        "tp_timing_gated": tp_timing_gated,
        "gate_chaos_recovery_s": chaos_recovery_s,
        "gate_kv_quant_capacity_uplift": kv_quant_gate,
        "gate_kv_quant_flip_rate": kv_quant_flip_budget,
        "gate_kv_quant_read_shrink": kv_quant_read_gate,
        "results": results,
        "prefix_results": prefix_results,
        "overload_results": overload_results,
        "tp_results": tp_results,
        "chaos_results": chaos_results,
        "kv_quant_results": kv_quant_results,
        "summary": summary,
    }
    # registry cross-check: every discipline in the registry must have a
    # section in this report — a bench that forgets one FAILS, it doesn't
    # silently drift (repro/serve/disciplines.py)
    covered = set()
    for r in results:
        covered |= {d for d in ("sequential", "continuous", "paged_gather",
                                "paged") if r.get(d) is not None}
    covered |= {"prefix"} if prefix_results else set()
    covered |= {"overload"} if overload_results else set()
    covered |= {"tp"} if tp_results else set()
    covered |= {"chaos"} if chaos_results else set()
    covered |= {"kv_quant"} if kv_quant_results else set()
    missing_disciplines = [n for n in DISCIPLINE_NAMES if n not in covered]
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps(summary, indent=2))
    print(f"wrote {args.out}")

    def paged_ok(r):
        if not (r["paged_transient_eliminated"]
                and r["paged_inplace_reads_less"]):
            return False
        if "num_pages" not in r["paged"]["cache"]:
            return True               # family never paged (dense fallback)
        return (r["paged_memory_saving"] >= mem_gate
                and r["paged_vs_dense_requests_per_s"] >= rps_gate
                and r["paged_inplace_vs_gather_tokens_per_s"] >= inplace_gate)

    def prefix_ok(r):
        return (r["token_identical"]
                and r["zero_steady_state_recompiles"]
                and r["traffic_exact"]
                and r["cached_prompt_tokens"] > 0
                and r["prefill_tokens_per_s_uplift"] >= prefix_gate
                and r["kv_pages_stored_reduction"] >= prefix_pages_gate)

    def overload_ok(r):
        return (r["high_prio_p95_ttft_ratio"] <= overload_gate
                and r["preemptions"] > 0
                and r["cancel_pages_freed_one_iteration"]
                and r["steady_state_recompiles"] == 0
                and r["traffic_exact"])

    def tp_ok(r):
        return (r["token_identical"]
                and r["traffic_exact"]
                and r["zero_steady_state_recompiles"]
                and r["kv_shards"] == r["tp"]
                and (not tp_timing_gated
                     or r["decode_tokens_per_s_speedup"] >= tp_gate))

    def kv_quant_ok(r):
        return (r["resident_tokens_per_byte_uplift"] >= kv_quant_gate
                and r["token_flip_rate"] <= kv_quant_flip_budget
                and r["kv_read_bytes_shrink"] >= kv_quant_read_gate
                and r["boundary_bytes_identical"]
                and r["traffic_exact"]
                and r["zero_steady_state_recompiles"])

    def chaos_ok(r):
        return (r["token_identical"]
                and r["all_done"]
                and r["all_faults_fired"]
                and r["recoveries"] > 0
                and r["quarantines"] > 0
                and r["failed"] == 0
                and r["pool_baseline_restored"]
                and r["recovery_bounded"]
                and r["zero_steady_state_recompiles"])

    ok = all(r["requests_per_s_speedup"] >= gate
             and r["steady_state_recompiles"] == 0
             and r["paged_steady_state_recompiles"] == 0
             and r["gather_steady_state_recompiles"] == 0
             and r["traffic_exact"]
             and paged_ok(r) for r in results) \
        and all(prefix_ok(r) for r in prefix_results) \
        and all(overload_ok(r) for r in overload_results) \
        and all(tp_ok(r) for r in tp_results) \
        and all(chaos_ok(r) for r in chaos_results) \
        and all(kv_quant_ok(r) for r in kv_quant_results) \
        and not missing_disciplines
    if not ok:
        print(f"FAIL: continuous < {gate}x sequential requests/s, paged < "
              f"{mem_gate}x memory saving, paged < {rps_gate}x dense "
              f"requests/s, paged in-place < {inplace_gate}x gather "
              "tokens/s, nonzero dense-view transient, in-place KV reads "
              ">= gather, steady-state recompile, traffic mismatch, a "
              f"prefix-cache gate (token identity, < {prefix_gate}x "
              f"prefill tokens/s, < {prefix_pages_gate}x page reduction, "
              f"no hits), an overload gate (high-prio p95 TTFT > "
              f"{overload_gate}x unloaded, no preemptions, cancelled pages "
              "not freed in one iteration), a tp gate (tp tokens differ "
              "from tp=1, traffic inexact, recompile, pool not head-cut"
              + (f", decode speedup < {tp_gate}x" if tp_timing_gated
                 else "")
              + "), a chaos gate (recovered tokens differ from the "
              "uninterrupted run, a request not DONE, a fault class never "
              "fired, no recovery/quarantine, pool not back to baseline, "
              f"recovery > {chaos_recovery_s}s, recompile on the repeat "
              "cycle), a kv_quant gate (resident tokens/byte < "
              f"{kv_quant_gate}x, argmax flip rate > "
              f"{kv_quant_flip_budget}, KV reads shrunk < "
              f"{kv_quant_read_gate}x, boundary bytes differ from bf16), "
              f"or registry coverage ({missing_disciplines})",
              file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
