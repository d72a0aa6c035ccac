#!/usr/bin/env python3
"""Run one cell of the benchmark on the machine it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it builds the cell's model from its configuration file with
weights drawn from ``--seed`` on the device in bf16, serves the cell's
traffic through ``ServeEngine`` + ``ContinuousBatchingScheduler`` (paged
pool, in-place paged decode, chunked prefill, prefix cache), warms up every
program the traffic uses, measures ``--seconds`` seconds, checks what the
window served against the plain float32 reference, and prints one JSON
object as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``check``, the numbers compared
beside their limits (also the last lines of standard error).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
a steady part of the window with the JAX profiler and reports its
per-layer metrics.  Without a TPU, with fewer chips than the cell asks for,
or on a device kind that ``bench/peaks.json`` does not list, it exits with
code 2 and prints no result.

``--rehearse`` runs the same path on the CPU at a tiny size (the
configuration's family and head ratio, its ``rehearsal`` sizes, the mix's
rehearsal knobs) and
prints only counts: no time, no device metric.  ``--rate`` overrides an
open-loop mix's arrival rate; it exists for the sweep that finds the knee.
``--control`` puts the fp8 control in the program's place: the tokens
that the reference computed in fp8 puts first, at the positions of the
same sample, are judged by the same limit, and ``correct`` comes out
false where the check does its work.

JAX's persistent compilation cache lives at ``<checkout>/.bench_cache/jax``
(or at ``JAX_COMPILATION_CACHE_DIR`` where that points inside the
checkout), so only a checkout's first run of a cell compiles.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness import spec  # noqa: E402

CACHE = ROOT / ".bench_cache"


class Refused(SystemExit):
    """A run that cannot give a result: exit 2, nothing on stdout."""


def refuse(msg: str):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    raise Refused(2)


def emit(kind: str, **fields) -> None:
    print(json.dumps({"line": kind, **fields}), flush=True)


def setup_jax(rehearse: bool):
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    if rehearse:
        jax.config.update("jax_enable_compilation_cache", False)
        return jax, None
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = CACHE / "jax"
    if env and Path(env).resolve().is_relative_to(ROOT.resolve()):
        path = Path(env)
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax, str(path)


def e2e_value(name: str, st, setup_s: float):
    """An end-to-end metric from the client's records: ``setup_s``,
    ``output_tok_s``, or a percentile ``ttft_pNN_ms`` / ``itl_pNN_ms``."""
    import re

    import numpy as np
    if name == "setup_s":
        return setup_s
    if name == "output_tok_s":
        return st["tokens"] / st["window_s"]
    m = re.fullmatch(r"(ttft|itl)_p(\d+)_ms", name)
    if m:
        xs = st[m.group(1) + "_s"]
        return float(np.percentile(xs, int(m.group(2)))) * 1e3 if xs else None
    raise spec.SpecError(f"no end-to-end metric {name!r} in the harness")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--control", action="store_true")
    return ap.parse_args(argv)


def run(args, patch=None) -> dict:
    """One run of a cell; returns the result line.  ``patch(eng, sched)``
    is called on the built server before warm-up (tests plant faults
    with it)."""
    bm = spec.benchmark()
    cell = spec.workload(bm, args.workload)
    conf = spec.config(bm, cell["config"])
    mix = spec.traffic(cell["traffic"])
    if not (ROOT / "src" / "repro").is_dir():
        refuse(f"{ROOT} holds no src/repro: nothing to measure")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    rehearse = args.rehearse
    jax, cache_dir = setup_jax(rehearse)
    devs = jax.devices()
    peaks = None
    if not rehearse:
        if devs[0].platform != "tpu":
            refuse(f"no TPU: jax.devices()[0].platform is {devs[0].platform!r}")
        if len(devs) < cell["chips"]:
            refuse(f"the cell asks for {cell['chips']} chips, found {len(devs)}")
        try:
            peaks = spec.peaks(devs[0].device_kind)
        except spec.SpecError as e:
            refuse(str(e))
    devs = devs[:cell["chips"]]

    from harness import check, drive, model, program_spans, readers
    from harness import trace as trace_mod
    from harness.traffic import Traffic
    from repro.launch.mesh import make_test_mesh

    log = drive.CompileLog()
    a = model.arch(conf, rehearse)
    cfg = model.model_config(conf, rehearse)
    ref = spec.reference(conf)
    costs = spec.costs(conf)
    traffic = Traffic(mix, args.seed, a["vocab_size"], rehearse, args.rate)
    serving = traffic.serving
    mesh = make_test_mesh(devs)
    t = time.perf_counter()
    params = model.make_weights(cfg, args.seed, mesh)
    weights_s = time.perf_counter() - t
    pages = model.num_pages(conf, serving, cfg, costs, rehearse)
    eng, sched = model.build_server(cfg, params, mesh, serving, pages,
                                    conf.get("kv_dtype", "bf16"))
    if not all(x is y for x, y in zip(jax.tree.leaves(eng.params),
                                       jax.tree.leaves(params))):
        raise RuntimeError("the engine copied the weights it was given")
    if patch is not None:
        patch(eng, sched)
    m = log.mark()
    t = time.perf_counter()
    sched.warmup()
    warm = log.since(m) | {"warmup_s": time.perf_counter() - t}
    setup_s = time.perf_counter() - T_PROC

    spans = drive.Spans(sched, eng) if args.trace else None
    trace_dir = CACHE / "trace" / args.workload
    tracer_fn = None
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        span = min(traffic.mix["trace_s"], args.seconds)

        def tracer_fn(w0, w1):
            mid = (w0 + w1) / 2
            return drive.Tracer(str(trace_dir), mid - span / 2, mid + span / 2)

    client = drive.Client(sched, traffic, args.seconds, spans=spans,
                          tracer_fn=tracer_fn)
    m = log.mark()
    client.run()
    window_compiles = log.since(m)
    st = client.stats()
    mem = devs[0].memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    emit("window", workload=args.workload, seed=args.seed, num_pages=pages,
         slots=serving["slots"], max_len=serving["max_len"],
         weights_s=weights_s, **warm, setup_s=setup_s,
         window_compiles=window_compiles["compiles"],
         window_s=st["window_s"], attempted=st["attempted"],
         failed=st["failed"], by_state=st["by_state"],
         requests_done=sum(1 for r in client.reqs.values()
                           if r.state == "DONE"),
         tokens=st["tokens"], decode_steps=st["decode_steps"],
         decoded_tokens=st["decoded_tokens"],
         ttft_p50_ms=_pct(st["ttft_s"], 50), ttft_p90_ms=_pct(st["ttft_s"], 90),
         ttft_n=len(st["ttft_s"]),
         ttft_p50_halves_ms=[_pct(h, 50) for h in st["ttft_halves_s"]],
         itl_p50_ms=_pct(st["itl_s"], 50), itl_n=len(st["itl_s"]),
         late_p50_ms=_pct(st["late_s"], 50), late_max_ms=_pct(st["late_s"], 100),
         queued_start=st["queued_start"], queued_end=st["queued_end"],
         filled=st["filled"], cache=eng.cache_stats(sched.cache),
         peak_bytes_in_use=peak, bytes_limit=mem.get("bytes_limit"),
         compile_cache=cache_dir)

    # free the program's state before the reference runs
    done = [(r.prompt, r.tokens) for r in client.reqs.values()
            if r.state == "DONE" and r.tokens]
    ctx = dict(reqs=client.reqs, win0=client.win0, win1=client.win1,
               admits=spans.admits if spans else {},
               tracer=client.tracer)
    decode_recs = spans.decode if spans else []
    chunk_recs = spans.chunks if spans else []
    client.sched = None
    if spans:
        spans.sched = spans.eng = None
    del eng, sched, client, spans
    gc.collect()

    red = None
    if args.trace:
        path = trace_mod.find_xplane(str(trace_dir))
        if path is not None:
            red = trace_mod.reduce(trace_mod.extract(path))
        shutil.rmtree(trace_dir, ignore_errors=True)

    pick = check.sample(done, args.seed, traffic.mix["check"]["sample_tokens"],
                        traffic.mix["check"]["sample_requests"])
    G = int(traffic.output.max())
    t = time.perf_counter()
    got = check.gaps(ref, params, a, [done[i] for i in pick],
                     serving["max_len"], G, control=args.control)
    got["reference_s"] = time.perf_counter() - t
    limit = (conf["rehearsal"] if rehearse else conf["check"])["max_logit_gap"]
    emit("check", **got, limit=limit)
    # the control is put in the program's place: its first choices are the
    # tokens judged, by the same rule
    judged = "control_max_logit_gap" if args.control else "max_logit_gap"
    correct = got[judged] is not None and got[judged] <= limit

    metrics = {}
    tr = ctx["tracer"]
    t0, t1 = (tr.start, tr.stop) if tr is not None and tr.start else (0, 0)
    traced = [r for r in decode_recs if t0 < r[0] <= t1]
    traced = [r + (c,) for r, c in zip(
        traced, program_spans.step_counters([r[0] for r in traced]))]
    rd = readers.Run(
        arch=a, peaks=peaks, stats=st, reqs=ctx["reqs"], win0=ctx["win0"],
        win1=ctx["win1"], open_loop=traffic.loop == "open",
        admits=ctx["admits"], decode=traced, costs=costs,
        chunks=[r for r in chunk_recs if t0 < r[0] <= t1], trace=red)
    for mt in spec.metrics_for(bm, args.workload, bool(args.trace)):
        if rehearse and mt["source"] in ("host_clock", "device_trace"):
            continue
        if mt["name"] in [e["name"] for e in bm["end_to_end"]]:
            v = e2e_value(mt["name"], st, setup_s)
        else:
            v = spec.metric_reader(mt["name"])(rd)
        if v is not None:
            metrics[mt["name"]] = {"value": v, "unit": mt["unit"]}

    if rehearse:
        device = {"platform": devs[0].platform, "rehearsal": True}
    else:
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs), "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": st["attempted"],
            "failed": st["failed"], "metrics": metrics, "device": device}
    if red is not None and not rehearse:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
        emit("trace", modules=red["modules"], kernels=red["kernels"],
             traced_decode_steps=len(rd.decode),
             traced_chunks=len(rd.chunks))
    line["check"] = {judged: {"value": got[judged], "limit": limit}}
    return line


def _pct(xs, q):
    import numpy as np
    return float(np.percentile(xs, q)) * 1e3 if xs else None


def main(argv=None) -> int:
    args = parse(argv)
    try:
        line = run(args)
    except (spec.SpecError, FileNotFoundError) as e:
        refuse(str(e))
    for k, v in line["check"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr,
              flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Refused as e:
        sys.exit(e.code)
