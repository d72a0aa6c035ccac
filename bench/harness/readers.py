"""What the per-layer metric files in ``bench/metrics/`` read: one run's
client records, the scheduler's counters, the traced steps' shapes and the
program's counts in each, the trace's reduction, and the configuration's
cost module, which counts the work of each traced step.  Every reader
returns None where it finds nothing to read, and the harness then leaves
its metric out of the line."""
from __future__ import annotations

import dataclasses
from types import ModuleType
from typing import Any, Dict, List, Optional

from harness import costs as dense_costs
from harness import trace as trace_mod

# the programs the served path runs, by the names their jitted functions
# carry (serve/engine.py): the masked paged decode step and the prefill chunk
DECODE_PROGRAM = "paged_step"
CHUNK_PROGRAM = "chunk_fn"


@dataclasses.dataclass
class Run:
    arch: Dict[str, Any]
    peaks: Optional[Dict[str, Any]]
    stats: Dict[str, Any]
    reqs: Dict[int, Any]
    win0: float
    win1: float
    open_loop: bool
    admits: Dict[int, tuple]
    decode: List[tuple]            # traced decode steps:
    #                                (t, n_active, read, the step's counters)
    chunks: List[tuple]            # traced prefill chunks: (t, start, width)
    trace: Optional[Dict[str, Any]]
    costs: ModuleType = dense_costs   # the configuration's work counts


def _window_admits(run: Run) -> List[tuple]:
    """(request, admission) of the requests the window counts: those due
    in it (open loop), or admitted in it (closed loop)."""
    out = []
    for uid, adm in run.admits.items():
        r = run.reqs.get(uid)
        if r is None:
            continue
        t = r.due if run.open_loop else adm[0]
        if run.win0 <= t < run.win1:
            out.append((r, adm))
    return out


def prefix_hit_pct(run: Run) -> Optional[float]:
    adm = [a for _, a in _window_admits(run)]
    prompt = sum(a[1] for a in adm)
    return 100.0 * sum(a[2] for a in adm) / prompt if prompt else None


def batch_mean(run: Run) -> Optional[float]:
    s = run.stats
    return s["decoded_tokens"] / s["decode_steps"] if s["decode_steps"] else None


def program_ms(run: Run, part: str) -> Optional[float]:
    if run.trace is None:
        return None
    p = trace_mod.program(run.trace, part)
    return p["seconds"] / p["count"] * 1e3 if p and p["count"] else None


def _roofline(run: Run, works) -> float:
    return sum(dense_costs.roofline_s(f, b, run.peaks)[0] for f, b in works)


def _decode_work(run: Run, fn):
    return [fn(run.arch, n, read, counters=c)
            for _, n, read, c in run.decode if n]


def _chunk_work(run: Run, fn):
    return [fn(run.arch, s, w) for _, s, w in run.chunks]


def kernel_roofline_pct(run: Run, which: str) -> Optional[float]:
    """Roofline time of the kernel's traced calls over its device time."""
    if run.trace is None or run.peaks is None:
        return None
    if which == "paged_decode":
        part, work = DECODE_PROGRAM, _decode_work(
            run, run.costs.paged_decode_kernel)
    else:
        part, work = CHUNK_PROGRAM, _chunk_work(
            run, run.costs.flash_prefill_kernel)
    k = trace_mod.kernel(run.trace, part)
    if not k or not k["seconds"] or not work:
        return None
    return 100.0 * _roofline(run, work) / k["seconds"]


def step_mfu_pct(run: Run) -> Optional[float]:
    """Roofline time of every traced decode step and prefill chunk over
    the traced window: the whole step's share of the chip's peak."""
    if run.trace is None or run.peaks is None:
        return None
    work = (_decode_work(run, run.costs.decode_step)
            + _chunk_work(run, run.costs.prefill_chunk))
    if not work:
        return None
    return 100.0 * _roofline(run, work) / run.trace["window_s"]


def idle_pct(run: Run) -> Optional[float]:
    if run.trace is None or not run.trace["window_s"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
