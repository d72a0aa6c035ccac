"""What the program records about its own iterations: the ``sched.step``
spans of ``repro.serve.telemetry``, one per scheduler iteration, on the
host clock the client's window is taken on.  A program without that
recorder gives nothing, and the metrics that read it are left out."""
from __future__ import annotations

import bisect
from typing import Any, Dict, List, Optional, Sequence, Tuple

STEP = "sched.step"
# the prefill-side work an iteration can carry beside its decode step
PREFILL_SIDE = ("seeds", "chunks", "inserts", "cow_copies")


def _telemetry():
    try:
        from repro.serve import telemetry
    except ImportError:
        return None
    return telemetry


def decode_iterations(run) -> Optional[Tuple[list, list]]:
    """(the window's iterations that dispatched a decode step, every record
    the recorder holds), or None without a recorder or such iterations."""
    tel = _telemetry()
    if tel is None:
        return None
    recs = tel.recorder().spans()
    steps = [s for s in recs if s.name == STEP and s.attrs
             and s.attrs.get("decode") and run.win0 <= s.t0 < run.win1]
    return (steps, recs) if steps else None


def step_counters(ends: Sequence[float]) -> List[Dict[str, Any]]:
    """For each time in ``ends``, the attributes of the ``sched.step``
    span whose interval holds it: what the program counted in that
    iteration.  An empty dict where no span holds it, or the program has
    no recorder."""
    tel = _telemetry()
    if tel is None:
        return [{} for _ in ends]
    steps = sorted((s for s in tel.recorder().spans() if s.name == STEP),
                   key=lambda s: s.t0)
    t0s = [s.t0 for s in steps]
    out = []
    for t in ends:
        i = bisect.bisect_right(t0s, t) - 1
        hit = i >= 0 and steps[i].t1 >= t
        out.append(dict(steps[i].attrs or {}) if hit else {})
    return out


def host_self_ms(run) -> Optional[float]:
    """Mean over the window's decode iterations of the ``sched.step``
    duration less its blocking ``*.wait`` read-backs, in ms."""
    got = decode_iterations(run)
    if got is None:
        return None
    steps, recs = got
    own: List[float] = _telemetry().self_times(steps, recs)
    return 1e3 * sum(own) / len(own)


def mixed_share_pct(run) -> Optional[float]:
    """Share of the window's decode iterations that also dispatched a
    seed, a prefill chunk, an insert or a CoW page copy, in percent."""
    got = decode_iterations(run)
    if got is None:
        return None
    steps = got[0]
    mixed = sum(1 for s in steps if any(s.attrs.get(k) for k in PREFILL_SIDE))
    return 100.0 * mixed / len(steps)
