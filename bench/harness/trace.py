"""From a profiler trace to device busy and idle time, device time per
program and per kernel, and idle gaps by the host span that covers them.

``extract`` reads the ``.xplane.pb`` the JAX profiler wrote into plain
lists; ``reduce`` turns those into numbers.  The traced window is the host
span ``bench.window`` that the client opens around the traced iterations.

Device events come from each TPU plane's ``XLA Ops`` line (one event per
operation run, named by its HLO text; a loop's op contains the ops of its
body) and ``XLA Modules`` line (one per program run).  A program is named
by its module, e.g. ``jit_paged_step(123)`` -> ``jit_paged_step``, and an
operation belongs to the program run that contains it.  A Pallas kernel is
a custom call whose target is ``tpu_custom_call``.  Host spans are the
bench's own (``sched.*``, ``engine.*``, ``client.*``): an idle gap goes to
the innermost of them that covers it.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

WINDOW = "bench.window"


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


HOST_SPANS = ("sched.", "engine.", "client.", WINDOW)


def op_kind(hlo: str) -> str:
    """A short kind for an operation's HLO text: the custom call's target,
    else the instruction's name without its number
    (``%copy.81 = bf16[...] copy(...)`` -> ``copy``)."""
    m = re.search(r'custom_call_target="([^"]+)"', hlo)
    if m:
        return m.group(1)
    name = hlo.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def _owner(ops, modules):
    """The module run containing each op (by start time), in op order."""
    mods = sorted(modules, key=lambda m: m[1])
    out, j = [], 0
    for _, a, b in ops:
        while j < len(mods) and mods[j][2] < a:
            j += 1
        out.append(mods[j][0] if j < len(mods) and mods[j][1] <= a else "")
    return out


def extract(path: str) -> Dict[str, Any]:
    """The parts of a trace the reduction reads, as plain lists:
    ``host`` [(name, start_ns, end_ns)] of the bench's spans, and per TPU
    plane ``ops`` [(kind, start_ns, end_ns, module, is_kernel)] and
    ``modules`` [(name, start_ns, end_ns)]."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host: List[Tuple[str, float, float]] = []
    devices: Dict[str, Dict[str, list]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [(_module_name(ev.name), ev.start_ns,
                             ev.start_ns + ev.duration_ns) for ev in line.events]
                elif line.name == "XLA Ops":
                    ops = [(op_kind(ev.name), ev.start_ns,
                            ev.start_ns + ev.duration_ns) for ev in line.events]
            ops.sort(key=lambda o: o[1])
            owner = _owner(ops, mods)
            devices[plane.name] = {
                "modules": mods,
                "ops": [(k, a, b, m, k == "tpu_custom_call")
                        for (k, a, b), m in zip(ops, owner)]}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0 and ev.name.startswith(HOST_SPANS):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return {"host": host, "devices": devices}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: float, b: float, w0: float, w1: float):
    a, b = max(a, w0), min(b, w1)
    return (a, b) if b > a else None


def _cover(spans: List[Tuple[str, float, float]], t: float) -> str:
    """The innermost bench span (shortest) that contains time ``t``."""
    best = None
    for name, a, b in spans:
        if a <= t <= b and (best is None or b - a < best[0]):
            best = (b - a, name)
    return best[1] if best else "outside the bench's spans"


def _self_times(ops) -> List[float]:
    """Each op's time less the time of the ops nested in it (sorted by
    start): a loop's own time excludes its body's."""
    own = [b - a for _, a, b, *_ in ops]
    stack: List[int] = []
    for i, (_, a, b, *_) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= b - a
        stack.append(i)
    return own


def reduce(tr: Dict[str, Any], top: int = 10) -> Optional[Dict[str, Any]]:
    """Numbers of the traced window, averaged over the TPU planes where a
    plane-level quantity is asked for; None without a TPU plane."""
    devs = tr["devices"]
    if not devs:
        return None
    wins = [(a, b) for n, a, b in tr["host"] if n == WINDOW]
    if wins:
        w0, w1 = wins[0]
    else:
        ev = [(a, b) for d in devs.values() for _, a, b, *_ in d["ops"]]
        w0, w1 = min(a for a, _ in ev), max(b for _, b in ev)
    spans = [s for s in tr["host"] if s[0] != WINDOW and s[2] > w0 and s[1] < w1]
    window_s = (w1 - w0) * 1e-9
    busy, modules, kernels, ops_t, gaps = 0.0, {}, {}, {}, {}
    for d in devs.values():
        ivs, inside = [], []
        for op in d["ops"]:
            c = _clip(op[1], op[2], w0, w1)
            if c is not None:
                ivs.append(c)
                inside.append((op[0], c[0], c[1], op[3], op[4]))
        for (name, a, b, mod, kern), own in zip(inside, _self_times(inside)):
            dt = (b - a) * 1e-9
            key = f"{mod}/{name}"
            ops_t[key] = ops_t.get(key, 0.0) + own * 1e-9
            if kern:
                k = kernels.setdefault(mod, {"count": 0, "seconds": 0.0})
                k["count"] += 1
                k["seconds"] += dt
        u = _union(ivs)
        busy += sum(b - a for a, b in u) * 1e-9
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                who = _cover(spans, (a + b) / 2)
                gaps[who] = gaps.get(who, 0.0) + (b - a) * 1e-9
        for name, a, b in d["modules"]:
            c = _clip(a, b, w0, w1)
            if c is None:
                continue
            m = modules.setdefault(name, {"count": 0, "seconds": 0.0})
            m["count"] += 1
            m["seconds"] += (c[1] - c[0]) * 1e-9
    n = len(devs)
    scale = lambda d: {k: {"count": v["count"] / n, "seconds": v["seconds"] / n}
                       for k, v in d.items()}
    rank = lambda d: sorted(([k, v / n] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {"window_s": window_s, "busy_s": busy / n,
            "window_ns": (w0, w1),
            "modules": scale(modules), "kernels": scale(kernels),
            "device_ops": rank(ops_t), "idle_gaps": rank(gaps)}


def program(red: Dict[str, Any], part: str) -> Optional[Dict[str, float]]:
    """Device time of the program whose module name contains ``part``."""
    hits = [v for k, v in red["modules"].items() if part in k]
    if not hits:
        return None
    return {"count": sum(h["count"] for h in hits),
            "seconds": sum(h["seconds"] for h in hits)}


def kernel(red: Dict[str, Any], part: str) -> Optional[Dict[str, float]]:
    """Device time of the kernels inside the program named by ``part``."""
    hits = [v for k, v in red["kernels"].items() if part in k]
    if not hits:
        return None
    return {"count": sum(h["count"] for h in hits),
            "seconds": sum(h["seconds"] for h in hits)}
