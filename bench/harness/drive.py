"""The client: sends a cell's traffic through the scheduler's open-loop
API (``submit`` / ``step`` / ``poll``) and records what a user sees, one
timestamp per token as the ``stream`` callback hands it over (after the
step's blocking read-back, so each is a device-synced time)."""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

clock = time.perf_counter


class CompileLog:
    """Backend compiles (persistent-cache reads included), their seconds,
    and persistent-cache hits, from JAX's monitoring events."""

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

    def since(self, mark) -> Dict[str, Any]:
        return {"compiles": self.count - mark[0],
                "compile_s": self.seconds - mark[1],
                "cache_hits": self.cache_hits - mark[2]}


@dataclasses.dataclass
class Req:
    i: int
    due: float                 # host clock
    prompt: np.ndarray
    max_new: int
    submitted: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    state: Optional[str] = None


class Spans:
    """Host spans around the calls into each layer, written into the
    profiler's trace (``TraceAnnotation``), and the shapes of each step
    the cost functions need.  Installed on one scheduler and its engine,
    in a traced run only."""

    def __init__(self, sched, eng):
        import jax
        self._ann = jax.profiler.TraceAnnotation
        self.decode: List[tuple] = []    # (t_end, n_active, sum of read lengths)
        self.chunks: List[tuple] = []    # (t_end, start, width)
        self.admits: Dict[int, tuple] = {}   # uid -> (t, prompt_len, cached)
        self.chunk = sched.prefill_chunk
        self.sched, self.eng = sched, eng
        for obj, attr, name in ((sched, "_admit", "sched.admit"),
                                (sched, "_decode_tick", "sched.decode_tick"),
                                (eng, "prefill_chunk_slot",
                                 "engine.prefill_chunk_slot"),
                                (eng, "insert_slot", "engine.insert_slot"),
                                (eng, "seed_request_cache",
                                 "engine.seed_request_cache")):
            setattr(obj, attr, self._wrap(getattr(obj, attr), name))
        sched._prefill_tick = self._prefill(sched._prefill_tick)
        eng.decode_slots = self._decode(eng.decode_slots)
        sched._start = self._start(sched._start)

    def span(self, name: str):
        return self._ann(name)

    def _wrap(self, fn: Callable, name: str) -> Callable:
        @functools.wraps(fn)
        def inner(*a, **k):
            with self._ann(name):
                return fn(*a, **k)
        return inner

    def _prefill(self, fn):
        sched = self.sched

        @functools.wraps(fn)
        def inner():
            job = sched._prefilling[0] if sched._prefilling else None
            start = job.consumed if job is not None else 0
            width = (min(self.chunk, len(job.prompt) - 1 - start)
                     if job is not None else 0)
            with self._ann("sched.prefill_tick"):
                fn()
            if job is not None and job.consumed == start + width and width:
                self.chunks.append((clock(), start, width))
        return inner

    def _decode(self, fn):
        pager = self.eng._pager

        @functools.wraps(fn)
        def inner(cache, tokens, active, corrupt=None):
            act = np.asarray(active, bool)
            lens = pager.host_len[act]
            with self._ann("engine.decode_slots"):
                out = fn(cache, tokens, active, corrupt)
            self.decode.append((clock(), int(act.sum()),
                                int(lens.sum() + act.sum())))
            return out
        return inner

    def _start(self, fn):
        @functools.wraps(fn)
        def inner(rec, slot, cached):
            uid = rec.req.uid
            if uid not in self.admits:
                prompt, _ = self.sched._effective(rec)
                self.admits[uid] = (clock(), len(prompt), int(cached))
            return fn(rec, slot, cached)
        return inner


class Tracer:
    """Starts the profiler at the first iteration boundary past ``t0`` and
    stops it at the first past ``t1``; the iterations between run inside
    one ``bench.window`` span, which marks the traced window on the
    trace's own clock."""

    def __init__(self, log_dir: str, t0: float, t1: float):
        import jax
        self.jax = jax
        self.dir, self.t0, self.t1 = log_dir, t0, t1
        self.state = "before"
        self.ann = None
        self.start = self.stop = None

    def tick(self, now: float) -> None:
        if self.state == "before" and now >= self.t0:
            # no Python function tracing: it costs the host more than the
            # work it would show, and the bench's spans say what it did
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            self.jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.ann = self.jax.profiler.TraceAnnotation("bench.window")
            self.ann.__enter__()
            self.start = clock()
            self.state = "on"
        elif self.state == "on" and now >= self.t1:
            self.close()

    def close(self) -> None:
        if self.state == "on":
            self.stop = clock()
            self.ann.__exit__(None, None, None)
            self.jax.profiler.stop_trace()
            self.state = "done"


class Client:
    def __init__(self, sched, traffic, seconds: float, spans: Spans = None,
                 tracer_fn: Callable = None, drain_s: float = 60.0):
        self.sched, self.traffic, self.seconds = sched, traffic, seconds
        self.spans = spans
        self.tracer_fn = tracer_fn      # (win0, win1) -> Tracer
        self.tracer = None
        self.drain_s = drain_s
        self.reqs: Dict[int, Req] = {}
        self.uid_base = 1_000_000       # clear of the warm-up's uids
        self.win0 = self.win1 = None
        self.counters: Dict[str, Any] = {}

    # ---------------------------------------------------------------- calls
    def _span(self, name: str):
        if self.spans is None:
            import contextlib
            return contextlib.nullcontext()
        return self.spans.span(name)

    def _submit(self, i: int, due_abs: float) -> None:
        from repro.serve.scheduler import Request
        _, prompt, max_new = self.traffic.request(i)
        r = Req(i, due_abs, prompt, max_new)
        uid = self.uid_base + i
        self.reqs[uid] = r

        def stream(tok, r=r):
            r.times.append(clock())
            r.tokens.append(int(tok))

        with self._span("client.submit"):
            r.submitted = clock()
            ok = self.sched.submit(Request(
                uid=uid, prompt=prompt, max_new=max_new,
                arrival_s=due_abs - self._loop0, stream=stream))
        if not ok:
            r.state = "REJECTED"

    def _step(self) -> List[int]:
        """One scheduler iteration; the uids that ended in it."""
        self.sched.step()
        now = clock()
        ended = []
        with self._span("client.poll"):
            for res in self.sched.poll():
                r = self.reqs.get(res.uid)
                if r is not None:
                    r.state = res.state
                    ended.append(res.uid)
            for rej in self.sched.poll_rejected():
                r = self.reqs.get(rej.uid)
                if r is not None:
                    r.state = "REJECTED"
                    ended.append(rej.uid)
        if self.tracer is not None:
            self.tracer.tick(now)
        return ended

    def _snapshot(self) -> Dict[str, int]:
        s = self.sched
        return {"decode_steps": s._decode_steps,
                "decoded_tokens": s._decoded_tokens,
                "iterations": s._iterations}

    def _open_window(self, win0: float) -> None:
        self.win0, self.win1 = win0, win0 + self.seconds
        self.counters["start"] = self._snapshot()
        self.counters["queued_start"] = len(self.sched._pending)
        if self.tracer_fn is not None:
            self.tracer = self.tracer_fn(self.win0, self.win1)

    # ---------------------------------------------------------------- loops
    def run(self) -> None:
        self._loop0 = clock() - self.sched.clock()
        self.t_start = clock()
        if self.traffic.loop == "open":
            self._open()
        else:
            self._closed()
        if self.tracer is not None:
            self.tracer.close()

    def _open(self) -> None:
        t = self.traffic
        t0 = self.t_start
        self._open_window(t0 + t.mix["ramp_s"])
        i = 0
        nxt = t0 + t.request(0)[0]
        closed_at = None
        while True:
            now = clock()
            while nxt <= now and nxt < self.win1:
                self._submit(i, nxt)
                i += 1
                nxt = t0 + t.request(i)[0]
            if closed_at is None and now >= self.win1:
                closed_at = now
                self.counters["end"] = self._snapshot()
                self.counters["queued_end"] = len(self.sched._pending)
            if closed_at is not None:
                waiting = [r for r in self.reqs.values()
                           if self.win0 <= r.due < self.win1
                           and not r.times and r.state is None]
                if not waiting or now - closed_at > self.drain_s:
                    break
            if self.sched.has_work():
                self._step()
            else:
                wake = min(nxt, self.win1) if closed_at is None else now
                with self._span("client.sleep"):
                    time.sleep(max(0.0, wake - clock()))
                if self.tracer is not None:
                    self.tracer.tick(clock())
        self.window_s = self.win1 - self.win0

    def _closed(self) -> None:
        t = self.traffic
        conc = t.mix["concurrency"]
        t0 = self.t_start
        for i in range(conc):
            self._submit(i, t0)
        i = conc
        while True:
            ended = self._step()
            now = clock()
            for _ in ended:
                self._submit(i, now)
                i += 1
            if self.win0 is None:
                decoding = sum(1 for r in self.reqs.values()
                               if r.times and r.state is None)
                if decoding == conc or now - t0 > t.mix["ramp_max_s"]:
                    self.counters["filled"] = decoding
                    self._open_window(now)
            elif now >= self.win1:
                self.counters["end"] = self._snapshot()
                self.win1 = now          # the window ends with this step
                break
        self.window_s = self.win1 - self.win0

    # -------------------------------------------------------------- results
    def in_window(self, r: Req) -> bool:
        return self.win0 <= r.due < self.win1

    def stats(self) -> Dict[str, Any]:
        """The user-side numbers of the window."""
        w0, w1 = self.win0, self.win1
        reqs = list(self.reqs.values())
        open_loop = self.traffic.loop == "open"
        counted = [r for r in reqs if self.in_window(r)] if open_loop else [
            r for r in reqs if r.submitted is not None and r.submitted < w1]
        ttft = [r.times[0] - r.due for r in counted if r.times]
        mid = (w0 + w1) / 2
        halves = [[r.times[0] - r.due for r in counted
                   if r.times and (r.due < mid) == first] for first in (1, 0)]
        itl = [b - a for r in reqs for a, b in zip(r.times, r.times[1:])
               if w0 <= b < w1]
        tokens = sum(1 for r in reqs for t in r.times if w0 < t <= w1)
        late = [r.submitted - r.due for r in reqs if r.submitted is not None
                and r.due >= self.t_start]
        failed = [r for r in counted
                  if (r.state not in (None, "DONE"))
                  or (open_loop and not r.times)]
        by_state: Dict[str, int] = {}
        for r in counted:
            k = r.state or ("DECODE" if r.times else "WAITING")
            by_state[k] = by_state.get(k, 0) + 1
        c = self.counters
        steps = (c["end"]["decode_steps"] - c["start"]["decode_steps"])
        decoded = (c["end"]["decoded_tokens"] - c["start"]["decoded_tokens"])
        return {"window_s": self.window_s, "attempted": len(counted),
                "failed": len(failed), "ttft_s": ttft, "itl_s": itl,
                "ttft_halves_s": halves,
                "tokens": tokens, "late_s": late, "by_state": by_state,
                "decode_steps": steps, "decoded_tokens": decoded,
                "queued_start": c.get("queued_start"),
                "queued_end": c.get("queued_end"),
                "filled": c.get("filled")}
