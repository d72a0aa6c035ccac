"""The in-program span recorder (serve/telemetry.py) and the spans the
serving loop records with it (DESIGN.md §14): nesting and parents, a
request's uid on its spans, the bounded ring, window selection, counts that
roll up into the iteration, the host self time that leaves the blocking
read-backs out, and the span tree of a scripted scheduler run."""
import dataclasses
import glob
import threading

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api
from repro.serve import telemetry
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import (COUNTERS, STEP_COUNTS,
                                   ContinuousBatchingScheduler, Request)
from repro.serve.telemetry import Recorder, Span


def _names(rec):
    return [s.name for s in rec.spans()]


def test_nesting_records_the_enclosing_span_as_parent():
    rec = Recorder()
    with rec.span("outer") as attrs:
        attrs["n"] = 1
        with rec.span("inner"):
            pass
        with rec.span("inner2"):
            with rec.span("leaf"):
                pass
    by = {s.name: s for s in rec.spans()}
    assert _names(rec) == ["inner", "leaf", "inner2", "outer"]   # by end
    assert by["outer"].parent is None
    assert by["inner"].parent == by["outer"].id
    assert by["inner2"].parent == by["outer"].id
    assert by["leaf"].parent == by["inner2"].id
    assert by["outer"].attrs == {"n": 1} and by["leaf"].attrs is None
    assert by["outer"].t0 <= by["inner"].t0 <= by["inner"].t1 \
        <= by["leaf"].t0 <= by["outer"].t1


def test_parents_are_per_thread():
    """A span opened on another thread while one is open here is a root
    there: each thread keeps its own stack."""
    rec = Recorder()
    with rec.span("main"):
        def other():
            with rec.span("loop"):
                pass
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    by = {s.name: s for s in rec.spans()}
    assert by["loop"].parent is None


def test_uid_is_inherited_by_spans_without_one():
    rec = Recorder()
    with rec.span("step"):
        with rec.span("start", uid=7):
            with rec.span("seed"):
                pass
            with rec.span("other", uid=9):
                pass
        with rec.span("decode"):
            pass
    by = {s.name: s for s in rec.spans()}
    assert by["start"].uid == by["seed"].uid == 7
    assert by["other"].uid == 9
    assert by["step"].uid is None and by["decode"].uid is None


def test_ring_keeps_the_newest_records():
    rec = Recorder(maxlen=4)
    for i in range(10):
        with rec.span(f"s{i}"):
            pass
    assert _names(rec) == ["s6", "s7", "s8", "s9"]
    rec.clear()
    assert rec.spans() == []


def test_spans_selects_by_start_in_a_half_open_window():
    rec = Recorder()
    for i in range(3):
        with rec.span(f"s{i}"):
            pass
    s0, s1, s2 = rec.spans()
    assert [s.name for s in rec.spans(s1.t0, s2.t0)] == ["s1"]
    assert [s.name for s in rec.spans(s0.t0, s1.t0)] == ["s0"]
    assert [s.name for s in rec.spans(s1.t0)] == ["s1", "s2"]
    assert rec.spans(s2.t0 + 1.0) == []


def test_count_rolls_up_to_the_span_that_carries_the_key():
    rec = Recorder()
    rec.count("chunks")                          # nothing open: no effect
    with rec.span("step") as attrs:
        attrs.update(chunks=0, syncs=0)
        with rec.span("tick") as tick:
            tick["own"] = 0
            rec.count("chunks")
            rec.count("own", 3)
            with rec.span("decode.wait"):        # a read-back: syncs += 1
                pass
            with rec.span("insert.wait"):
                pass
        rec.count("missing")                     # no span carries it
    by = {s.name: s for s in rec.spans()}
    assert by["step"].attrs == {"chunks": 1, "syncs": 2}
    assert by["tick"].attrs == {"own": 3}


def test_traced_decorator_keeps_the_function_and_records_a_span():
    rec = telemetry.recorder()

    @telemetry.traced("test.traced")
    def f(a, b=2):
        """doc"""
        return a + b

    assert f.__name__ == "f" and f.__doc__ == "doc"
    n0 = len([s for s in rec.spans() if s.name == "test.traced"])
    assert f(1, b=3) == 4
    assert len([s for s in rec.spans() if s.name == "test.traced"]) == n0 + 1


def _span(i, name, t0, t1, parent=None):
    return Span(i, name, t0, t1, parent, None, None)


def test_self_times_take_out_the_union_of_wait_descendants():
    step = _span(0, "sched.step", 0.0, 10.0)
    other = _span(10, "sched.step", 20.0, 25.0)
    recs = [
        step, other,
        _span(1, "sched.decode_tick", 1.0, 9.0, parent=0),
        _span(2, "sched.decode.wait", 2.0, 5.0, parent=1),   # 3 s
        _span(3, "engine.insert_slot", 4.0, 7.0, parent=0),
        _span(4, "engine.insert.wait", 4.0, 6.0, parent=3),  # overlaps: +1
        _span(5, "late.wait", 9.5, 12.0, parent=0),          # clipped: 0.5
        _span(6, "outer.wait", 1.0, 1.5, parent=None),       # not a child
        _span(7, "x.wait", 7.5, 8.0, parent=5),              # under a wait
    ]
    assert telemetry.self_times([step, other], recs) == \
        pytest.approx([10.0 - 3.0 - 1.0 - 0.5, 5.0])
    assert telemetry.self_times([], recs) == []


def test_spans_land_in_the_profiler_trace_under_their_bare_names(tmp_path):
    """The annotation a span opens is what a profiler trace shows, under
    the span's own name: attrs never enter the event's name."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("sched.step") as attrs:
            attrs["chunks"] = 1
            with telemetry.span("sched.decode.wait"):
                jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines
             for ev in line.events}
    assert {"sched.step", "sched.decode.wait"} <= names


# --------------------------------------------------- the serving loop's spans
@pytest.fixture(scope="module")
def scripted_run():
    """A scripted mix on a tiny lm through the paged scheduler: request 1's
    body streams as three chunks, then decodes alone; request 2 hits the
    prefix its pages published (seed + one tail chunk + insert), request 3
    repeats the whole prefix (seed + insert, then a CoW copy on its first
    decode step)."""
    cfg = get_config("stablelm-1.6b").reduced()
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_len=64, page_size=8, num_pages=33,
                      prefix_cache="on")
    sched = ContinuousBatchingScheduler(eng, max_slots=3, prefill_chunk=8,
                                        max_prefill_jobs=1)
    rng = np.random.default_rng(0)
    tok = lambda n: rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
    shared = tok(16)
    sched.begin()
    rec = telemetry.recorder()
    t0 = rec.spans()[-1].t1 if rec.spans() else float("-inf")
    sched.submit(Request(uid=1, prompt=np.concatenate([shared, tok(3)]),
                         max_new=6))
    for _ in range(4):
        sched.step()
    sched.submit(Request(uid=2, prompt=np.concatenate([shared, tok(5)]),
                         max_new=4))
    sched.submit(Request(uid=3, prompt=shared.copy(), max_new=4))
    while sched.has_work():
        sched.step()
    sched.poll()
    return [s for s in rec.spans() if s.t0 > t0], sched


def test_scheduler_step_attrs_of_a_scripted_mix(scripted_run):
    recs, sched = scripted_run
    steps = [s for s in recs if s.name == "sched.step"]
    assert [s.attrs["iteration"] for s in steps] == list(range(len(steps)))
    assert len(steps) == sched.counters()["iterations"]
    assert all(set(s.attrs) == {"iteration", *STEP_COUNTS} for s in steps)
    a = [s.attrs for s in steps]
    # request 1: admitted with its first chunk, two more chunks, the last
    # one inserted (its length read back) and decoded in the same iteration
    assert a[0] == dict(a[0], admitted=1, chunks=1, decode=0, syncs=0)
    assert a[1] == dict(a[1], chunks=1, inserts=0, decode=0)
    assert a[2] == dict(a[2], chunks=1, inserts=1, decode=1, n_active=1,
                        syncs=2)
    # a decode-only iteration: one blocking read-back, nothing prefill-side
    assert a[3] == dict(iteration=3, n_active=1, admitted=0, seeds=0,
                        chunks=0, inserts=0, cow_copies=0, syncs=1, decode=1)
    # the prefix hits: a seed each, request 3's whole-prompt hit copies the
    # shared page it appends into
    assert sum(x["seeds"] for x in a) == 2
    assert sum(x["cow_copies"] for x in a) == 1
    assert sum(x["admitted"] for x in a) == 3
    assert sum(x["decode"] for x in a) == sched.counters()["steps"]
    assert sum(x["n_active"] for x in a) == sched.counters()["decoded_tokens"]
    assert all(x["syncs"] == x["decode"] + x["inserts"] for x in a)


def test_scheduler_span_tree_and_request_uids(scripted_run):
    recs, _ = scripted_run
    by = {s.id: s for s in recs}
    parent = lambda s: by[s.parent].name if s.parent in by else None
    want = {
        "sched.admit": "sched.step", "sched.prefill_tick": "sched.step",
        "sched.decode_tick": "sched.step", "sched.try_admit": "sched.admit",
        "sched.start": "sched.admit",
        "engine.pager.admit": "sched.try_admit",
        "engine.seed_request_cache": "sched.start",
        "engine.prefill_chunk_slot": "sched.prefill_tick",
        "engine.decode_slots": "sched.decode_tick",
        "engine.pager.pre_decode": "engine.decode_slots",
        "engine.decode.dispatch": "engine.decode_slots",
        "engine.insert.wait": "engine.insert_slot",
        "sched.decode.wait": "sched.decode_tick",
        "sched.decode.emit": "sched.decode_tick",
    }
    seen = {s.name for s in recs}
    assert set(want) | {"sched.step", "engine.insert_slot",
                        "engine.pager.publish", "engine.meter",
                        "engine.pager.table_upload"} <= seen
    for s in recs:
        if s.name in want:
            assert parent(s) == want[s.name], s
        if s.name == "sched.step":
            assert s.parent is None
    # insert and publish run under the request that caused them
    assert {parent(s) for s in recs if s.name == "engine.insert_slot"} == \
        {"sched.prefill_tick", "sched.start"}
    # admission, seed, chunk and insert spans carry the request's uid
    for name in ("sched.try_admit", "engine.pager.admit", "sched.start",
                 "engine.seed_request_cache", "engine.prefill_chunk_slot",
                 "engine.insert_slot", "engine.insert.wait",
                 "sched.prefill_tick"):
        assert all(s.uid in (1, 2, 3) for s in recs if s.name == name), name
    assert {s.uid for s in recs if s.name == "engine.seed_request_cache"} \
        == {2, 3}
    assert {s.uid for s in recs if s.name == "engine.prefill_chunk_slot"} \
        == {1, 2}
    # the batched decode works for no one request
    assert all(s.uid is None for s in recs
               if s.name in ("engine.decode_slots", "sched.decode.wait"))


def test_counters_are_public_and_zero_before_the_loop_begins(scripted_run):
    _, sched = scripted_run
    assert ContinuousBatchingScheduler(engine=None).counters() == \
        dict.fromkeys(COUNTERS, 0)
    got = sched.counters()
    assert tuple(got) == COUNTERS
    assert got["iterations"] == sched._iterations
    assert got["cached_prompt_tokens"] > 0 and got["steps"] > 0
