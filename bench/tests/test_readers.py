"""The traced readers count each step's work with the configuration's cost
module, handing it what the program counted in that step."""
import types

import pytest

from harness import costs, program_spans, readers, spec
from harness import trace as trace_mod
from repro.serve import telemetry
from repro.serve.telemetry import Recorder, Span
from test_costs import LM, PEAKS

MS = 1e6    # ns


def traced():
    """One TPU plane over a 100-ms window: a decode step of 30 ms whose
    kernel runs 20 ms, and a prefill chunk of 10 ms, all of it its kernel."""
    ops = [("custom-call.1", 10 * MS, 30 * MS, "jit_paged_step", True),
           ("fusion.2", 30 * MS, 40 * MS, "jit_paged_step", False),
           ("custom-call.3", 50 * MS, 60 * MS, "jit_chunk_fn", True)]
    mods = [("jit_paged_step", 10 * MS, 40 * MS), ("jit_chunk_fn", 50 * MS, 60 * MS)]
    return trace_mod.reduce({"host": [("bench.window", 0, 100 * MS)],
                             "devices": {"/device:TPU:0": {"ops": ops,
                                                           "modules": mods}}})


DECODE = [(1.0, 4, 4000, {"iteration": 7, "n_active": 4}),
          (1.1, 0, 0, {"iteration": 8, "n_active": 0}),
          (1.2, 8, 9000, {"iteration": 9, "n_active": 8})]
CHUNKS = [(1.05, 0, 256), (1.15, 256, 256)]


def run_with(cost):
    return readers.Run(arch=LM, peaks=PEAKS, stats={}, reqs={}, win0=0.0,
                       win1=2.0, open_loop=True, admits={}, decode=DECODE,
                       chunks=CHUNKS, trace=traced(), costs=cost)


def roofline(works):
    return sum(costs.roofline_s(f, b, PEAKS)[0] for f, b in works)


def test_the_default_module_reads_what_the_readers_read_before():
    """The sums the readers made with ``harness/costs.py`` called by name,
    over the same steps, give the same numbers through ``Run.costs``."""
    run = run_with(spec.costs({"name": "stablelm-1.6b", "family": "lm"}))
    assert run.costs is costs
    live = [(n, read) for _, n, read, _ in DECODE if n]
    dec = roofline([costs.paged_decode_kernel(LM, n, r) for n, r in live])
    pre = roofline([costs.flash_prefill_kernel(LM, s, w) for _, s, w in CHUNKS])
    step = roofline([costs.decode_step(LM, n, r) for n, r in live]
                    + [costs.prefill_chunk(LM, s, w) for _, s, w in CHUNKS])
    assert readers.kernel_roofline_pct(run, "paged_decode") == \
        pytest.approx(100 * dec / 20e-3, rel=1e-12)
    assert readers.kernel_roofline_pct(run, "flash_prefill") == \
        pytest.approx(100 * pre / 10e-3, rel=1e-12)
    assert readers.step_mfu_pct(run) == pytest.approx(100 * step / 100e-3,
                                                      rel=1e-12)


def test_a_cost_module_receives_each_traced_steps_counters():
    got = []

    def decode(a, n, read, counters=None):
        got.append(("step", n, counters))
        return 1e9, 1e9

    def kernel(a, n, read, counters=None):
        got.append(("kernel", n, counters))
        return 1e9, 1e9

    stub = types.SimpleNamespace(decode_step=decode, paged_decode_kernel=kernel,
                                 prefill_chunk=lambda a, s, w: (1e9, 1e9),
                                 flash_prefill_kernel=lambda a, s, w: (1e9, 1e9))
    run = run_with(stub)
    assert readers.step_mfu_pct(run) is not None
    assert readers.kernel_roofline_pct(run, "paged_decode") is not None
    live = [(n, c) for _, n, _, c in DECODE if n]
    assert got == [("step", n, c) for n, c in live] + \
        [("kernel", n, c) for n, c in live]


@pytest.fixture
def recorded(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(telemetry, "recorder", lambda: rec)
    return rec


def test_a_decode_record_gets_the_counters_of_the_step_that_holds_it(recorded):
    s1 = Span(1, "sched.step", 100.0, 100.010, None, None, {"iteration": 1})
    tick = Span(2, "sched.decode_tick", 100.002, 100.009, 1, None, None)
    s2 = Span(3, "sched.step", 100.010, 100.030, None, None,
              {"iteration": 2, "chunks": 1})
    recorded._ring.extend([s1, tick, s2])
    got = program_spans.step_counters([100.005, 100.020, 100.5, 99.0])
    assert got == [{"iteration": 1}, {"iteration": 2, "chunks": 1}, {}, {}]
    got[0]["iteration"] = 5
    assert s1.attrs == {"iteration": 1}


def test_no_recorder_no_counters(monkeypatch):
    """A parent program, which has no ``repro.serve.telemetry``."""
    import sys

    import repro.serve
    monkeypatch.setitem(sys.modules, "repro.serve.telemetry", None)
    monkeypatch.delattr(repro.serve, "telemetry")
    assert program_spans.step_counters([1.0, 2.0]) == [{}, {}]
