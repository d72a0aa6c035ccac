"""What the program reports about itself: the readers of its span recorder
(``sched.host_self_ms``, ``sched.mixed_iter_share``) on a hand-built run,
and the named Pallas kernels, which the trace reduction must still count
as kernels."""
import sys

import pytest

from harness import program_spans, readers, spec
from harness import trace as trace_mod
from repro.serve import telemetry
from repro.serve.telemetry import Recorder, Span

WIN0, WIN1 = 100.0, 200.0


def _run():
    return readers.Run(arch={}, peaks=None, stats={}, reqs={}, win0=WIN0,
                       win1=WIN1, open_loop=True, admits={}, decode=[],
                       chunks=[], trace=None)


def _step(i, t0, t1, decode=1, **counts):
    attrs = dict(iteration=i, n_active=4, admitted=0, seeds=0, chunks=0,
                 inserts=0, cow_copies=0, syncs=decode, decode=decode)
    attrs.update(counts)
    return Span(1000 * i, "sched.step", t0, t1, None, None, attrs)


def _wait(step, t0, t1, name="sched.decode.wait", k=1):
    tick = Span(step.id + k, "sched.decode_tick", t0, t1, step.id, None, None)
    return [tick, Span(step.id + k + 1, name, t0, t1, tick.id, None, None)]


@pytest.fixture
def recorded(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(telemetry, "recorder", lambda: rec)
    return rec


def _fill(rec):
    s0 = _step(0, 90.0, 90.5)                         # before the window
    s1 = _step(1, 100.0, 100.010)                     # 10 ms, 4 ms waiting
    s2 = _step(2, 100.010, 100.030, chunks=1, inserts=1, syncs=2)
    s3 = _step(3, 100.030, 100.031, decode=0, chunks=1)   # no decode
    s4 = _step(4, 100.031, 100.039, seeds=1)
    s5 = _step(5, 199.990, 200.050)                   # starts in the window
    s6 = _step(6, 200.0, 200.5)                       # after it
    recs = [s0, s1, s2, s3, s4, s5, s6]
    recs += _wait(s1, 100.004, 100.008)
    recs += _wait(s2, 100.012, 100.020)
    recs += _wait(s2, 100.025, 100.027, "engine.insert.wait", k=3)
    recs += _wait(s5, 200.000, 200.040)               # 40 of its 60 ms
    rec._ring.extend(recs)


def test_host_self_ms_is_the_mean_decode_iteration_less_its_waits(recorded):
    _fill(recorded)
    # s1: 10 - 4; s2: 20 - 8 - 2; s4: 8; s5: 60 - 40 (s3 dispatched no
    # decode step; s0 and s6 start outside the window)
    want = (6.0 + 10.0 + 8.0 + 20.0) / 4
    assert program_spans.host_self_ms(_run()) == pytest.approx(want)
    assert spec.metric_reader("sched.host_self_ms.chat")(_run()) == \
        pytest.approx(want)
    assert spec.metric_reader("sched.host_self_ms.batch")(_run()) == \
        pytest.approx(want)


def test_mixed_iter_share_counts_decode_iterations_with_prefill_work(recorded):
    _fill(recorded)
    # of s1, s2, s4, s5: s2 (chunk + insert) and s4 (seed) carry prefill work
    assert program_spans.mixed_share_pct(_run()) == pytest.approx(50.0)
    assert spec.metric_reader("sched.mixed_iter_share.chat")(_run()) == \
        pytest.approx(50.0)
    recorded.clear()
    recorded._ring.append(_step(1, 100.0, 100.1, cow_copies=1))
    recorded._ring.append(_step(2, 100.1, 100.2))
    assert program_spans.mixed_share_pct(_run()) == pytest.approx(50.0)


def test_readers_give_nothing_without_decode_iterations(recorded):
    assert program_spans.host_self_ms(_run()) is None
    recorded._ring.append(_step(0, 150.0, 150.1, decode=0, chunks=1))
    recorded._ring.append(_step(1, 250.0, 250.1))
    assert program_spans.host_self_ms(_run()) is None
    assert program_spans.mixed_share_pct(_run()) is None


def test_readers_give_nothing_for_a_program_without_a_recorder(monkeypatch):
    """A parent program, which has no ``repro.serve.telemetry``: the
    metric is left out, nothing raises."""
    import repro.serve
    monkeypatch.setitem(sys.modules, "repro.serve.telemetry", None)
    monkeypatch.delattr(repro.serve, "telemetry")
    assert program_spans.host_self_ms(_run()) is None
    assert program_spans.mixed_share_pct(_run()) is None


@pytest.mark.parametrize("name", ["paged_flash_decode", "flash_prefill"])
def test_a_named_kernel_is_still_a_kernel_to_the_reduction(name):
    """``pallas_call(name=...)`` renames the custom call's instruction, not
    its target, so ``op_kind`` and the kernel metrics read it as before."""
    hlo = (f"%{name}.1 = bf16[2,1,4,64]{{3,2,1,0}} custom-call(%copy.1, "
           f'%copy.2), custom_call_target="tpu_custom_call", '
           'backend_config={"custom_call_config": {}}')
    assert trace_mod.op_kind(hlo) == "tpu_custom_call"
