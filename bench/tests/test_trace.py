"""The reduction from a trace to device busy time, program and kernel time,
and idle gaps by host span."""
from pathlib import Path

import pytest

from harness import trace

DATA = Path(__file__).parent / "data"


def hand_trace():
    """Two TPU planes over a 100 ns window [1000, 1100]:
    plane 0 runs a decode program 1000-1040 (a kernel 1010-1030 in it) and a
    chunk program 1060-1080; plane 1 runs one op 1000-1100.  The host is in
    sched.decode_tick 995-1045 and client.poll 1045-1105."""
    host = [("bench.window", 1000, 1100), ("sched.decode_tick", 995, 1045),
            ("engine.decode_slots", 996, 1041), ("client.poll", 1045, 1105)]
    d0 = {"ops": [("fusion.1", 1000, 1010, "jit_paged_step", False),
                  ("custom-call.2", 1010, 1030, "jit_paged_step", True),
                  ("fusion.3", 1030, 1040, "jit_paged_step", False),
                  ("fusion.4", 1060, 1080, "jit_chunk_fn", False),
                  ("fusion.9", 900, 950, "jit_paged_step", False)],
          "modules": [("jit_paged_step", 1000, 1040), ("jit_chunk_fn", 1060, 1080),
                      ("jit_paged_step", 900, 950)]}
    d1 = {"ops": [("fusion.1", 1000, 1100, "jit_paged_step", False)],
          "modules": [("jit_paged_step", 1000, 1100)]}
    return {"host": host, "devices": {"/device:TPU:0": d0, "/device:TPU:1": d1}}


def test_busy_idle_and_programs():
    red = trace.reduce(hand_trace())
    assert red["window_s"] == pytest.approx(100e-9)
    # plane 0 busy 40 + 20 = 60, plane 1 busy 100: mean 80
    assert red["busy_s"] == pytest.approx(80e-9)
    assert trace.program(red, "paged_step")["count"] == pytest.approx(1.0)
    assert trace.program(red, "paged_step")["seconds"] == pytest.approx(70e-9)
    assert trace.program(red, "chunk_fn")["seconds"] == pytest.approx(10e-9)
    assert trace.kernel(red, "paged_step")["seconds"] == pytest.approx(10e-9)
    assert trace.kernel(red, "chunk_fn") is None


def test_idle_gaps_go_to_the_innermost_host_span():
    red = trace.reduce(hand_trace())
    gaps = dict(red["idle_gaps"])
    # plane 0 idles 1040-1060 (mid 1050: client.poll) and 1080-1100 (poll)
    assert gaps == {"client.poll": pytest.approx(20e-9)}
    assert "bench.window" not in gaps


def test_no_tpu_plane_reads_nothing():
    assert trace.reduce({"host": [("bench.window", 0, 10)], "devices": {}}) is None


def test_recorded_chip_trace(tmp_path):
    """A one-second trace of the chat cell on one TPU v5e, as the profiler
    wrote it (``run.py --seconds 1 --trace 1``, its ``.xplane.pb`` kept and
    gzipped): one TPU plane, the window span, the decode program and its
    kernels, every number inside its bounds."""
    import gzip
    import shutil

    src = DATA / "chat.xplane.pb.gz"
    path = tmp_path / "chat.xplane.pb"
    with gzip.open(src, "rb") as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    tr = trace.extract(str(path))
    assert list(tr["devices"]) == ["/device:TPU:0"]
    names = {n for n, *_ in tr["host"]}
    assert {"bench.window", "sched.decode_tick", "engine.decode_slots"} <= names
    red = trace.reduce(tr)
    assert 0 < red["busy_s"] <= red["window_s"]
    step = trace.program(red, "paged_step")
    kern = trace.kernel(red, "paged_step")
    assert step["count"] >= 1 and 0 < kern["seconds"] < step["seconds"]
    # one paged kernel per layer per decode step (24 layers)
    assert kern["count"] == 24 * step["count"]
    assert red["device_ops"] and red["idle_gaps"]
    assert all(s > 0 for _, s in red["device_ops"] + red["idle_gaps"])
    assert red["device_ops"][0][0].startswith("jit_")
