"""Each cell's whole run, rehearsed on the CPU at a tiny size: the same
client, scheduler, engine, trace spans and check as on the chip."""
import json
from pathlib import Path

import pytest

import run as bench_run

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def rehearse(cell, seed, trace=0, control=False, patch=None, seconds=3):
    argv = ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
            "--trace", str(trace), "--rehearse"] + (["--control"] if control else [])
    return bench_run.run(bench_run.parse(argv), patch=patch)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct(cell):
    line = rehearse(cell, 2 ** 31 + 5)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"] == {"platform": "cpu", "rehearsal": True}
    # a CPU run writes no time and no device metric
    assert line["metrics"] == {}
    assert list(line)[-1] == "check"


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_counters_only(cell):
    line = rehearse(cell, 11, trace=1)
    assert line["correct"]
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    src = {m["name"]: m["source"] for m in bm["per_layer"]}
    assert line["metrics"], "no counter was read"
    assert all(src[k] == "program_counter" for k in line["metrics"])
    assert "breakdown" not in line and "busy_s" not in line["device"]
