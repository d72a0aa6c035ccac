"""Find a cell's parts by name.

``BENCHMARK.json`` at the root of the checkout names each cell's
configuration and traffic mix; every part lives in a file of its own under
``bench/`` and is found by that name, so a new cell, mix, configuration or
per-layer metric is added as files and entries alone:

- configuration  ``bench/configs/<config>.json`` (its ``file`` entry); its
  plain reference is ``bench/configs/<reference>.py``, the file's
  ``reference`` key or else its ``family``, and its work counts come from
  ``bench/costs/<costs>.py`` where the file has a ``costs`` key, else from
  ``harness/costs.py`` (the dense ``lm`` block);
- traffic mix    ``bench/traffic/<traffic>.json``;
- metric reader  ``bench/metrics/<metric>.py`` with ``read(run)``, or, for
  a quantity split by the end-to-end metric it moves
  (``step_mfu.chat``, ``step_mfu.batch``), the one reader
  ``bench/metrics/<metric less its last dotted part>.py``;
- peaks          ``bench/peaks.json``, keyed by ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class SpecError(RuntimeError):
    """The benchmark's files do not describe the cell that was asked for."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {ROOT}")
    return load_json(path)


def workload(bm: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                    f"{[w['name'] for w in bm['workloads']]}")


def config(bm: Dict[str, Any], name: str) -> Dict[str, Any]:
    for c in bm["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise SpecError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str) -> Dict[str, Any]:
    path = BENCH / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no traffic mix file {path}")
    return load_json(path)


def peaks(kind: str) -> Dict[str, Any]:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise SpecError(f"device kind {kind!r} is not in bench/peaks.json "
                        f"(have {sorted(table)}): no peaks, no rooflines")
    return table[kind]


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(conf: Dict[str, Any]):
    """The plain reference module of a configuration."""
    name = conf.get("reference", conf["family"])
    path = BENCH / "configs" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no plain reference {path} for configuration "
                        f"{conf['name']!r}")
    return _load_module(path, f"bench_reference_{name}")


def costs(conf: Dict[str, Any]):
    """The module that counts a configuration's work: its file's
    ``costs``, or the dense ``lm`` block's."""
    from harness import costs as dense
    name = conf.get("costs")
    if name is None:
        return dense
    path = BENCH / "costs" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no cost module {path} for configuration "
                        f"{conf['name']!r}")
    mod = _load_module(path, f"bench_costs_{name}")
    missing = [f for f in dense.MODULE if not callable(getattr(mod, f, None))]
    if missing:
        raise SpecError(f"cost module {path} lacks {missing}")
    return mod


def metric_reader(name: str) -> Callable:
    for stem in (name, name.rpartition(".")[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if stem and path.is_file():
            return _load_module(path, "bench_metric_" + stem.replace(".", "_")).read
    raise SpecError(f"no reader bench/metrics/{name}.py for per-layer metric "
                    f"{name!r}")


def metrics_for(bm: Dict[str, Any], cell: str, trace: bool
                ) -> List[Dict[str, Any]]:
    """The metric entries a run of ``cell`` reports: its end-to-end ones
    without a trace, its per-layer ones with it.  An entry without a
    ``workloads`` key belongs to every cell that reports what it moves."""
    e2e = [m for m in bm["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]

