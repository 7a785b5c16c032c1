"""Find every piece of a cell by name, from files of its own.

  cell            chipbench/cells/<cell>.json
  configuration   chipbench/configs/<config>.json
  traffic mix     chipbench/traffic/<mix>.json
  generator kind  chipbench/generators/<kind>.py   (named by the mix)
  metric          chipbench/metrics/<metric>.py    (a `read(ctx)` function)

BENCHMARK.json, at the root of the repository, says which end-to-end and
per-layer metrics each cell reports. A new cell, configuration, mix,
generator or metric is a new file and a new entry; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def _module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cells(base: Path = BENCH) -> list:
    return sorted(p.stem for p in (base / "cells").glob("*.json"))


def cell(name: str, base: Path = BENCH) -> dict:
    """{"name", "cell", "arch", "mix", "generator"} of cell `name`."""
    c = _json(base / "cells" / f"{name}.json")
    mix = _json(base / "traffic" / f"{c['traffic']}.json")
    return {"name": name, "cell": c,
            "arch": _json(base / "configs" / f"{c['config']}.json"),
            "mix": mix,
            "generator": _module(base / "generators"
                                 / f"{mix['generator']}.py")}


def benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def metric_names(bench: dict, workload: str, group: str) -> list:
    """Names of the `group` ("end_to_end" or "per_layer") metrics that
    `workload` reports."""
    return [m["name"] for m in bench[group]
            if workload in m.get("workloads", [workload])]


def metric(name: str, base: Path = BENCH):
    return _module(base / "metrics" / f"{name}.py")
