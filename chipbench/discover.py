"""Find every piece of a cell by name, from files of its own.

  cell            chipbench/cells/<cell>.json
  configuration   chipbench/configs/<config>.json
  traffic mix     chipbench/traffic/<mix>.json
  generator kind  chipbench/generators/<kind>.py   (named by the mix)
  metric          chipbench/metrics/<metric>.py    (a `read(ctx)` function)
  family          the configuration's "family" key names two files:
    reference     chipbench/reference/<family>.py  (float32 reference, weight
                                                    layout, FLOPs and bytes)
    adapter       chipbench/adapters/<family>.py   (the program's config and
                                                    parameter tree)

BENCHMARK.json, at the root of the repository, says which end-to-end and
per-layer metrics each cell reports. A new cell, configuration, mix,
generator, metric or architecture family is a new file and a new entry; no
file here changes.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path
from typing import NamedTuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def _module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(path)
    name = f"chipbench_{path.parent.name}_{path.stem}"
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
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


class Family(NamedTuple):
    name: str
    reference: object     # reference/<family>.py: hidden, head_stats, shapes,
                          # prompt_flops, decode_flops, sweep_work
    adapter: object       # adapters/<family>.py: model_config, params


def family(arch: dict, base: Path = BENCH) -> Family:
    """The architecture family the configuration `arch` names. Both of its
    files are looked for before either is loaded, so a missing one fails
    at once, naming its path."""
    return _family(arch["family"], base)


@functools.lru_cache(maxsize=None)
def _family(name: str, base: Path) -> Family:
    paths = (base / "reference" / f"{name}.py",
             base / "adapters" / f"{name}.py")
    for path in paths:
        if not path.is_file():
            raise FileNotFoundError(
                f"architecture family {name!r}: no file {path}")
    return Family(name, *map(_module, paths))
