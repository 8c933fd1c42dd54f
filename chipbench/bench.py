"""Finds everything a cell needs by name, so that a new configuration,
traffic mix or per-layer metric is a new file and a new entry, never an
edit:

  BENCHMARK.json                       the cells and metrics
  chipbench/configs/<config>.json      sizes; names its model ``family``
  chipbench/configs/<family>_ref.py    the family's plain reference
  chipbench/configs/<family>_sut.py    builds the system under test
  chipbench/traffic/<traffic>.json     a mix: its ``kind`` and parameters
  chipbench/metrics/<metric>.py        ``read(ctx)`` -> number or None
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"chipbench: no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str, here: pathlib.Path = HERE) -> dict:
    with open(here / "configs" / f"{name}.json") as f:
        return json.load(f)


def load_mix(name: str, here: pathlib.Path = HERE) -> dict:
    from chipbench.traffic import check_mix
    with open(here / "traffic" / f"{name}.json") as f:
        mix = json.load(f)
    check_mix(mix)
    return mix


def family(cfg: dict, part: str, here: pathlib.Path = HERE):
    """The family's ``ref`` (plain reference) or ``sut`` (program) module."""
    fam = cfg["family"]
    return _module(here / "configs" / f"{fam}_{part}.py",
                   f"chipbench_{fam}_{part}")


def metric_reader(name: str, here: pathlib.Path = HERE):
    """``read(ctx)`` of one per-layer metric."""
    mod = _module(here / "metrics" / f"{name}.py",
                  "chipbench_metric_" + name.replace(".", "_")
                  .replace("-", "_"))
    return mod.read


def resolve(workload: str, bench: dict | None = None,
            here: pathlib.Path = HERE) -> dict:
    """One cell with its configuration, mix and metrics, as a plan."""
    bench = load_benchmark() if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]

    def applies(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if applies(m) and m["moves"] in moved]
    return {"cell": cell, "config": load_config(cell["config"], here),
            "mix": load_mix(cell["traffic"], here),
            "end_to_end": e2e, "per_layer": layer, "here": here}
