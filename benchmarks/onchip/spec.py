"""Find a cell's files by name: BENCHMARK.json binds a configuration to a
traffic mix; each of those, the cell's own parameters, every per-layer
metric reader and every kernel cost lives in a file of its own."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import one file by its path (reader, cost and reference files are
    named after metrics and kernels, which may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        "onchip_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<mix>.json
    cell: dict            # cells/<cell>.json
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json",
              base: Path = HERE) -> Cell:
    bench = load_json(bench_path)
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in {bench_path} "
                       f"(have {sorted(wl)})")
    w = wl[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    traffic = load_json(base / "traffic" / f"{w['traffic']}.json")
    cell = load_json(base / "cells" / f"{name}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, cell=cell,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


def metric_reader(name: str, base: Path = HERE):
    """metrics/<name>.py -> its ``read(ctx)`` function."""
    return load_module(base / "metrics" / f"{name}.py", name).read


def kernel_cost(kernel: str, base: Path = HERE):
    """costs/<kernel>.py -> its ``cost(**shapes)`` function."""
    return load_module(base / "costs" / f"{kernel}.py", kernel).cost


def reference(config: str, base: Path = HERE):
    """references/<config>.py -> the module holding ``logits``."""
    return load_module(base / "references" / f"{config}.py", config)


def peaks(device_kind: str, base: Path = HERE) -> dict:
    table = load_json(base / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]
