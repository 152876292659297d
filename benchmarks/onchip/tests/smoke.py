"""Cells at the -smoke sizes of the same architectures, for CPU tests:
the real configuration, traffic and cell files with their sizes cut."""
from __future__ import annotations

import copy

from benchmarks.onchip import spec

SMOKE_SIZES = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
               "head_dim": 16, "d_ff": 128, "vocab": 512}


def smoke_cell(name: str, **cell_over):
    cell = spec.load_cell(name)
    cfg = copy.deepcopy(cell.config)
    cfg["arch"] += "-smoke"
    cfg["sizes"] = {k: SMOKE_SIZES.get(k, v) for k, v in cfg["sizes"].items()}
    cfg["serve"].update(slots=4, max_len=64, prefill_chunk=16)
    if cfg["serve"].get("kv_layout") == "paged":
        cfg["serve"]["num_pages"] = 16
    cfg["warm_granule"] = 16
    cell.config = cfg
    t = copy.deepcopy(cell.traffic)
    t["prompt"].update(min=8, max=40, median=20)
    t["output"].update(min=2, max=12, median=6)
    t["max_total"] = 64
    t["ramp_s"] = 0.5
    t["check_requests"] = 4
    t["requests"] = 16
    if t["loop"] == "open":
        t["grace_s"] = 2.0
    cell.traffic = t
    cell.cell = {**copy.deepcopy(cell.cell), **cell_over}
    if "rate_per_s" in cell.cell:
        cell.cell["rate_per_s"] = 6.0
    return cell
