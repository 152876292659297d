"""Every file a cell needs is found by name; unknown names are errors."""
import json

import pytest

from benchmarks.onchip import spec


def _bench():
    return json.loads((spec.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [m["name"] for m in _bench()["per_layer"]])
def test_metric_file_found_by_name(name):
    assert callable(spec.metric_reader(name))


def test_unknown_metric_is_an_error():
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric")


@pytest.mark.parametrize("name", [w["name"] for w in _bench()["workloads"]])
def test_cell_files_found_by_name(name):
    cell = spec.load_cell(name)
    assert cell.config["name"] == name.split(".")[0]
    assert cell.traffic["loop"] in ("open", "closed")
    assert "max_logit_gap" in cell.cell["limits"]
    assert callable(spec.reference(cell.config["name"]).logits)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer


def test_peaks_by_device_kind():
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("cpu")
