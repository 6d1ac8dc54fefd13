"""Which metrics each cell reports, by BENCHMARK.json alone: every cell
reports `setup_s`, another end-to-end metric and a per-layer metric; a
per-layer metric is read only in cells that report the metric it moves; a
quantity split by cells reads through its base name's file.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_cell_metrics.py -q
"""

import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.readers import window_rate  # noqa: E402

BENCH = run.load(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_end_to_end_metric_and_what_moves_it(cell):
    e2e, per_layer = run.reported(BENCH, cell)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    assert per_layer and {m["moves"] for m in per_layer} <= names
    # a per-layer metric without a list is read in every cell that reports what it moves
    for m in BENCH["per_layer"]:
        if "workloads" not in m and m["moves"] in names:
            assert m in per_layer, m["name"]
        if cell in m.get("workloads", []):
            assert m["moves"] in names, m["name"]


def test_the_fan_out_reports_its_rate_per_layer_and_the_serial_cells_end_to_end():
    e2e, per_layer = run.reported(BENCH, "node21-64k.fanout")
    assert [m["name"] for m in e2e] == ["pack_p95_s_per_gib", "setup_s"]
    assert "convert_mib_per_s.fanout" in {m["name"] for m in per_layer}
    for cell in CELLS:
        if cell != "node21-64k.fanout":
            assert "convert_mib_per_s" in {m["name"] for m in run.reported(BENCH, cell)[0]}, cell


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_finds_its_file_and_reader(name):
    spec = run.load(run.HERE, "metrics", run.metric_file(name))
    assert callable(importlib.import_module(f"benchmark.readers.{spec['reader']}").read)
    if name.endswith(".fanout") and name != "convert_mib_per_s.fanout":
        assert run.metric_file(name) == name[: -len(".fanout")] + ".json"


def record(verb, t0, t1, nbytes=0, ok=True, traced=False):
    return {"verb": verb, "t0": t0, "t1": t1, "bytes": nbytes, "ok": ok, "traced": traced}


def test_window_rate_is_the_end_to_end_rate_of_the_window():
    records = [record("pack", 0.0, 2.0, 300 << 20), record("pack", 0.5, 2.5, 200 << 20),
               record("pack", 2.5, 3.0, 50 << 20, ok=False), record("merge", 2.5, 4.0)]
    rate = window_rate.read({"records": records})
    assert rate == pytest.approx(run.end_to_end(records, 1.0)["convert_mib_per_s"]) == pytest.approx(500 / 4.0)
    traced = records + [record("pack", 9.0, 11.0, 300 << 20, traced=True)]
    assert window_rate.read({"records": traced}) == pytest.approx(rate)
    assert window_rate.read({"records": [record("merge", 0.0, 1.0)]}) is None
