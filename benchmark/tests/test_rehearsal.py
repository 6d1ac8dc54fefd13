"""CPU rehearsal of every cell through run.py's own functions, at a tiny image.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Outside tests/: tier-1 does not collect it.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402

CELLS = [w["name"] for w in run.load(ROOT, "BENCHMARK.json")["workloads"]]
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    """The cells' files as committed, the images cut to a few MiB."""
    real = run.load

    def load(*parts):
        doc = real(*parts)
        if "image_mib" in doc:
            doc["image_mib"] = 6
        if isinstance(doc.get("image"), dict):
            doc["image"]["mib"] = 3
        if "plain_sample_mib" in doc:
            doc["plain_sample_mib"] = 2
        return doc

    monkeypatch.setattr(run, "load", load)
    monkeypatch.setenv("TMPDIR", str(tmp_path))


def run_cell(capfd, *argv, require_tpu=False):
    rc = run.main(list(argv), require_tpu=require_tpu)
    out = capfd.readouterr().out.strip().splitlines()
    return rc, out


# the traced rehearsal is slow on the CPU backend (its thread pools fill the trace): one serial
# cell (its first pack alone under the profiler) and the fan-out (an image's packs together)
@pytest.mark.parametrize("cell,trace", [(c, 0) for c in CELLS] + [("mlimage-1m.fresh", 1), ("node21-64k.fanout", 1)])
def test_cell_rehearsal(tiny, capfd, cell, trace):
    rc, out = run_cell(capfd, "--workload", cell, "--seed", "3000000019", "--seconds", "6", "--trace", str(trace))
    assert rc == 0
    last = json.loads(out[-1])
    assert list(last) == RESULT_KEYS or list(last) == RESULT_KEYS[:-1] + ["breakdown", "checks"]
    assert last["correct"] is True, last["checks"]
    assert last["failed"] == 0 and last["attempted"] >= 2
    bench = run.load(ROOT, "BENCHMARK.json")
    e2e, per_layer = run.reported(bench, cell)
    if trace:
        assert set(last["metrics"]) <= {m["name"] for m in per_layer}
        # a quantity split by cells (`merge_s_per_image.fanout`) reads under its base name's file
        assert {"merge_s_per_image", "host_outside_lane_s_per_gib", "lane_programs"} <= \
            {n.split(".")[0] for n in last["metrics"]}
    else:
        assert set(last["metrics"]) == {m["name"] for m in e2e}
        assert all(m["value"] > 0 for m in last["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(last["device"])


def test_no_tpu_no_result(tiny, capfd):
    rc, out = run_cell(capfd, "--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0",
                       require_tpu=True)
    assert rc != 0
    assert out == []
