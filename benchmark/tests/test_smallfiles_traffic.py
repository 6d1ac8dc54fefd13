"""The `smallfiles-64k` configuration's image: its file-size law is the
measured layer's (`measured` in the configuration's file: what `pip install
jupyterlab==4.6.2` wrote on python:3.12, by `tools/pip_layer_sizes.py`), drawn
at the layer's own size, and nothing of its pack differs from `node21-64k`'s.
In a file of its own: a PR that is not a `benchmark` PR adds files, edits none."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.traffic import image  # noqa: E402

CONFIG = run.load(run.HERE, "configs", "smallfiles-64k.json")


MEASURED = CONFIG["measured"]


def test_the_law_is_the_measured_layers():
    law = CONFIG["file_law"]
    assert law["lognormal_mu"] == pytest.approx(MEASURED["log_mean"], abs=0.005)
    assert law["lognormal_sigma"] == pytest.approx(MEASURED["log_stdev"], abs=0.005)
    assert all(law["mix"][k] == pytest.approx(v, abs=0.002) for k, v in MEASURED["kind_share_by_file"].items())
    assert sum(law["mix"].values()) == pytest.approx(1.0)
    assert law["max_bytes"] >= MEASURED["largest_file_bytes"]
    assert CONFIG["image_mib"] == round(MEASURED["bytes"] / 2**20)
    assert len(MEASURED["distributions"]) == 81 and "jupyterlab==4.6.2" in MEASURED["distributions"]
    assert all(f"{n:,}" in CONFIG["source"] for n in (MEASURED["files"], CONFIG["image_mib"]))


def test_the_law_yields_the_measured_layer():
    shape = image.image_shape(CONFIG["shape_seed"], CONFIG["file_law"], CONFIG["image_mib"] << 20,
                              CONFIG["layer_weights"])
    assert len(shape) == CONFIG["layers"] == 1
    sizes = np.array([m.size for m in shape[0]])
    assert sizes.sum() == CONFIG["image_mib"] << 20
    # what a two-parameter law keeps of the layer: the count within a tenth, the quartiles within a fifth
    assert len(sizes) == pytest.approx(MEASURED["files"], rel=0.10)
    for q in ("0.25", "0.5", "0.75", "0.9"):
        assert np.quantile(sizes, float(q)) == pytest.approx(MEASURED["quantile_bytes"][q], rel=0.20)
    cut = sizes > CONFIG["chunk_size"] // 4  # the files CDC cuts: over min_size, 16 KiB
    assert cut.mean() == pytest.approx(MEASURED["files_over_16384_share"], abs=0.01)
    assert sizes[cut].sum() / sizes.sum() == pytest.approx(MEASURED["bytes_over_16384_share"], abs=0.04)
    assert CONFIG["file_law"]["min_bytes"] <= sizes.min()
    kinds = np.array([m.kind for m in shape[0]])
    assert all(np.mean(kinds == k) == pytest.approx(v, abs=0.01) for k, v in MEASURED["kind_share_by_file"].items())
    # the small-file regime: nearly three times node21's files a GiB
    sibling = run.load(run.HERE, "configs", "node21-64k.json")
    other = image.image_shape(sibling["shape_seed"], sibling["file_law"], CONFIG["image_mib"] << 20, [1])
    assert len(sizes) > 2.5 * len(other[0])


def test_it_shares_every_pack_argument_and_guarantee_with_node21_64k():
    sibling = run.load(run.HERE, "configs", "node21-64k.json")
    assert CONFIG["pack_args"] == sibling["pack_args"] and CONFIG["chunk_size"] == sibling["chunk_size"]
    assert CONFIG["guarantees"] == sibling["guarantees"]
    entry = next(c for c in run.load(ROOT, "BENCHMARK.json")["configs"] if c["name"] == "smallfiles-64k")
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == list(CONFIG["reduced"]) == ["layers"]
    assert entry["file"] == "benchmark/configs/smallfiles-64k.json"
