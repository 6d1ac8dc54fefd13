"""The `mlimage-1m` configuration's layer and the traffic kind that draws it
(`traffic/convert_loop_listed.py`): the files over 1 MiB as they were measured
(`measured` in the configuration's file: what `pip install jax jaxlib libtpu`
wrote on python 3.12, by `tools/pip_layer_table.py`), the rest by the body's
law, the same member set for every `--seed`, and the same layer in small when
`image_mib` is cut, as the CPU rehearsal cuts it. Nothing of its pack differs
from `node21-1m`'s. In a file of its own: a PR that is not a `benchmark` PR
adds files, edits none."""

import copy
import os
import sys
import tarfile

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.traffic import convert_loop_listed  # noqa: E402

CONFIG = run.load(run.HERE, "configs", "mlimage-1m.json")
CELL = run.load(run.HERE, "traffic", "mixes", "fresh-listed.json")
MEASURED = CONFIG["measured"]
BODY = MEASURED["body"]
LISTED = [f["bytes"] for f in CONFIG["listed_files"]]


def test_the_configuration_is_the_measured_table():
    assert CONFIG["listed_files"] == [{"bytes": f["bytes"], "kind": f["kind"]} for f in MEASURED["listed_files"]]
    assert len(LISTED) == 23 and LISTED == sorted(LISTED, reverse=True) and min(LISTED) > MEASURED["listed_over_bytes"]
    assert LISTED[:2] == [643724408, 322254936] and sum(LISTED[:2]) / MEASURED["bytes"] == pytest.approx(0.795, abs=0.001)
    assert sum(LISTED) == MEASURED["listed_bytes"] == MEASURED["bytes"] - BODY["bytes"]
    assert MEASURED["files"] == len(LISTED) + BODY["files"] + MEASURED["empty_files"] == 3018
    law = CONFIG["file_law"]
    assert law["lognormal_mu"] == pytest.approx(BODY["log_mean"], abs=0.0005)
    assert law["lognormal_sigma"] == pytest.approx(BODY["log_stdev"], abs=0.0005)
    assert all(law["mix"][k] == pytest.approx(v, abs=0.001) for k, v in BODY["kind_share_by_file"].items())
    assert sum(law["mix"].values()) == pytest.approx(1.0)
    assert BODY["largest_file_bytes"] <= law["max_bytes"] == MEASURED["listed_over_bytes"]
    assert CONFIG["image_mib"] == round(MEASURED["bytes"] / 2**20) == 1158
    assert len(MEASURED["distributions"]) == 7
    assert {"jax==0.9.0", "jaxlib==0.9.0", "libtpu==0.0.34"} <= set(MEASURED["distributions"])
    assert all(f"{n:,}" in CONFIG["source"] for n in (MEASURED["files"], CONFIG["image_mib"]))


def test_it_shares_every_pack_argument_and_guarantee_with_node21_1m():
    sibling = run.load(run.HERE, "configs", "node21-1m.json")
    assert CONFIG["pack_args"] == sibling["pack_args"] and CONFIG["chunk_size"] == sibling["chunk_size"] == 1 << 20
    assert CONFIG["guarantees"] == sibling["guarantees"]
    bench = run.load(ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "mlimage-1m")
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == list(CONFIG["reduced"]) == ["layers"]
    assert entry["file"] == "benchmark/configs/mlimage-1m.json"
    (cell,) = [w for w in bench["workloads"] if w["config"] == "mlimage-1m"]
    assert (cell["name"], cell["traffic"], cell["chips"]) == ("mlimage-1m.fresh", "fresh-listed", 1)
    fresh = run.load(run.HERE, "traffic", "mixes", "fresh.json")
    assert CELL["kind"] == "convert_loop_listed"
    assert {k: v for k, v in CELL.items() if k not in ("kind", "what")} == \
        {k: v for k, v in fresh.items() if k not in ("kind", "what")}


def test_the_layer_is_the_listed_files_as_they_stand_and_the_bodys_draw():
    members = convert_loop_listed.layer_members(CONFIG)
    listed, body = members[:len(LISTED)], members[len(LISTED):]
    assert [m.size for m in listed] == LISTED  # the factor is exactly 1 at the committed size
    assert [m.kind for m in listed] == [f["kind"] for f in CONFIG["listed_files"]]
    sizes = np.array([m.size for m in body])
    assert sum(m.size for m in members) == MEASURED["bytes"] and sizes.sum() == BODY["bytes"]
    assert len(body) == pytest.approx(BODY["files"], rel=0.10)
    assert np.median(sizes) == pytest.approx(BODY["median_bytes"], rel=0.10)
    assert CONFIG["file_law"]["min_bytes"] <= sizes.min() and sizes.max() <= CONFIG["file_law"]["max_bytes"]
    kinds = np.array([m.kind for m in body])
    assert all(np.mean(kinds == k) == pytest.approx(v, abs=0.02) for k, v in BODY["kind_share_by_file"].items())
    assert len({m.name for m in members}) == len(members)
    # what CDC cuts at 1 MiB chunks (files over min_size, 256 KiB) holds nearly all the bytes, in a few dozen files
    cut = [m.size for m in members if m.size > CONFIG["chunk_size"] // 4]
    assert sum(cut) / MEASURED["bytes"] == pytest.approx(MEASURED["bytes_over_262144_share"], abs=0.01)
    assert len(cut) == pytest.approx(MEASURED["files_over_262144"], rel=0.25)


def small(image_mib: int) -> dict:
    config = copy.deepcopy(CONFIG)
    config["image_mib"] = image_mib  # as benchmark/tests/test_rehearsal.py patches it
    return config


@pytest.mark.parametrize("image_mib", [6, 64])
def test_a_smaller_image_is_the_same_layer_in_proportion(image_mib):
    members = convert_loop_listed.layer_members(small(image_mib))
    sizes = [m.size for m in members[:len(LISTED)]]
    factor = image_mib / CONFIG["image_mib"]
    assert sizes == [int(s * factor) for s in LISTED]
    assert sizes == sorted(sizes, reverse=True) and min(sizes) > 0
    assert sum(sizes[:2]) / sum(m.size for m in members) == pytest.approx(0.795, abs=0.002)
    assert sum(m.size for m in members) == round(MEASURED["bytes"] * factor)


def generated(tmp_path, seed: int):
    loop = convert_loop_listed.build(CELL, small(6), seed, str(tmp_path), lambda *_a, **_k: None)
    loop.generate()
    return loop


def test_every_seed_packs_the_same_member_set_in_another_order(tmp_path):
    loops = []
    for seed in (3, 3000000019):
        (tmp_path / str(seed)).mkdir()
        loops.append(generated(tmp_path / str(seed), seed))
    a, b = ([(m.name, m.size, m.kind) for m in loop.members[0]] for loop in loops)
    assert sorted(a) == sorted(b) and a != b
    for loop in loops:
        assert len(loop.tars) == 1 and loop.tar_bytes == [os.path.getsize(loop.tars[0])]
        with tarfile.open(loop.tars[0]) as tf:
            assert [(ti.name, ti.size) for ti in tf.getmembers()] == [(m.name, m.size) for m in loop.members[0]]
    # the files CDC cuts have data_seed's bytes under every --seed: one bucket plan a configuration
    with tarfile.open(loops[0].tars[0]) as ta, tarfile.open(loops[1].tars[0]) as tb:
        for name in ("layer0/listed/f0.bin", "layer0/listed/f1.bin"):
            assert ta.extractfile(name).read() == tb.extractfile(name).read()
    assert list(loops[0].verbs("out"))[0][:3] == ("pack", 0, loops[0].tar_bytes[0])
    assert loops[0].files() == ["layer0.nydus", "image.boot"]


@pytest.mark.parametrize("cell,config", [
    ({**CELL, "dictionary": "config"}, CONFIG),
    ({**CELL, "image": {"mib": 512, "layer_weights": [1], "reuse_fraction": 0.5}}, CONFIG),
    (CELL, {**CONFIG, "layers": 2, "layer_weights": [1, 1]}),
])
def test_it_refuses_a_dictionary_a_second_image_or_a_second_layer(tmp_path, cell, config):
    with pytest.raises(SystemExit):
        convert_loop_listed.build(cell, config, 1, str(tmp_path), lambda *_a, **_k: None)
