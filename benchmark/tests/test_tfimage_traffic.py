"""The `tfimage-1m` configuration's layer (what `pip install tensorflow`
wrote on python 3.12, by `tools/pip_layer_table.py`: `measured` in the
configuration's file) and its cell `tfimage-1m.fresh`: the members' law at the
committed size, the two device batches the program makes of it whatever the
seed, and a CPU rehearsal of the cell at a few MiB with the lane's int32 limit
patched small, so that the split engages there too. The traffic kind is
`mlimage-1m`'s (`test_mlimage_traffic.py` holds it). In a file of its own: a
PR that is not a `benchmark` PR adds files, edits none."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import control, run  # noqa: E402
from benchmark.tests.test_rehearsal import run_cell, tiny  # noqa: E402,F401
from benchmark.traffic import convert_loop_listed, image  # noqa: E402
from benchmark.traffic.convert_loop import SALT_CONFIG_IMAGE  # noqa: E402

CONFIG = run.load(run.HERE, "configs", "tfimage-1m.json")
CELL = run.load(run.HERE, "traffic", "mixes", "fresh-listed-wide.json")
MEASURED = CONFIG["measured"]
BODY = MEASURED["body"]
LISTED = [f["bytes"] for f in CONFIG["listed_files"]]
MIB = 1 << 20


def test_the_configuration_is_the_measured_table():
    assert CONFIG["listed_files"] == [{"bytes": f["bytes"], "kind": f["kind"]} for f in MEASURED["listed_files"]]
    assert len(LISTED) == 73 and LISTED == sorted(LISTED, reverse=True) and min(LISTED) > MEASURED["listed_over_bytes"]
    assert LISTED[:2] == [1096372272, 162648808] and LISTED[0] / MEASURED["bytes"] == pytest.approx(0.516, abs=0.001)
    assert MEASURED["listed_files"][0]["path"] == "tensorflow/libtensorflow_cc.so.2"
    assert sum(LISTED) == MEASURED["listed_bytes"] == MEASURED["bytes"] - BODY["bytes"]
    assert MEASURED["files"] == len(LISTED) + BODY["files"] + MEASURED["empty_files"] == 25861
    assert MEASURED["bytes"] == 2123604273 and MEASURED["empty_files"] == 300
    law = CONFIG["file_law"]
    assert law["lognormal_mu"] == pytest.approx(BODY["log_mean"], abs=0.0005)
    assert law["lognormal_sigma"] == pytest.approx(BODY["log_stdev"], abs=0.0005)
    assert all(law["mix"][k] == pytest.approx(v, abs=0.001) for k, v in BODY["kind_share_by_file"].items())
    assert sum(law["mix"].values()) == pytest.approx(1.0)
    assert BODY["largest_file_bytes"] <= law["max_bytes"] == MEASURED["listed_over_bytes"]
    assert CONFIG["image_mib"] == round(MEASURED["bytes"] / 2**20) == 2025
    assert "tensorflow==2.21.0" in MEASURED["distributions"] and len(MEASURED["distributions"]) == 32
    assert all(f"{n:,}" in CONFIG["source"] for n in (MEASURED["files"], CONFIG["image_mib"]))


def test_it_shares_every_pack_argument_and_guarantee_with_mlimage_1m_and_its_mix_but_for_the_sample():
    sibling = run.load(run.HERE, "configs", "mlimage-1m.json")
    assert CONFIG["pack_args"] == sibling["pack_args"] and CONFIG["chunk_size"] == sibling["chunk_size"] == 1 << 20
    assert CONFIG["guarantees"] == sibling["guarantees"]
    bench = run.load(ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "tfimage-1m")
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == list(CONFIG["reduced"]) == ["layers"]
    assert entry["file"] == "benchmark/configs/tfimage-1m.json"
    (cell,) = [w for w in bench["workloads"] if w["config"] == "tfimage-1m"]
    assert (cell["name"], cell["traffic"], cell["chips"]) == ("tfimage-1m.fresh", "fresh-listed-wide", 1)
    assert bench["workloads"][-1] == cell and bench["configs"][-1] == entry
    names = [m["name"] for m in bench["per_layer"]]  # the cell's two metrics, in the order it added them
    assert names.index("lane_buffer_fill_share") == names.index("lane_batches_max") + 1
    narrow = run.load(run.HERE, "traffic", "mixes", "fresh-listed.json")
    assert {k: v for k, v in CELL.items() if k not in ("plain_sample_mib", "what")} == \
        {k: v for k, v in narrow.items() if k not in ("plain_sample_mib", "what")}
    # the plain reference reaches past the one largest file: ~100 MiB of the other batch's files
    assert LISTED[0] + 100 * MIB < CELL["plain_sample_mib"] << 20 < LISTED[0] + LISTED[1]


def tar_layout(members: list) -> tuple[list[tuple[int, int]], int]:
    """[(data offset, size)] of the members in a GNU tar as `image.write_tar`
    writes it (one header block a member: every name is short), and the tar's bytes."""
    pos, table = 0, []
    for m in members:
        assert len(m.name) < 100
        table.append((pos + 512, m.size))
        pos += 512 + -(-m.size // 512) * 512
    return table, -(-(pos + 1024) // 10240) * 10240  # two end blocks, then tarfile's 10 KiB record


@pytest.fixture(scope="module")
def layer():
    return convert_loop_listed.layer_members(CONFIG)


def test_the_layer_is_the_listed_files_as_they_stand_and_the_bodys_draw(layer):
    listed, body = layer[:len(LISTED)], layer[len(LISTED):]
    assert [m.size for m in listed] == LISTED  # the factor is exactly 1 at the committed size
    assert [m.kind for m in listed] == [f["kind"] for f in CONFIG["listed_files"]]
    sizes = np.array([m.size for m in body])
    assert sum(m.size for m in layer) == MEASURED["bytes"] and sizes.sum() == BODY["bytes"]
    assert (len(layer), len(body)) == (30459, 30386)  # the law's draw: +19% on the measured 25,488 (the file's assumed)
    assert np.median(sizes) == pytest.approx(BODY["median_bytes"], rel=0.16)
    assert CONFIG["file_law"]["min_bytes"] <= sizes.min() and sizes.max() <= CONFIG["file_law"]["max_bytes"]
    kinds = np.array([m.kind for m in body])
    assert all(np.mean(kinds == k) == pytest.approx(v, abs=0.02) for k, v in BODY["kind_share_by_file"].items())
    assert len({m.name for m in layer}) == len(layer)
    table, tar_bytes = tar_layout(layer)
    assert tar_bytes == 2147000320 and tar_bytes < 1 << 31 < tar_bytes + (512 << 20)  # 2,047.5 MiB: it pads past int32


@pytest.mark.parametrize("seed", [3, 3600000021])
def test_the_program_makes_the_same_two_batches_of_it_whatever_the_seed(layer, seed):
    """The program's own rule (fused_convert.plan_batches) on the tar of this
    seed's order: everything but the one huge file, then that file alone."""
    from nydus_snapshotter_tpu.ops import cdc, fused_convert

    members, _ = image.shuffled(seed, SALT_CONFIG_IMAGE, 0, layer, list(range(len(layer))))
    table, tar_bytes = tar_layout(members)
    max_size = cdc.CDCParams(CONFIG["chunk_size"]).max_size
    with pytest.raises(fused_convert.FusedOverflow):
        fused_convert.padded_length(tar_bytes, max_size)
    others, big = fused_convert.plan_batches(table, tar_bytes, max_size)
    assert [members[i].size for i in big.files] == [LISTED[0]] and len(big.runs) == 1
    assert len(others.files) == len(layer) - 1 and len(others.runs) == 2  # before and after it in the tar
    assert others.size + big.size == tar_bytes  # every byte of the tar in exactly one batch
    padded = [fused_convert.padded_length(b.size, max_size) for b in (others, big)]
    assert padded == [1024 * MIB, 1280 * MIB]
    assert 100 * tar_bytes / sum(padded) == pytest.approx(88.9, abs=0.1)  # lane_buffer_fill_share


@pytest.fixture
def small_limit(monkeypatch, tiny):  # noqa: F811
    """The lane's int32 limit at 12 MiB: a lane buffer is 8 MiB (4 MiB of
    guard at 1 MiB chunks), the 6 MiB layer pads past it, and its 3.2 MiB
    file is over a quarter of the limit. The plain reference's sample in
    proportion: that file and a MiB of the others."""
    from nydus_snapshotter_tpu import trace
    from nydus_snapshotter_tpu.ops import fused_convert

    monkeypatch.setattr(fused_convert, "ADDRESS_LIMIT", 12 * MIB)
    trace.reset()  # a fresh ring: one that other tests of this process made drop spans reads as no span metric
    tiny_load = run.load

    def load(*parts):
        doc = tiny_load(*parts)
        if "plain_sample_mib" in doc:
            doc["plain_sample_mib"] = 4
        return doc

    monkeypatch.setattr(run, "load", load)


def test_the_cell_in_small_is_correct_in_two_batches(small_limit, capfd):
    rc, out = run_cell(capfd, "--workload", "tfimage-1m.fresh", "--seed", "3600000019", "--seconds", "4", "--trace", "1")
    assert rc == 0
    last = json.loads(out[-1])
    assert last["correct"] is True, last["checks"]
    checks, metrics = last["checks"], {k: v["value"] for k, v in last["metrics"].items()}
    assert metrics["lane_batches_max"] == 2 and 0 < metrics["lane_buffer_fill_share"] < 100
    packs = sum(1 for r in next(json.loads(l) for l in out if '"timeline"' in l)["verbs"] if r[1] == "pack") + 1
    assert checks["dispatch_gap"]["value"] == packs  # two dispatches a pack: the warm-up's, the window's, the traced one
    assert checks["dispatched_bytes_gap"]["value"] == 0 and checks["host_fallbacks"]["value"] == 0
    plain = next(json.loads(l) for l in out if '"plain_reference"' in l)
    assert plain["files"] > 1  # files of both batches were cut and digested by the plain reference


def test_the_controls_are_not_correct(small_limit, capfd):
    rc = control.main(["--workload", "tfimage-1m.fresh", "--seeds", "5"])
    lines = [json.loads(l) for l in capfd.readouterr().out.strip().splitlines()]
    assert rc == 0 and [l["control"] is None for l in lines] == [True, False, False]
    assert lines[0]["correct"] and not lines[1]["correct"] and not lines[2]["correct"]
    assert all("plain_files_differ" in l["numbers_failed"] for l in lines[1:])
