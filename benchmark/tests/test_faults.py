"""`correct` has to come out false: for each mix's controls, and for a run
whose timed path is broken underneath (an answer altered where it is
produced; a pack that quietly runs on the host lanes)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import control  # noqa: E402
from benchmark.tests.test_rehearsal import CELLS, run_cell, tiny  # noqa: E402,F401


# at this 3 MiB size no file of node21-1m.dict is long enough for fixed and content-defined cuts to differ
# at 1 MiB chunks: test_dict_1m_controls.py holds that cell's controls to "not correct" at 48 MiB
@pytest.mark.parametrize("cell", [pytest.param(c, marks=pytest.mark.xfail(strict=True)) if c == "node21-1m.dict" else c
                                  for c in CELLS])
def test_controls_are_not_correct(tiny, capfd, cell):  # noqa: F811
    rc = control.main(["--workload", cell, "--seeds", "5,6,7"])
    lines = [json.loads(l) for l in capfd.readouterr().out.strip().splitlines()]
    assert rc == 0 and len(lines) == 9
    assert all(l["correct"] for l in lines if l["control"] is None)
    lines = [l for l in lines if l["control"] is not None]
    assert len(lines) == 6 and all(not l["correct"] and l["numbers_failed"] for l in lines)
    # each is failed by a number the plain reference decides, not by the second witness alone
    # (a 6 MiB image may hold no file long enough for 1 MiB and 2 MiB cuts to differ)
    independent = {"plain_files_differ", "stored_chunks_differ", "dedup_differ"}
    judged = [l for l in lines if (cell, l["control"]) != ("node21-1m.fresh", "the stated chunk size")]
    assert all(independent & set(l["numbers_failed"]) for l in judged), lines


def _alter_digest(monkeypatch):
    from nydus_snapshotter_tpu.ops import fused_convert

    real = fused_convert.FusedDeviceEngine.process_many

    def altered(self, streams, *a, **kw):
        res = real(self, streams, *a, **kw)
        i = max(range(len(res.digests)), key=lambda j: len(res.digests[j]))
        d = bytearray(res.digests[i][-1])
        d[0] ^= 1  # one bit of one chunk's digest
        res.digests[i][-1] = bytes(d)
        return res

    monkeypatch.setattr(fused_convert.FusedDeviceEngine, "process_many", altered)


def _alter_cut(monkeypatch):
    from nydus_snapshotter_tpu.ops import fused_convert

    real = fused_convert.FusedDeviceEngine.resolve

    def altered(self, cand_s, cand_l, table):
        cuts = real(self, cand_s, cand_l, table)
        i = max(range(len(cuts)), key=lambda j: len(cuts[j]))
        cuts[i][0] -= 1  # the largest file's first chunk ends one byte early
        return cuts

    monkeypatch.setattr(fused_convert.FusedDeviceEngine, "resolve", altered)


def _host_fallback(monkeypatch):
    from nydus_snapshotter_tpu.ops import fused_convert

    def overflow(self, streams, *a, **kw):
        raise fused_convert.FusedOverflow("planted")

    monkeypatch.setattr(fused_convert.FusedDeviceEngine, "process_many", overflow)


def _dictionary_half_deaf(monkeypatch):
    """Beneath both lanes (they share the dedup): the second witness agrees
    with the program, only the plain reference's digest sets can tell."""
    from nydus_snapshotter_tpu.models import bootstrap

    real = bootstrap.ChunkDict.get
    monkeypatch.setattr(bootstrap.ChunkDict, "get", lambda self, d: None if d[0] & 1 else real(self, d))


def test_dedup_is_judged_by_the_plain_reference(tiny, capfd, monkeypatch):  # noqa: F811
    _dictionary_half_deaf(monkeypatch)
    rc, out = run_cell(capfd, "--workload", "node21-64k.dict", "--seed", "78", "--seconds", "3", "--trace", "0")
    last = json.loads(out[-1])
    assert rc == 0 and last["correct"] is False
    checks = last["checks"]
    assert checks["dedup_differ"]["value"] > 0 and checks["dictionary_hits_expected"]["value"] > 0
    assert checks["artifacts_differ"]["value"] == 0 and checks["dict_hits_differ"]["value"] == 0


@pytest.mark.parametrize("cell", ["node21-64k.dict", "node21-64k.fanout"])  # verb after verb; an image's packs at once
@pytest.mark.parametrize("fault,fails", [
    (_alter_digest, {"artifacts_differ", "plain_files_differ"}),
    (_alter_cut, {"artifacts_differ", "plain_files_differ"}),
    (_host_fallback, {"host_fallbacks", "dispatch_gap", "dispatched_bytes_gap"}),
])
def test_broken_timed_path_is_not_correct(tiny, capfd, monkeypatch, fault, fails, cell):  # noqa: F811
    fault(monkeypatch)
    rc, out = run_cell(capfd, "--workload", cell, "--seed", "77", "--seconds", "3", "--trace", "0")
    last = json.loads(out[-1])
    assert rc == 0 and last["correct"] is False
    not_met = {n for n, c in last["checks"].items()
               if (c["value"] > c["limit"] if c["rule"] == "<=" else c["value"] < c["limit"])}
    assert fails <= not_met, last["checks"]
