"""`node21-1m.dict`'s controls have to come out NOT correct, at a size where
they can: `test_faults.test_controls_are_not_correct` cuts every image to
3 MiB, and there no file is long enough for fixed-size and content-defined
cuts to differ at 1 MiB chunks (`min_size` 256 KiB), so the control "cuts are
content-defined" reads correct and that case fails for this cell. Here the
images are 48 MiB: files over 1 MiB among them. In a file of its own: a PR
that is not a `benchmark` PR adds files, edits none."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import control, run  # noqa: E402

MIB = 48


@pytest.fixture
def small(monkeypatch, tmp_path):
    """The cell's files as committed, the images cut to 48 MiB."""
    real = run.load

    def load(*parts):
        doc = real(*parts)
        if "image_mib" in doc:
            doc["image_mib"] = MIB
        if isinstance(doc.get("image"), dict):
            doc["image"]["mib"] = MIB
        if "plain_sample_mib" in doc:
            doc["plain_sample_mib"] = MIB
        return doc

    monkeypatch.setattr(run, "load", load)
    monkeypatch.setenv("TMPDIR", str(tmp_path))


def test_both_controls_are_not_correct_at_1_mib_chunks(small, capfd):
    rc = control.main(["--workload", "node21-1m.dict", "--seeds", "5,6"])
    lines = [json.loads(l) for l in capfd.readouterr().out.strip().splitlines()]
    assert rc == 0 and len(lines) == 6
    assert all(l["correct"] for l in lines if l["control"] is None)
    broken = [l for l in lines if l["control"] is not None]
    assert {l["control"] for l in broken} == {"a chunk the dictionary holds is not stored again",
                                              "cuts are content-defined"}
    assert len(broken) == 4 and all(not l["correct"] for l in broken)
    # each by a number the plain reference decides, not by the second witness alone
    independent = {"plain_files_differ", "stored_chunks_differ", "dedup_differ"}
    assert all(independent & set(l["numbers_failed"]) for l in broken), broken
