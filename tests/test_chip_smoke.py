"""chip_smoke.py, rehearsed without the chip.

The script itself needs a TPU and fails without one; what can be held
here is (a) its phases, end to end at a tiny size on the CPU backend
through the same importable function ``main`` calls (the Pallas probe in
interpret mode because THIS test asks for it), (b) the verdict line's
exact shape — a PR was thrown away for that line alone — and (c) that on
a CPU-only machine the script exits non-zero and never prints a verdict.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_verdict_line_has_exactly_the_contract_keys():
    line = chip_smoke.verdict_line("tpu", "TPU v5 lite", 1)
    assert "\n" not in line
    d = json.loads(line)
    assert set(d) == {"ok", "device"}
    assert set(d["device"]) == {"platform", "kind", "count"}
    assert d["ok"] is True
    assert d == {"ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert json.loads(chip_smoke.verdict_line("tpu", "TPU v5 lite", 4))["device"]["count"] == 4


def test_one_chip_phases_rehearsed_tiny_on_cpu(tmp_path):
    lines = []
    cfg = chip_smoke.SmokeConfig(
        image_mib=6,
        weights=(3, 2, 1),
        image_b_mib=3,
        weights_b=(2, 1),
        blake3_layers=(2,),
        numpy_layers=(2,),
        default_chunk_layer=1,
        probe_layer_b=0,
        probe_kernels=("pallas-interpret", "xla"),
        require_tpu=False,
    )
    chip_smoke.run_one_chip(
        str(tmp_path), cfg, lambda phase, **facts: lines.append({"phase": phase, **facts})
    )
    phases = [ln["phase"] for ln in lines]
    for phase in ("corpus", "pack", "reference", "merge_check_unpack",
                  "dict_pack", "probe_lane", "summary"):
        assert phase in phases, phase
    counted = next(ln for ln in lines if ln["phase"] == "dispatches")
    # 3 first + 3 steady + blake3 + default-chunk + 2 of B + 2 probe kernels twice
    assert counted["fused_packs"] == counted["ntpu_fused_convert_dispatches"] == 14
    summary = lines[-1]
    assert summary["ntpu_fused_convert_dispatches"] == 14
    assert summary["ntpu_fused_convert_host_fallbacks"] == 0
    assert all(json.dumps(ln, default=str) for ln in lines)  # every line is JSON-able


def test_four_chip_phases_rehearsed_tiny_on_virtual_devices():
    """--chips 4's two phases on four of conftest's virtual CPU devices."""
    lines = []
    cfg = chip_smoke.SmokeConfig(image_mib=4, require_tpu=False)
    chip_smoke.run_four_chips(
        cfg, lambda phase, **facts: lines.append({"phase": phase, **facts}),
        dict_entries=1 << 14, n_queries=1 << 10,
    )
    by_phase = {ln["phase"]: ln for ln in lines}
    assert len(by_phase["sharded_dict"]["table_bytes_per_device"]) == 4
    assert by_phase["sharded_dict"]["matches_host"] is True
    assert len(by_phase["sharded_convert_step"]["corpus_bytes_per_device"]) == 4
    assert by_phase["sharded_convert_step"]["identical"] is True


def test_a_failed_phase_raises(tmp_path, monkeypatch):
    """A mismatch with the reference is a SmokeFailure, never a verdict."""
    real = chip_smoke._sha
    monkeypatch.setattr(
        chip_smoke, "_sha", lambda p: real(p) + ("x" if p.endswith(".hybrid") else "")
    )
    cfg = chip_smoke.SmokeConfig(
        image_mib=2, weights=(1,), image_b_mib=1, weights_b=(1,), blake3_layers=(),
        numpy_layers=(),
        default_chunk_layer=0, probe_layer_b=0, probe_kernels=("xla",),
        require_tpu=False,
    )
    with pytest.raises(chip_smoke.SmokeFailure, match="blob bytes"):
        chip_smoke.run_one_chip(str(tmp_path), cfg, lambda phase, **facts: None)


@pytest.mark.parametrize("alone", [False, True], ids=["in-checkout", "script-alone"])
def test_without_a_tpu_the_script_fails_and_prints_no_verdict(tmp_path, alone):
    cwd = REPO
    if alone:
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr
