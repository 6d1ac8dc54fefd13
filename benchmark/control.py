"""The control: the reference put in the program's place with one guarantee
of the configuration broken. It has to come out as NOT correct.

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13

This system states no precision, so there is no lower one to compute in; the
steps that would tempt a later PR are a cheaper answer of another kind, and
each mix's file lists them under ``controls``: cuts that are not content-
defined, another chunk size, a dictionary that is not consulted. For every
seed the cell's image is generated at the cell's own size, each control
converts it (host lane, so no compile), and the run's own comparison
(verify.compare) judges its artifacts against the true reference; so it does
the same convert with nothing broken, which has to come out correct. Prints
one line per seed and control; exits 0 only if every control failed a number
and the unbroken convert none. The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import program, run, verify  # noqa: E402


def control_run(loop, work: str, ref_dir: str, ref_lines: dict, control: dict) -> list[dict]:
    """One whole convert by the control, judged as a window's would be."""
    out_dir = os.path.join(work, "control")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    records, extra = [], list(control.get("extra", ()))
    if "chunk_size_factor" in control:  # argparse keeps the last --chunk-size
        extra += ["--chunk-size", hex(loop.config["chunk_size"] * control["chunk_size_factor"])]
    for verb, layer, nbytes, argv in loop.verbs(out_dir, backend=control["backend"], extra=extra,
                                                 use_dict=control.get("use_dict", True)):
        t0 = time.perf_counter()
        records.append({"verb": verb, "layer": layer, "iter": 0, "bytes": nbytes, "t0": t0, "ok": True,
                        "result": program.cli(argv), "t1": time.perf_counter()})
    as_if_device = {"dispatches": loop.fused_packs, "host_fallbacks": 0}  # judged on its answers alone
    return verify.compare(loop, records, [out_dir], ref_dir, ref_lines, as_if_device, lambda *_a, **_k: None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    _bench, _entry, cell, config = run.find_cell(args.workload)
    program.prepare()
    kind = importlib.import_module(f"benchmark.traffic.{cell['kind']}")
    as_expected = True
    for seed in (int(s) for s in args.seeds.split(",")):
        work = tempfile.mkdtemp(prefix="ntpu_control.", dir=run.work_root())
        try:
            loop = kind.build(cell, config, seed, work, lambda *_a, **_k: None)
            loop.generate()
            ref_dir = os.path.join(work, "ref")
            ref_lines = verify.run_reference(loop, ref_dir)
            for control in [{"breaks": None, "backend": "hybrid"}] + cell["controls"]:
                t0 = time.perf_counter()
                checks = control_run(loop, work, ref_dir, ref_lines, control)
                failed = {c["name"]: c["value"] for c in checks if not c["ok"]}
                as_expected &= bool(failed) == bool(control["breaks"])
                print(json.dumps({"workload": args.workload, "seed": seed, "control": control["breaks"],
                                  "correct": not failed, "numbers_failed": failed,
                                  "wall_s": round(time.perf_counter() - t0, 2)}), flush=True)
        finally:
            shutil.rmtree(work, ignore_errors=True)
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())
