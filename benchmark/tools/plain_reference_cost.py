"""What the plain reference costs the host: peak RSS and wall of
``reference.plain_chunks`` over one configuration's largest file, and the
wall of each arm over a 192 MiB sample of another's files.

    python3 benchmark/tools/plain_reference_cost.py --other <dir holding reference.py>

``--other`` is a second copy of the reference, such as an older commit's
(``git archive <commit> benchmark | tar -x -C <dir>``, then ``<dir>/benchmark``);
its gear CDC + sha256 arm runs beside this one on the same bytes, and the two
must cut and digest them alike. Every step runs in a child process of its own:
the files are made by the image generator from the configurations' seeds
under ``$TMPDIR`` (or the checkout's git-ignored ``.bench_work``) and removed
at the end; a measuring child reads them as ``verify.py`` reads a tar member
(one ``bytes``) and reports its own high-water RSS (``VmHWM``: ``ru_maxrss``
carries over what the process that spawned it held). Needs no accelerator and
imports nothing of the program. Prints one JSON object a measurement, then a
summary; exits 1 where the two references disagree.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import resource
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.traffic import convert_loop_listed, image  # noqa: E402
from benchmark.traffic.convert_loop import SALT_CONFIG_IMAGE  # noqa: E402

SEED = 4200000042
ARMS = [("cdc", "sha256"), ("cdc", "blake3"), ("fixed", "sha256"), ("fixed", "blake3")]


def high_water_mib() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmHWM:"):
                return int(line.split()[1]) / 1024
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024


def largest_listed_file(config: dict, path: str) -> list[int]:
    """The configuration's largest listed file, with the bytes its layer gives it, into ``path`` -> [its size]."""
    members = convert_loop_listed.layer_members(config)
    datas = image.layer_bytes(SEED, config["data_seed"], config["chunk_size"] // 4, SALT_CONFIG_IMAGE, 0, members)
    data = datas[max(range(len(members)), key=lambda i: members[i].size)]
    with open(path, "wb") as f:
        f.write(memoryview(data))
    return [len(data)]


def sample_files(config: dict, path: str, mib: int) -> list[int]:
    """Files of the configuration's first layer, picked as ``verify.plain_checks``
    picks them (the largest, then in an order drawn from the seed) until ``mib``
    MiB, one after another into ``path`` -> their sizes."""
    members = image.image_shape(config["shape_seed"], config["file_law"], config["image_mib"] << 20,
                                config["layer_weights"])[0]
    datas = image.layer_bytes(SEED, config["data_seed"], config["chunk_size"] // 4, SALT_CONFIG_IMAGE, 0, members)
    order = [max(range(len(members)), key=lambda i: members[i].size)]
    order += [int(i) for i in np.random.default_rng([SEED, 0xC0]).permutation(len(members)) if i != order[0]]
    sizes = []
    with open(path, "wb") as f:
        for i in order:
            if sum(sizes) >= mib << 20:
                break
            f.write(memoryview(datas[i]))
            sizes.append(members[i].size)
    return sizes


def cut(ref_dir: str, path: str, sizes: list[int], avg: int, chunking: str, digester: str) -> dict:
    """The reference in ``ref_dir`` over the files in ``path``, timed."""
    spec = importlib.util.spec_from_file_location("reference_under_test", os.path.join(ref_dir, "reference.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    with open(path, "rb") as f:
        blob = f.read()
    before = high_water_mib()
    datas = [np.frombuffer(blob, np.uint8, size, at) for size, at in zip(sizes, np.cumsum([0, *sizes[:-1]]))]
    t0 = time.perf_counter()
    if hasattr(ref, "plain_chunks_many"):
        chunks = ref.plain_chunks_many(datas, avg, chunking, digester)
    else:  # a reference of one arm: gear CDC + sha256, a file at a time
        if (chunking, digester) != ("cdc", "sha256"):
            raise SystemExit(f"{ref_dir}: no arm {chunking} / {digester}")
        chunks = [ref.plain_chunks(data, avg) for data in datas]
    wall = time.perf_counter() - t0
    return {"wall_s": wall, "peak_rss_mib": high_water_mib(), "rss_before_mib": before,
            "result": [[(size, digest.hex()) for size, digest in file] for file in chunks]}


def spawn(job: dict) -> dict:
    """``job`` in a child process -> its result (the child's last line)."""
    out = subprocess.run([sys.executable, __file__, "--job", json.dumps(job)], check=True, capture_output=True,
                         text=True).stdout
    return json.loads(out.strip().splitlines()[-1])


def measure(label: str, ref_dir: str, data: dict, chunking: str, digester: str) -> dict:
    got = spawn({"do": "cut", "ref_dir": ref_dir, "path": data["path"], "sizes": data["sizes"], "avg": data["avg"],
                 "chunking": chunking, "digester": digester})
    line = {"measure": label, "reference": ref_dir, "bytes": sum(data["sizes"]), "files": len(data["sizes"]),
            "avg": data["avg"], "chunking": chunking, "digester": digester,
            **{k: v for k, v in got.items() if k != "result"}, "chunks": sum(len(c) for c in got["result"])}
    print(json.dumps(line), flush=True)
    return {**line, "result": got["result"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--other", help="a directory holding another reference.py (required)")
    ap.add_argument("--large", default="tfimage-1m", help="configuration whose largest listed file is cut")
    ap.add_argument("--sample", default="node21-1m", help="configuration whose files make the sample")
    ap.add_argument("--sample-mib", type=int, default=192)
    ap.add_argument("--job", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    here = os.path.join(ROOT, "benchmark")
    if args.job:
        job = json.loads(args.job)
        if job["do"] == "cut":
            got = cut(job["ref_dir"], job["path"], job["sizes"], job["avg"], job["chunking"], job["digester"])
        else:
            config = run.load(here, "configs", f"{job['config']}.json")
            sizes = (largest_listed_file(config, job["path"]) if job["do"] == "large"
                     else sample_files(config, job["path"], job["mib"]))
            got = {"sizes": sizes, "avg": config["chunk_size"]}
        print(json.dumps(got))
        return 0
    if not args.other:
        ap.error("--other is required")
    work = tempfile.mkdtemp(prefix="plain_reference_cost.", dir=run.work_root())
    try:
        large = {"path": os.path.join(work, "large.bin")}
        large.update(spawn({"do": "large", "config": args.large, "path": large["path"]}))
        pair = [measure("largest_file", ref, large, "cdc", "sha256") for ref in (args.other, here)]
        os.unlink(large["path"])
        sample = {"path": os.path.join(work, "sample.bin")}
        sample.update(spawn({"do": "sample", "config": args.sample, "path": sample["path"], "mib": args.sample_mib}))
        other = measure("sample", args.other, sample, "cdc", "sha256")
        arms = [measure("sample", here, sample, chunking, digester) for chunking, digester in ARMS]
        same = {"largest_file": pair[0]["result"] == pair[1]["result"], "sample": other["result"] == arms[0]["result"]}
        print(json.dumps({
            "same_chunks": same, "largest_file_peak_rss_mib": [m["peak_rss_mib"] for m in pair],
            "largest_file_wall_s": [m["wall_s"] for m in pair],
            "sample_sha256_wall_s": [other["wall_s"], arms[0]["wall_s"]],
            "sample_wall_s_by_arm": {f"{m['chunking']}/{m['digester']}": m["wall_s"] for m in arms}}), flush=True)
        return 0 if all(same.values()) else 1
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
