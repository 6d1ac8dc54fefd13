"""Record the small trace kept under benchmark/testdata/ (run on the chip):

    python3 benchmark/tools/record_trace.py <out.xplane.pb>

One tiny two-layer image (1 MiB of files <= 2 KiB) is converted once untraced and once
under the profiler, with run.py's own verbs and annotations, and the xplane
file is copied out. benchmark/tests/test_trace_reduce.py pins its reduction.
"""

import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import program, run, trace_reduce  # noqa: E402
from benchmark.traffic import convert_loop  # noqa: E402


def main(out_path: str) -> None:
    # 1 MiB of files of at most 2 KiB: the digest loops run over classes of at most 32
    # blocks, so the trace has thousands of device events and not the real image's millions
    config = run.load(run.HERE, "configs", "node21-64k.json")
    config["image_mib"] = 1
    config["file_law"] = {**config["file_law"], "max_bytes": 2048}
    cell = run.load(run.HERE, "traffic", "mixes", "fresh.json")
    program.prepare()
    work = tempfile.mkdtemp(prefix="ntpu_trace.", dir=run.work_root())
    try:
        loop = convert_loop.build(cell, config, 25, work, lambda *_a, **_k: None)
        loop.generate()
        os.makedirs(os.path.join(work, "warm"))
        run.convert(program, loop, os.path.join(work, "warm"), -1, [])
        records = []
        os.makedirs(os.path.join(work, "out"))
        session = run.profiler_session()
        try:
            run.convert(program, loop, os.path.join(work, "out"), 0, records)
        finally:
            xspace = session.stop()
        with open(out_path, "wb") as f:
            f.write(xspace)
        reduced = trace_reduce.reduce_file(out_path)
        print(json.dumps({"bytes": os.path.getsize(out_path), "verbs": len(records),
                          **{k: reduced.get(k) for k in ("busy_s", "window_s", "structure", "spans")}}))
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
