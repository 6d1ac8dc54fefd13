"""The files that `pip install <requirements>` wrote, measured where they lie:
the table behind `benchmark/configs/smallfiles-64k.json`'s `file_law`.

    python3 benchmark/tools/pip_layer_sizes.py jupyterlab

Run it in an environment that holds the requirement (python:3.12 +
`pip install jupyterlab==4.6.2` gives the configuration's `measured`, to the
byte where pip resolved the same pins). The layer is the requirement's
dependency closure by the installed `Requires-Dist` lines (markers judged for
this interpreter, no extras) and, of each distribution, every file its
`RECORD` lists that is on the disk: modules, the `.pyc` pip compiled, scripts,
`share/`. Prints one JSON object. Imports nothing of the program or harness.
"""

from __future__ import annotations

import json
import math
import os
import re
import statistics
import sys
import zlib
from importlib import metadata

from packaging.requirements import Requirement

QUANTILES = (0.1, 0.25, 0.5, 0.75, 0.9, 0.99)
TEXT_BYTES = frozenset(range(32, 127)) | {9, 10, 13}


def _norm(name: str) -> str:
    return re.sub(r"[-_.]+", "-", name).lower()


def closure(roots: list[str]) -> list[metadata.Distribution]:
    dists = {_norm(d.metadata["Name"]): d for d in metadata.distributions()}
    seen, todo = set(), [_norm(r) for r in roots]
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in (dists[name].requires or []) if name in dists else []:
            req = Requirement(line)
            if req.marker is None or req.marker.evaluate({"extra": ""}):
                todo.append(_norm(req.name))
    return [dists[n] for n in sorted(seen) if n in dists]


def kind_of(data: bytes) -> str:
    """As the generator's three kinds: mostly printable ASCII is text, what
    zlib cannot shrink by a tenth is random (compressed assets), the rest
    binary (.pyc, shared objects, catalogs)."""
    if sum(b in TEXT_BYTES for b in data[:65536]) >= 0.95 * min(len(data), 65536):
        return "text"
    return "random" if len(zlib.compress(data, 1)) > 0.9 * len(data) else "binary"


def measure(roots: list[str], over: int = 16384) -> dict:
    dists = closure(roots)
    sizes, kinds = [], {"text": 0, "binary": 0, "random": 0}
    for d in dists:
        for f in d.files or []:
            path = os.path.normpath(str(f.locate()))
            if os.path.isfile(path) and not os.path.islink(path):
                sizes.append(os.path.getsize(path))
                if sizes[-1]:
                    with open(path, "rb") as fh:
                        kinds[kind_of(fh.read())] += 1
    sizes.sort()
    n, total, logs = len(sizes), sum(sizes), [math.log(s) for s in sizes if s]
    return {
        "requirements": roots,
        "python": "%d.%d" % sys.version_info[:2],
        "distributions": [f"{d.metadata['Name']}=={d.version}" for d in dists],
        "files": n,
        "bytes": total,
        "empty_files": n - len(logs),
        "largest_file_bytes": sizes[-1],
        "mean_bytes": round(total / n, 1),
        "quantile_bytes": {str(q): sizes[int(q * n)] for q in QUANTILES},
        "log_mean": round(statistics.mean(logs), 3),
        "log_stdev": round(statistics.pstdev(logs), 3),
        f"files_over_{over}_share": round(sum(s > over for s in sizes) / n, 4),
        f"bytes_over_{over}_share": round(sum(s for s in sizes if s > over) / total, 4),
        "kind_share_by_file": {k: round(v / len(logs), 4) for k, v in kinds.items()},
    }


if __name__ == "__main__":
    print(json.dumps(measure(sys.argv[1:] or ["jupyterlab"]), indent=1))
