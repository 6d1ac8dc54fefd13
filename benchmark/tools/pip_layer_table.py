"""The table of a pip layer that a few huge files hold: the one behind
`benchmark/configs/mlimage-1m.json`'s `listed_files` and `file_law`.

    python3 benchmark/tools/pip_layer_table.py jax jaxlib libtpu

`pip_layer_sizes.py` fits ONE log-normal law to a layer, which cannot hold two
shared objects of 614 and 307 MiB beside 2,925 files of median 5 KB. This
lists every file over `--over` bytes (1 MiB) as it is, (bytes, kind, path),
and gives of the rest, the body, what a law needs: the count, the bytes, the
empties, the log-moments, the median and the kind shares. The closure and the
rule for a file's kind are `pip_layer_sizes`' own. Run it in an environment
that holds the pins the configuration's `measured` lists. Prints one JSON
object. Imports nothing of the program or harness.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from pip_layer_sizes import closure, kind_of  # noqa: E402


def files_of(roots: list[str]) -> tuple[list[str], list[tuple[int, str, str]]]:
    """-> (the pins, [(bytes, kind, path under site-packages)]) of every
    file the closure's RECORDs list that is on the disk; an empty file has
    the kind ""."""
    dists, out = closure(roots), []
    for d in dists:
        for f in d.files or []:
            path = os.path.normpath(str(f.locate()))
            if os.path.isfile(path) and not os.path.islink(path):
                size = os.path.getsize(path)
                with open(path, "rb") as fh:
                    out.append((size, kind_of(fh.read()) if size else "", str(f)))
    return [f"{d.metadata['Name']}=={d.version}" for d in dists], out


def table(roots: list[str], over: int = 1 << 20, cut_over: int = 1 << 18) -> dict:
    pins, files = files_of(roots)
    total = sum(size for size, _k, _p in files)
    listed = sorted((f for f in files if f[0] > over), reverse=True)
    body = sorted(size for size, _k, _p in files if 0 < size <= over)
    kinds = [k for size, k, _p in files if 0 < size <= over]
    logs = [math.log(s) for s in body]
    return {
        "requirements": roots,
        "python": "%d.%d" % sys.version_info[:2],
        "distributions": pins,
        "files": len(files),
        "bytes": total,
        "empty_files": sum(size == 0 for size, _k, _p in files),
        "listed_over_bytes": over,
        "listed_files": [{"bytes": size, "kind": kind, "path": path} for size, kind, path in listed],
        "listed_bytes": sum(size for size, _k, _p in listed),
        "listed_share_of_bytes": round(sum(size for size, _k, _p in listed) / total, 4),
        "body": {
            "files": len(body),
            "bytes": sum(body),
            "median_bytes": body[len(body) // 2],
            "largest_file_bytes": body[-1],
            "log_mean": round(statistics.mean(logs), 3),
            "log_stdev": round(statistics.pstdev(logs), 3),
            "kind_share_by_file": {k: round(kinds.count(k) / len(kinds), 4) for k in ("text", "binary", "random")},
        },
        f"files_over_{cut_over}": sum(size > cut_over for size, _k, _p in files),
        f"bytes_over_{cut_over}_share": round(sum(size for size, _k, _p in files if size > cut_over) / total, 4),
    }


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("requirements", nargs="+")
    ap.add_argument("--over", type=int, default=1 << 20, help="list every file larger than this as it is")
    args = ap.parse_args()
    print(json.dumps(table(args.requirements, args.over), indent=1))
