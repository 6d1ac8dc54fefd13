from benchmark.readers import gib, ok_packs


def read(ctx):
    """Pack wall the lane's five stage counters do not account for: file read,
    tar scan, dedup, compress, blob write, bootstrap. Per GiB of tar packed."""
    packs = ok_packs(ctx)
    if not packs:
        return None
    wall = sum(r["t1"] - r["t0"] for r in packs)
    return (wall - sum(sum(r["stages"].values()) for r in packs)) / gib(packs)
