from benchmark.readers import gib, ok_packs


def read(ctx, stages: list):
    """ntpu_fused_convert_stage_seconds{stage} deltas over the window's packs,
    per GiB of tar packed."""
    packs = ok_packs(ctx)
    return sum(r["stages"][s] for r in packs for s in stages) / gib(packs) if packs else None
