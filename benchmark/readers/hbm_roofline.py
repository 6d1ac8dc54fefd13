from benchmark import roofline
from benchmark.readers import ok_packs


def read(ctx):
    """Least seconds the chip's HBM needs for the traced packs' bytes, over
    the seconds the device was busy in them, in %. Nothing without a trace."""
    trace = ctx["trace"]
    traced = [r for r in ok_packs(ctx) if r.get("traced")]
    if not trace or not trace.get("busy_s") or not traced or not ctx["peaks"]:
        return None
    least = roofline.least_seconds(sum(r["bytes"] for r in traced), ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / trace["busy_s"]
