def read(ctx):
    """1 - union of device-op intervals / wall of the traced pack, in %."""
    trace = ctx["trace"]
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
