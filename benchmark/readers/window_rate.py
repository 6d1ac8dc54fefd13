from benchmark.run import end_to_end


def read(ctx):
    """``convert_mib_per_s`` as ``run.end_to_end`` takes it, over the window's
    records (the traced verbs after it are left out), for a cell that reports
    it per layer."""
    records = [r for r in ctx["records"] if not r.get("traced")]
    if not any(r["verb"] == "pack" and r["ok"] for r in records):
        return None
    return end_to_end(records, 0.0)["convert_mib_per_s"]
