def read(ctx, verb: str):
    """Mean wall of the window's completed verbs of one kind, host clock."""
    walls = [r["t1"] - r["t0"] for r in ctx["records"] if r["verb"] == verb and r["ok"]]
    return sum(walls) / len(walls) if walls else None
