"""One module per kind of reading. ``read(ctx, **params)`` returns the number,
or None where there is nothing to read (the metric is then left out of the
line). ``ctx``: ``records`` (the window's verbs, each with its wall and its
stage-counter deltas), ``trace`` (trace_reduce.reduce's result, traced runs
only), ``setup`` (counts taken when set-up ended), ``peaks``, ``loop``. The
profiled pack's record has ``traced`` set. A later PR adds a reader as a new file here.
"""


def ok_packs(ctx) -> list:
    return [r for r in ctx["records"] if r["verb"] == "pack" and r["ok"]]


def gib(records) -> float:
    return sum(r["bytes"] for r in records) / 2**30
