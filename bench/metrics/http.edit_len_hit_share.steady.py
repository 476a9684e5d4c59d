"""Share of the window's edits that found the document's length at the
writer's version remembered on the oplog (`OpLog.length_at`) and paid
no checkout: 100 * len_hit / (len_hit + len_miss), the `http.edit`
row's own counts. None on a program without the counter."""
from bench import phases


def read(ctx):
    hit = phases.delta(ctx, "http.edit", "counts.len_hit")
    miss = phases.delta(ctx, "http.edit", "counts.len_miss")
    if hit is None or miss is None:
        return None
    return phases.ratio(hit, hit + miss, 100.0)
