"""`shard_map` dispatches a flush window: `mesh.replay` calls over
`sched.flush` roots. One a capacity class in the window, and one more
for every `shards x flush_docs` rows of a class beyond the first."""
from bench import mesh, phases


def read(ctx):
    return phases.ratio(phases.delta(ctx, mesh.REPLAY, "count"),
                        phases.delta(ctx, mesh.WINDOW, "count"))
