"""Share of the replayed rows whose session lives on another chip than
the mesh device whose slice replayed them: each crosses the
interconnect on its way back, and on its way in when it was gathered.
Rows are batched by shape class in shard order, not by placement."""
from bench import mesh, phases


def read(ctx):
    return phases.ratio(mesh.count(ctx, "rows_off_home"),
                        mesh.count(ctx, "rows"), 100.0)
