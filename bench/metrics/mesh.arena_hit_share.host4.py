"""Share of the dispatches (a class in a window) whose state was the
last window's donated output handed straight back: the same session
list recurred in the class. The others gathered their rows."""
from bench import mesh, phases


def read(ctx):
    hits = mesh.count(ctx, "arena_hits")
    misses = mesh.count(ctx, "arena_misses")
    if hits is None or misses is None:
        return None
    return phases.ratio(hits, hits + misses, 100.0)
