"""Replayed documents as a percentage of the rows the mesh programs
were padded to (`pad_batch_count`, then steering), over every capacity
class: the rest are inert padding rows."""
from bench import mesh, phases


def read(ctx):
    return phases.ratio(mesh.total(ctx, "docs"),
                        mesh.total(ctx, "padded_rows"), 100.0)
