"""Share of a mesh replay spent in staging: the arena's hand-back,
or the chip-to-chip gather of the sessions' rows: `mesh.stage` / `mesh.replay`."""
from bench import mesh


def read(ctx):
    return mesh.step_share(ctx, "mesh.stage")
