"""Share of a mesh replay spent in adoption: each row cut from its
shard and sent back to its session's chip, the length fence, the
arena: `mesh.adopt` / `mesh.replay`."""
from bench import mesh


def read(ctx):
    return mesh.step_share(ctx, "mesh.adopt")
