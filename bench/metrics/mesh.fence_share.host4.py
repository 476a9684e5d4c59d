"""Share of a mesh replay spent in waiting for the device (the
fetch of the lengths): `mesh.fence` / `mesh.replay`."""
from bench import mesh


def read(ctx):
    return mesh.step_share(ctx, "mesh.fence")
