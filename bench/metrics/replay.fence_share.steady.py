"""Share of a fused replay call spent waiting for the device (the
fetch of the lengths): `replay.fence` / `replay`. Higher is better:
the rest is host work."""
from bench import phases


def read(ctx):
    return phases.share_of(ctx, "replay.fence", "replay")
