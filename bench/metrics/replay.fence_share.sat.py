"""Share of a fused replay call in `b4-papers.edit-sat` spent waiting
for the device (the fetch of the lengths): `replay.fence` / `replay`.
Higher is better: the rest of a call is host work, done with the
interpreter the edit handlers want."""
from bench import phases


def read(ctx):
    return phases.share_of(ctx, "replay.fence", "replay")
