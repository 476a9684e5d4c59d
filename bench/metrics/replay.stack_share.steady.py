"""Share of a fused replay call spent stacking the sessions' rows
(the two `jnp.stack`): `replay.stack` / `replay`."""
from bench import phases


def read(ctx):
    return phases.share_of(ctx, "replay.stack", "replay")
