"""Share of the host plan walk spent in the transform
(`get_xf_operations_full` and the seconds inside its lazy iterator's
`next()`): `plan.xf` / `plan.tail`."""
from bench import phases


def read(ctx):
    return phases.share_of(ctx, "plan.xf", "plan.tail")
