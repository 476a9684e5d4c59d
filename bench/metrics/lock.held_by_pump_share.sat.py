"""Share of the window `DocStore.lock` was held by the flush path
(resolve, session build and plan walk, adoption: acquisitions made
under `sched.flush`)."""
from bench import phases


def read(ctx):
    return phases.lock_held_share(ctx, phases.PUMP_SITES)
