"""Share of the autosave passes' seconds spent waiting for
`DocStore.lock`: `autosave.pass` lock wait / `autosave.pass`."""
from bench import phases


def read(ctx):
    return phases.ratio(phases.delta(ctx, "autosave.pass", "lock_wait_s"),
                        phases.delta(ctx, "autosave.pass"), 100.0)
