"""The edit's checkout at the writer's version (frontier map and
`len(ol.checkout(frontier))`, under the store lock) without the wait
for the lock, mean over the window: `edit.checkout`."""
from bench import phases


def read(ctx):
    return phases.mean_ms(ctx, "edit.checkout", own=True)
