"""Share of the window `DocStore.lock` was held by edit handlers
(acquisitions made with `http.edit` or one of its steps open)."""
from bench import phases


def read(ctx):
    return phases.lock_held_share(ctx, phases.EDIT_SITES)
