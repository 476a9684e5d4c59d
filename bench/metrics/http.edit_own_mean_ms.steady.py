"""An edit handler without its waits for clocked locks, mean over the
window: (`http.edit` seconds - its lock wait) / edits."""
from bench import phases


def read(ctx):
    return phases.mean_ms(ctx, "http.edit", own=True)
