"""Encoding one document under the store lock, the wait for the lock
left out: (`autosave.encode` - its lock wait) / documents encoded."""
from bench import phases


def read(ctx):
    s = phases.delta(ctx, "autosave.encode")
    if s is None:
        return None
    s -= phases.delta(ctx, "autosave.encode", "lock_wait_s")
    return phases.ratio(s, phases.delta(ctx, "autosave.pass",
                                        "counts.docs"), 1e3)
