"""Committed rows left on another chip than their bank's, counted by
the window coordinator after each mesh replay (`sched.flush`'s own
count). Must read 0 (the fault PR 21 found and repaired: a slice of
the sharded result comes back replicated)."""
from bench import mesh


def read(ctx):
    return mesh.window_count(ctx, "homes_off_bank")
