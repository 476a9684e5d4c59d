"""Mean milliseconds of a flush window: the `sched.flush` root of
`_flush_window` (plan every due shard's bucket, one dispatch a class,
adopt), its waits for `DocStore.lock` included."""
from bench import mesh, phases


def read(ctx):
    mesh.keep(ctx)
    return phases.mean_ms(ctx, mesh.WINDOW)
