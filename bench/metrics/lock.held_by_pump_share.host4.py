"""Share of the window `DocStore.lock` was held by the flush path: the
acquisitions made under `sched.flush` on the one pump thread, which in
a mesh window plans every due shard's bucket in turn (`bank.resolve`,
`bank.plan`, `adopt`, and the window's and the mesh rung's own
steps)."""
from bench import mesh, phases


def read(ctx):
    return phases.lock_held_share(ctx, mesh.PUMP_SITES)
