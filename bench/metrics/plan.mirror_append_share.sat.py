"""Share of the native mirror's syncs, among those the window's plan
walks caused, that followed the oplog by appending and did not build
the mirror whole again: 100 * mirror_appended / (mirror_appended +
mirror_rebuilt), the `plan.tail` row's own counts. None on a program
without the counters."""
from bench import phases


def read(ctx):
    appended = phases.delta(ctx, "plan.tail", "counts.mirror_appended")
    rebuilt = phases.delta(ctx, "plan.tail", "counts.mirror_rebuilt")
    if appended is None or rebuilt is None:
        return None
    return phases.ratio(appended, appended + rebuilt, 100.0)
