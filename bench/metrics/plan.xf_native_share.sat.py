"""Share of the window's plan walks with work whose transform ran on
the oplog's native mirror (`NativeContext.transform`) and not on the
pure-Python `TransformedOps`: 100 * xf_native / (xf_native +
xf_python), the `plan.tail` row's own counts. None on a program
without the counters."""
from bench import phases


def read(ctx):
    native = phases.delta(ctx, "plan.tail", "counts.xf_native")
    python = phases.delta(ctx, "plan.tail", "counts.xf_python")
    if native is None or python is None:
        return None
    return phases.ratio(native, native + python, 100.0)
