"""`host4-mixed.edit-sat128`: the merge pump's CPU (the mesh windows run on
it) as a percentage of one core over the traffic's seconds
(bench/inside.py)."""
from bench import inside


def read(ctx):
    return inside.cpu_share(ctx, "pump_s")
