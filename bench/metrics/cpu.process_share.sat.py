"""`b4-papers.edit-sat`: the whole process's CPU as a percentage of one
core over the traffic's seconds (bench/inside.py)."""
from bench import inside


def read(ctx):
    return inside.cpu_share(ctx, "process_s")
