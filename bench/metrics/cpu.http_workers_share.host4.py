"""`host4-mixed.edit-sat128`: the resident handler threads' CPU as a
percentage of one core over the traffic's seconds (bench/pool.py)."""
from bench.pool import workers_cpu_share as read  # noqa: F401
