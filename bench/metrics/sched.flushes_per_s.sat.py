"""`b4-papers.edit-sat`: `sched.flush` roots closed a second of traffic
(bench/inside.py)."""
from bench.inside import flushes_per_s as read  # noqa: F401
