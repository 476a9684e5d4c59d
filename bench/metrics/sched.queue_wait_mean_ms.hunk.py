"""`a2-sources.hunk-sat`'s `sched.queue_wait_mean_ms` (submit -> the end of
the flush): one reader for every cell, in bench/phases.py."""
from bench.phases import queue_wait_mean_ms as read  # noqa: F401
