"""`b4-papers.edit-sat`: `accept()` returning -> the handler thread's first
line, mean ms (bench/inside.py)."""
from bench.inside import thread_start_mean_ms as read  # noqa: F401
