"""`host4-mixed.edit-sat128`: CPU ms of a handler thread from its birth to
its last line (bench/inside.py)."""
from bench.inside import thread_cpu_ms_a_request as read  # noqa: F401
