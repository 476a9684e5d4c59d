"""`host4-mixed.edit-sat128`: mean ms a thread that becomes runnable waits
for the interpreter (bench/inside.py)."""
from bench.inside import gil_wait_mean_ms as read  # noqa: F401
