"""`host4-mixed.edit-sat128`: `sched.queue_wait_mean_ms` (submit -> the
end of the window that merged the item), the reader of bench/phases.py."""
from bench.phases import queue_wait_mean_ms as read  # noqa: F401
