"""`host4-mixed.edit-sat128`: share of the window's connections that
were handed to a parked resident handler thread (bench/pool.py)."""
from bench.pool import pooled_share as read  # noqa: F401
