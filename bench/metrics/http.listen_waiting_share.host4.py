"""`host4-mixed.edit-sat128`: share of the samples taken just after an
accept that found a further connection waiting already
(bench/inside.py)."""
from bench.inside import listen_waiting_share as read  # noqa: F401
