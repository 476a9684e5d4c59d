"""`yb-pages.read95`'s mean of the row's fetch:
bench/reads.py."""
from bench.reads import fetch_mean_ms as read  # noqa: F401
