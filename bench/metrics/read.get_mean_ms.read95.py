"""`yb-pages.read95`'s mean of a GET in its handler:
bench/reads.py."""
from bench.reads import get_mean_ms as read  # noqa: F401
