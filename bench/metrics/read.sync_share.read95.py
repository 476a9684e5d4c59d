"""`yb-pages.read95`'s share of its GET seconds spent bringing sessions to the tip:
bench/reads.py."""
from bench.reads import sync_share as read  # noqa: F401
