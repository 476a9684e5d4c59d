"""`yb-pages.read95`'s share of its device reads whose session was at the tip:
bench/reads.py."""
from bench.reads import at_tip_share as read  # noqa: F401
