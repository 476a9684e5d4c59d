"""`yb-pages.read95`'s share of the GETs the device answered:
bench/reads.py."""
from bench.reads import device_share as read  # noqa: F401
