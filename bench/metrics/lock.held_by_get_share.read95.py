"""`yb-pages.read95`'s share of the window `DocStore.lock` was held by GETs:
bench/reads.py."""
from bench.reads import get_held_share as read  # noqa: F401
