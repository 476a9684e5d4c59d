"""Plan rows a plan walk with work made in `b4-papers.paste`
(bench/block.py)."""
from bench.block import rows_per_walk as read  # noqa: F401
