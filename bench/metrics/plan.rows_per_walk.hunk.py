"""Plan rows a plan walk with work made in `a2-sources.hunk-sat`
(bench/block.py)."""
from bench.block import rows_per_walk as read  # noqa: F401
