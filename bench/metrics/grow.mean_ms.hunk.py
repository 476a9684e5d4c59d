"""Mean milliseconds of a growth in `a2-sources.hunk-sat`: the program's
`bank.grow` phase (bench/block.py)."""
from bench.block import grow_mean_ms as read  # noqa: F401
