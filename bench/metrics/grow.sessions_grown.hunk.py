"""Sessions of `a2-sources.hunk-sat` that grew to a larger capacity class on
the device, from the window's opening to the drain's end, where `correct`
compares them with the reference (bench/block.py). The cell stops
testing growth when this reads 0."""
from bench.block import sessions_grown as read  # noqa: F401
