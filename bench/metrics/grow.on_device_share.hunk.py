"""Share of `a2-sources.hunk-sat`'s growths made by the copy program on the
device and not by a rebuild from a host checkout (bench/block.py):
must read 100."""
from bench.block import on_device_share as read  # noqa: F401
