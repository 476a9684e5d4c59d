"""`b4-papers.paste`'s share of the window `DocStore.lock` was held by the
flush path: one reader for both block-edit cells, in bench/block.py."""
from bench.block import pump_held_share as read  # noqa: F401
