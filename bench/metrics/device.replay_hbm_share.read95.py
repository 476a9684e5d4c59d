"""`yb-pages.read95`'s share of the HBM roofline the replays reached (its
reads' own flushes and the paced ones): the block-edit cells' reader,
bench/block.py."""
from bench.block import replay_hbm_share as read  # noqa: F401
