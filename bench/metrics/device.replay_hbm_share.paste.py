"""`b4-papers.paste`'s share of the HBM roofline the replays reached: one
reader for both block-edit cells, in bench/block.py."""
from bench.block import replay_hbm_share as read  # noqa: F401
