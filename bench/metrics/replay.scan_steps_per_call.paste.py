"""Scan steps a `fused_replay` call was padded to in `b4-papers.paste`
(bench/block.py)."""
from bench.block import scan_steps_per_call as read  # noqa: F401
