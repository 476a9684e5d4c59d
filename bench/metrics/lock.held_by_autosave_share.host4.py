"""The four-chip cell's `lock.held_by_autosave_share`: one reader for
every cell, in bench/phases.py."""
from bench.phases import autosave_held_share as read  # noqa: F401
