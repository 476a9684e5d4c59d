"""The steady cell's `lock.held_by_autosave_share`: one reader for
both cells, in bench/phases.py."""
from bench.phases import autosave_held_share as read  # noqa: F401
