"""`host4-mixed.edit-sat128`'s share of edits the lean HTTP parser took: one reader for every cell, in bench/front.py."""
from bench.front import lean_share as read  # noqa: F401
