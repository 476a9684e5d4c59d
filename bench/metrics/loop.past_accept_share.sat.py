"""`b4-papers.edit-sat`: share of the closed loop's clients that the server
holds past `accept()` at an instant (bench/inside.py)."""
from bench.inside import past_accept_share as read  # noqa: F401
