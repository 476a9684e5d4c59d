"""`b4-papers.edit-sat`: share of the traffic's seconds the flush worker
sat out its pause (bench/inside.py)."""
from bench.inside import pause_share as read  # noqa: F401
