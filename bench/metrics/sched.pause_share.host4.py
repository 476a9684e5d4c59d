"""`host4-mixed.edit-sat128`: share of the traffic's seconds the merge
pump sat out after its paced flush windows (bench/inside.py)."""
from bench.inside import pause_share as read  # noqa: F401
