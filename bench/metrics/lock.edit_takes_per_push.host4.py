"""`host4-mixed.edit-sat128`: acquisitions of `DocStore.lock` a push
makes on the edit path (bench/takes.py)."""
from bench.takes import edit_takes_per_push as read  # noqa: F401
