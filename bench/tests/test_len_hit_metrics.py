"""`http.edit_len_hit_share.sat` / `.steady`: the share of a window's
edits whose length at the writer's version the oplog remembered
(`len_hit` / `len_miss` on the `http.edit` row). Appended to the
manifest after the fourteen readers of `test_phase_metrics.py`; None
on a program without the counter, which is every parent of the PR that
added it.

    python -m pytest bench/tests/test_len_hit_metrics.py -q -p no:cacheprovider
"""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.run import metric_reader  # noqa: E402

CELLS = {"http.edit_len_hit_share.sat":
         ("b4-papers.edit-sat", "acked_edits_per_s"),
         "http.edit_len_hit_share.steady":
         ("b1-notes.edit-steady", "edit_ack_p50_ms")}


def ctx_with(row0, row1):
    def serve(row):
        if row is None:
            return {"version": 14}
        return {"phases": {"version": 1, "locks": {},
                           "phases": {"http.edit": row}}}
    return {"m0": {"serve": serve(row0), "_at": 10.0},
            "m1": {"serve": serve(row1), "_at": 20.0}}


def test_the_manifest_names_both_last():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf8") as f:
        bench = json.load(f)
    last = bench["per_layer"][-2:]
    assert [m["name"] for m in last] == list(CELLS)
    for m in last:
        cell, moves = CELLS[m["name"]]
        assert m == {"name": m["name"], "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "HTTP front end",
                     "moves": moves, "workloads": [cell]}


@pytest.mark.parametrize("name", list(CELLS))
def test_the_share_is_taken_over_the_window(name):
    read = metric_reader(name)
    row = {"count": 10, "sum_s": 1.0}
    # no clocks at all; clocks but no counter (the parent): left out
    assert read(ctx_with(None, None)) is None
    assert read(ctx_with(row, dict(row, count=50))) is None
    assert read(ctx_with(row, dict(row, counts={"docs": 3}))) is None
    # before the window 4 hits and 6 misses, in it 27 and 3
    before = dict(row, counts={"len_hit": 4, "len_miss": 6})
    after = dict(row, count=40, counts={"len_hit": 31, "len_miss": 9})
    assert read(ctx_with(before, after)) == pytest.approx(90.0)
    # a window of misses alone reads 0, not nothing
    after = dict(row, count=12, counts={"len_hit": 4, "len_miss": 8})
    assert read(ctx_with(before, after)) == 0.0
    # the first edits of a process fall inside the window
    assert read(ctx_with({"count": 0}, dict(
        row, counts={"len_hit": 9, "len_miss": 1}))) == pytest.approx(90.0)
