"""`http.lean_share.sat` / `.steady` / `.host4`: the share of a
window's edits that the server's lean HTTP parser took (`lean` /
`stdlib` on the `http.edit` row, bench/front.py). Found in the manifest
by name. None on a program without the counts, which is every parent of
the PR that added them. A CPU rehearsal proves the counts, nothing
about the chip.

    python -m pytest bench/tests/test_lean_metrics.py -q -p no:cacheprovider
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.run import metric_reader  # noqa: E402

CELLS = {"http.lean_share.sat": ("b4-papers.edit-sat", "acked_edits_per_s"),
         "http.lean_share.steady": ("b1-notes.edit-steady",
                                    "edit_ack_p50_ms"),
         "http.lean_share.host4": ("host4-mixed.edit-sat128",
                                   "acked_edits_per_s")}


def ctx_with(row0, row1):
    """A recorded pair of scrapes: the `http.edit` row at each."""
    def serve(row):
        if row is None:
            return {"version": 15}
        return {"phases": {"version": 1, "locks": {},
                           "phases": {"http.edit": row}}}
    return {"m0": {"serve": serve(row0), "_at": 10.0},
            "m1": {"serve": serve(row1), "_at": 20.0}}


def test_the_manifest_names_all_three():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf8") as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (cell, moves) in CELLS.items():
        assert by_name[name] == {
            "name": name, "unit": "%", "better": "higher",
            "source": "program_counter", "layer": "HTTP front end",
            "moves": moves, "workloads": [cell]}


@pytest.mark.parametrize("name", list(CELLS))
def test_the_lean_share_is_taken_over_the_window(name):
    read = metric_reader(name)
    row = {"count": 10, "sum_s": 1.0}
    # no clocks at all; clocks but no such counts (the parent): left out
    assert read(ctx_with(None, None)) is None
    assert read(ctx_with(row, dict(row, count=12))) is None
    assert read(ctx_with(dict(row, counts={"len_hit": 10}),
                         dict(row, counts={"len_hit": 12}))) is None
    # the change's own window: every edit the lean parser's, and the
    # count that stays at 0 is never written
    before = dict(row, counts={"len_hit": 10, "lean": 10})
    after = dict(row, count=60, counts={"len_hit": 60, "lean": 60})
    assert read(ctx_with(before, after)) == 100.0
    # before the window 4 by the stdlib's, in it 45 lean and 5 not
    before = dict(row, counts={"lean": 6, "stdlib": 4})
    after = dict(row, count=60, counts={"lean": 51, "stdlib": 9})
    assert read(ctx_with(before, after)) == pytest.approx(90.0)
    # the stdlib's alone reads 0, not nothing
    assert read(ctx_with(dict(row, counts={"stdlib": 1}),
                         dict(row, counts={"stdlib": 3}))) == 0.0
    # and a window with no edit has no share
    assert read(ctx_with(before, dict(before))) is None


def test_a_traced_rehearsal_reports_the_steady_share():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "b1-notes.edit-steady", "--seed", "3000000019", "--seconds", "8",
         "--trace", "1", "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["http.lean_share.steady"] == {
        "value": 100.0, "unit": "%"}
