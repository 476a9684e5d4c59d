"""`lock.held_by_autosave_share.host4`, `store.autosave_unlocked_share.sat`
and `store.autosave_unlocked_share.host4`: how long the autosave held
`DocStore.lock` on the four-chip host, and what share of the documents
a window's passes saved were encoded from the oplog's native mirror
outside that lock (`docs_unlocked` / `docs_locked` on the
`autosave.pass` row). None on a program without the counters, which is
every parent of the PR that added them. A CPU rehearsal proves the
counts, nothing about the chip.

    python -m pytest bench/tests/test_autosave_metrics.py -q -p no:cacheprovider
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

SAT = "b4-papers.edit-sat"
HOST4 = "host4-mixed.edit-sat128"
SHARES = {"store.autosave_unlocked_share.sat": SAT,
          "store.autosave_unlocked_share.host4": HOST4}
HELD = "lock.held_by_autosave_share.host4"


def ctx_with(row0, row1, sites0=None, sites1=None):
    """A recorded pair of scrapes, 10 s apart: the `autosave.pass` row
    and who held `store.oplog`, by site."""
    def serve(row, sites):
        if row is None:
            return {"version": 15}
        return {"phases": {"version": 1,
                           "locks": {"store.oplog": sites or {}},
                           "phases": {"autosave.pass": row}}}
    return {"m0": {"serve": serve(row0, sites0), "_at": 10.0},
            "m1": {"serve": serve(row1, sites1), "_at": 20.0}}


def test_the_manifest_names_all_three():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf8") as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, cell in SHARES.items():
        assert by_name[name] == {
            "name": name, "unit": "%", "better": "higher",
            "source": "program_counter", "layer": "persistence",
            "moves": "acked_edits_per_s", "workloads": [cell]}
    assert by_name[HELD] == dict(
        by_name["lock.held_by_autosave_share.sat"], name=HELD,
        workloads=[HOST4])


@pytest.mark.parametrize("name", list(SHARES))
def test_the_unlocked_share_is_taken_over_the_window(name):
    read = metric_reader(name)
    row = {"count": 10, "sum_s": 1.0}
    # no clocks at all; clocks but no counters (the parent): left out
    assert read(ctx_with(None, None)) is None
    assert read(ctx_with(row, dict(row, count=12))) is None
    assert read(ctx_with(dict(row, counts={"docs": 30}),
                         dict(row, counts={"docs": 60}))) is None
    # before the window 4 outside the lock and 6 under it, in it 27 and 3
    before = dict(row, counts={"docs": 10, "docs_unlocked": 4,
                               "docs_locked": 6})
    after = dict(row, count=12, counts={"docs": 40, "docs_unlocked": 31,
                                        "docs_locked": 9})
    assert read(ctx_with(before, after)) == pytest.approx(90.0)
    # the change's own window: every document from its mirror (the
    # counter that stays at 0 is written all the same)
    after = dict(row, count=12, counts={"docs": 40, "docs_unlocked": 34,
                                        "docs_locked": 6})
    assert read(ctx_with(before, after)) == 100.0
    # and a pass that saved nothing in the window has no share
    assert read(ctx_with(before, dict(before, count=12))) is None


def test_the_held_share_counts_the_encode_site_alone():
    read = metric_reader(HELD)
    row = {"count": 1, "sum_s": 1.0}
    assert read(ctx_with(None, None)) is None

    def site(hold_s):
        return {"acquires": 1, "wait_s": 0.0, "hold_s": hold_s}
    # 0.25 s of the 10 s window under `autosave.encode`; what the write
    # loop and the handlers held is not the encode's
    before = {"autosave.encode": site(1.0), "autosave.write": site(0.5),
              "edit.checkout": site(2.0)}
    after = {"autosave.encode": site(1.25), "autosave.write": site(0.9),
             "edit.checkout": site(4.0)}
    assert read(ctx_with(row, row, before, after)) == pytest.approx(2.5)


def test_a_traced_rehearsal_reports_the_sat_share():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", SAT, "--seed",
         "3000000019", "--seconds", "8", "--trace", "1", "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    got = out["metrics"]
    assert got["store.autosave_unlocked_share.sat"] == {
        "value": 100.0, "unit": "%"}
    assert 0.0 <= got["lock.held_by_autosave_share.sat"]["value"] < 50.0
