"""`plan.xf_native_share.sat` / `plan.mirror_append_share.sat`: whether
a window's plan walks ran their transform on the oplog's native mirror,
and whether that mirror followed the oplog by appending (`xf_native` /
`xf_python`, `mirror_appended` / `mirror_rebuilt` on the `plan.tail`
row). Appended to the manifest last; None on a program without the
counters, which is every parent of the PR that added them. A CPU
rehearsal proves the counts, nothing about the chip.

    python -m pytest bench/tests/test_mirror_metrics.py -q -p no:cacheprovider
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
COUNTS = {"plan.xf_native_share.sat": ("xf_native", "xf_python"),
          "plan.mirror_append_share.sat": ("mirror_appended",
                                           "mirror_rebuilt")}


def ctx_with(row0, row1):
    def serve(row):
        if row is None:
            return {"version": 14}
        return {"phases": {"version": 1, "locks": {},
                           "phases": {"plan.tail": row}}}
    return {"m0": {"serve": serve(row0), "_at": 10.0},
            "m1": {"serve": serve(row1), "_at": 20.0}}


def test_the_manifest_names_both():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf8") as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in COUNTS:
        assert by_name[name] == {
            "name": name, "unit": "%", "better": "higher",
            "source": "program_counter", "layer": "replay rungs",
            "moves": "acked_edits_per_s", "workloads": [SAT]}


@pytest.mark.parametrize("name", list(COUNTS))
def test_the_share_is_taken_over_the_window(name):
    read = metric_reader(name)
    good, bad = COUNTS[name]
    row = {"count": 10, "sum_s": 1.0}
    # no clocks at all; clocks but no counters (the parent): left out
    assert read(ctx_with(None, None)) is None
    assert read(ctx_with(row, dict(row, count=50))) is None
    assert read(ctx_with(row, dict(row, counts={"docs": 3}))) is None
    # before the window 1 of the one kind and 5 of the other, in it 27
    # and 3
    before = dict(row, counts={good: 1, bad: 5})
    after = dict(row, count=40, counts={good: 28, bad: 8})
    assert read(ctx_with(before, after)) == pytest.approx(90.0)
    # the change's own window: every walk native, every sync an append
    # (the counter that stays at 0 is written all the same)
    after = dict(row, count=40, counts={good: 31, bad: 5})
    assert read(ctx_with(before, after)) == 100.0
    assert read(ctx_with({"count": 0}, dict(
        row, counts={good: 9, bad: 0}))) == 100.0


def test_a_traced_rehearsal_reports_both():
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
    assert got["plan.xf_native_share.sat"] == {"value": 100.0, "unit": "%"}
    assert 99.0 <= got["plan.mirror_append_share.sat"]["value"] <= 100.0
