"""`replay.fence_share.sat`: the share of a `fused_replay` call spent
at the length fence, waiting for the device. Found in the manifest by
name; it reads any program that has the phase clocks, the parent of the
PR that added it too. A CPU rehearsal proves that it is reported,
nothing about the chip.

    python -m pytest bench/tests/test_fence_share_metric.py -q -p no:cacheprovider
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
FENCE = "replay.fence_share.sat"


def ctx_with(rows0, rows1):
    """A recorded pair of scrapes: the phase rows at each."""
    def serve(rows):
        if rows is None:
            return {"version": 15}
        return {"phases": {"version": 1, "locks": {}, "phases": rows}}
    return {"m0": {"serve": serve(rows0), "_at": 10.0},
            "m1": {"serve": serve(rows1), "_at": 20.0}}


def test_the_manifest_names_it():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf8") as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name[FENCE] == {
        "name": FENCE, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "replay rungs",
        "moves": "acked_edits_per_s", "workloads": [SAT]}


def test_the_fence_share_reads_a_parent_too():
    read = metric_reader(FENCE)
    assert read(ctx_with(None, None)) is None
    assert read(ctx_with({}, {})) is None
    # the rows a program has kept since PR 25, no new count needed
    before = {"replay": {"count": 10, "sum_s": 1.0},
              "replay.fence": {"count": 10, "sum_s": 0.25}}
    after = {"replay": {"count": 50, "sum_s": 5.0},
             "replay.fence": {"count": 50, "sum_s": 3.25}}
    assert read(ctx_with(before, after)) == pytest.approx(75.0)
    assert read(ctx_with(before, before)) is None


def test_a_traced_rehearsal_reports_it():
    # a seed of its own: the trace directory is named by cell and seed,
    # and test_mirror_metrics.py rehearses this cell too, maybe beside
    # this test
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", SAT, "--seed",
         "3000000037", "--seconds", "8", "--trace", "1", "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert 0.0 < out["metrics"][FENCE]["value"] < 100.0
