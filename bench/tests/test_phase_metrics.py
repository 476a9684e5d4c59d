"""The per-layer metrics that read the program's phase clocks
(`bench/phases.py` and its fourteen readers): one `--tiny --trace 1`
rehearsal of each cell finds a number for every one of them, and every
reader returns None on a context whose program has no clocks (the
parent of the PR that added them). A CPU rehearsal proves nothing
about the chip. The listen-queue pair of the PR's issue is not in the
manifest and has no code: the chip machine's kernel gives `TcpExt` no
values (PERF.md section 7).

    python -m pytest bench/tests -q -p no:cacheprovider
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

from bench import phases  # noqa: E402
from bench.run import metric_reader  # noqa: E402

SAT, STEADY = "b4-papers.edit-sat", "b1-notes.edit-steady"
NEW = {
    SAT: ["http.edit_checkout_mean_ms.sat", "lock.held_by_edit_share.sat",
          "lock.held_by_autosave_share.sat", "lock.held_by_pump_share.sat",
          "store.autosave_lock_wait_share.sat",
          "store.autosave_encode_ms_a_doc.sat",
          "sched.queue_wait_mean_ms.sat", "plan.xf_share.sat"],
    STEADY: ["http.accept_wait_mean_ms.steady",
             "http.edit_own_mean_ms.steady",
             "lock.held_by_autosave_share.steady",
             "sched.queue_wait_mean_ms.steady", "replay.stack_share.steady",
             "replay.fence_share.steady"]}


def test_the_manifest_names_them_with_layer_moves_and_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf8") as f:
        bench = json.load(f)
    moves = {SAT: "acked_edits_per_s", STEADY: "edit_ack_p50_ms"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert sum(len(v) for v in NEW.values()) == 14
    for cell, names in NEW.items():
        for name in names:
            m = by_name[name]
            assert m["workloads"] == [cell] and m["moves"] == moves[cell]
            assert m["source"] == "program_span"
    # appended: what the benchmark had comes first, in its old order
    assert [m["name"] for m in bench["per_layer"]][-14:] == [
        n for n in by_name if n in NEW[SAT] + NEW[STEADY]]


@pytest.mark.parametrize("cell", [SAT, STEADY])
def test_a_traced_rehearsal_reports_every_one(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    # 8 s: an autosave pass (every 3 s) that encodes falls inside
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         "3000000019", "--seconds", "8", "--trace", "1", "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    got = out["metrics"]
    for name in NEW[cell]:
        assert name in got, f"{name} reports nothing in {cell}"
        assert got[name]["value"] >= 0
    for name in NEW[cell]:
        if name in got and got[name]["unit"] == "%":
            assert got[name]["value"] <= 100.0, (name, got[name])


def ctx_with(block0, block1):
    return {"m0": {"serve": dict(block0), "_at": 10.0},
            "m1": {"serve": dict(block1), "_at": 20.0}}


def test_every_reader_returns_none_without_the_clocks():
    """The parent's `metrics_json()` has no `phases`: nothing raises,
    every new metric is left out of the line."""
    ctx = ctx_with({"version": 14}, {"version": 14})
    for names in NEW.values():
        for name in names:
            assert metric_reader(name)(ctx) is None, name
    # a block with no such row, or nothing to divide by, reads None too
    empty = {"phases": {"version": 1, "phases": {}, "locks": {}}}
    ctx = ctx_with(empty, empty)
    for names in NEW.values():
        for name in names:
            v = metric_reader(name)(ctx)
            assert v is None or v == 0.0, (name, v)


def test_the_readers_take_differences_over_the_window():
    def block(k):
        row = {"count": 10 * k, "sum_s": 1.0 * k, "max_s": 0.5,
               "lock_wait_s": 0.25 * k, "lock_hold_s": 0.5 * k}
        return {"phases": {
            "version": 1,
            "phases": {"http.edit": row, "edit.checkout": row,
                       "http.accept_wait": row, "sched.queue_wait": row,
                       "autosave.pass": dict(row, counts={"docs": 5 * k}),
                       "autosave.encode": row, "plan.tail": row,
                       "plan.xf": dict(row, sum_s=0.5 * k),
                       "replay": dict(row, sum_s=4.0 * k),
                       "replay.stack": row, "replay.fence": row},
            "locks": {"store.oplog": {
                "edit.checkout": {"hold_s": 2.0 * k},
                "edit.publish": {"hold_s": 0.5 * k},
                "autosave.encode": {"hold_s": 1.0 * k},
                "autosave.write": {"hold_s": 9.0 * k},
                "bank.plan": {"hold_s": 0.25 * k},
                "adopt": {"hold_s": 0.25 * k},
                "other": {"hold_s": 7.0 * k}}}}}
    ctx = ctx_with(block(1), block(3))      # window 10 s, rows doubled
    want = {"http.accept_wait_mean_ms.steady": 100.0,
            "http.edit_own_mean_ms.steady": 75.0,
            "http.edit_checkout_mean_ms.sat": 75.0,
            "lock.held_by_edit_share.sat": 50.0,
            "lock.held_by_autosave_share.sat": 20.0,
            "lock.held_by_autosave_share.steady": 20.0,
            "lock.held_by_pump_share.sat": 10.0,
            "store.autosave_lock_wait_share.sat": 25.0,
            "store.autosave_encode_ms_a_doc.sat": 150.0,
            "sched.queue_wait_mean_ms.sat": 100.0,
            "sched.queue_wait_mean_ms.steady": 100.0,
            "plan.xf_share.sat": 50.0,
            "replay.stack_share.steady": 25.0,
            "replay.fence_share.steady": 25.0}
    assert set(want) == set(NEW[SAT] + NEW[STEADY])
    for name, value in want.items():
        assert metric_reader(name)(ctx) == pytest.approx(value), name
    assert phases.window_s(ctx) == 10.0
