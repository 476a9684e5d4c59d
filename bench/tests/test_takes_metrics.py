"""`lock.edit_takes_per_push.sat` / `.host4` / `.hunk`: acquisitions of
`DocStore.lock` at the edit path's sites over the `http.edit` roots
closed between the scrapes (`locks["store.oplog"][<site>].acquires`;
bench/takes.py). Found in the manifest BY NAME. 4.0 on a program that
takes the lock for the document, the ops, the dirty flag and the
condition (every parent of PR 43), 1.0 on one that takes it once, None
on a program without the `locks` block. A CPU rehearsal proves the
counts and the arithmetic, nothing about the chip.

    python -m pytest bench/tests/test_takes_metrics.py -q -p no:cacheprovider
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

CELLS = {"sat": "b4-papers.edit-sat", "host4": "host4-mixed.edit-sat128",
         "hunk": "a2-sources.hunk-sat"}
NAMES = [f"lock.edit_takes_per_push.{cell}" for cell in CELLS]


def test_the_manifest_names_all_three():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf8") as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == "acked_edits_per_s")
    for cell, workload in CELLS.items():
        name = f"lock.edit_takes_per_push.{cell}"
        assert by_name[name] == {
            "name": name, "unit": "takes", "better": "lower",
            "source": "program_counter", "layer": "HTTP front end",
            "moves": "acked_edits_per_s", "workloads": [workload]}, name
        assert workload in moved["workloads"]
        assert os.path.exists(os.path.join(
            ROOT, "bench", "metrics", name + ".py")), name


# ---- recorded scrapes --------------------------------------------------------

def cell(acquires):
    return {"acquires": acquires, "wait_s": 0.0, "hold_s": acquires * 1e-4,
            "max_wait_s": 0.0, "max_hold_s": 1e-4}


def block(edits, sites, locks=True):
    out = {"version": 1,
           "phases": {"http.edit": {"count": edits, "sum_s": edits * 2e-3,
                                    "max_s": 0.0, "lock_wait_s": 0.0}}}
    if locks:
        out["locks"] = {"store.oplog": {s: cell(n) for s, n in sites.items()}}
    return out


def ctx_of(b0, b1):
    return {"m0": {"serve": {"phases": b0} if b0 else {"version": 15},
                   "_at": 0.0},
            "m1": {"serve": {"phases": b1} if b1 else {"version": 15},
                   "_at": 100.0},
            "seconds": 50.0}


# 100 edits before the window, 1,000 inside it; the pump's and the
# autosave's takes are nobody's push
OTHERS = {"bank.plan": 40, "adopt": 40, "autosave.encode": 3,
          "get.checkout": 7, "other": 2}
PARENT0 = dict(OTHERS, **{"edit.parse": 100, "edit.checkout": 100,
                          "edit.publish": 200})
PARENT1 = dict({k: 9 * v for k, v in OTHERS.items()},
               **{"edit.parse": 1100, "edit.checkout": 1100,
                  "edit.publish": 2200})
CHANGE0 = dict(OTHERS, **{"edit.parse": 32, "edit.checkout": 100})
CHANGE1 = dict({k: 9 * v for k, v in OTHERS.items()},
               **{"edit.parse": 32, "edit.checkout": 1100})


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_counts_the_edit_sites_takes_a_push(name):
    read = metric_reader(name)
    # no clocks at all; clocks without the lock table
    assert read(ctx_of(None, None)) is None
    assert read(ctx_of(block(100, {}, locks=False),
                       block(1100, {}, locks=False))) is None
    assert read(ctx_of(block(100, PARENT0), block(1100, PARENT1))) == 4.0
    assert read(ctx_of(block(100, CHANGE0), block(1100, CHANGE1))) == 1.0


def test_a_first_push_reads_a_little_over_one_and_an_empty_window_nothing():
    read = metric_reader("lock.edit_takes_per_push.sat")
    # 32 documents first asked for inside the window: 32 loads under
    # the lock, filed under `edit.parse`
    first = dict(CHANGE1, **{"edit.parse": 64})
    assert read(ctx_of(block(100, CHANGE0), block(1100, first))) \
        == pytest.approx(1.032)
    assert read(ctx_of(block(100, CHANGE0), block(100, CHANGE0))) is None
    # a lock nobody on the edit path took yet: 0 takes, not silence
    assert read(ctx_of(block(0, OTHERS), block(10, OTHERS))) == 0.0


def test_a_traced_rehearsal_prints_one_take_a_push():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "b4-papers.edit-sat", "--seed", "3000000019", "--seconds", "6",
         "--trace", "1", "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    takes = out["metrics"]["lock.edit_takes_per_push.sat"]
    assert takes["unit"] == "takes" and 1.0 <= takes["value"] <= 1.05
