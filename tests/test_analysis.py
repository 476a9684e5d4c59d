"""Concurrency invariant analyzer (analysis/): dt-lint + lock witness.

Covers the static_analysis PR top to bottom:
  * each lint rule fires on its seeded known-bad fixture
    (tests/fixtures/analysis/) and names the right line;
  * same-line `# dt-lint: ignore[rule]` and `# dt-lint: skip-file`
    suppressions silence findings;
  * the repaired tree lints CLEAN — `cli dt-lint --fail-on warn`
    exits 0 (the tier-1 gate) and nonzero when pointed at a fixture;
  * the runtime lock witness: order-graph edges, cycle detection,
    same-class rank monotonicity, disabled no-op, reentrancy;
  * regression pins for the two tree repairs this PR shipped — the
    sorted `_flush_window` device-lock acquisition and the
    admit-gated read path that no longer dispatches under the oplog
    guard.
"""

import json
import os
import subprocess
import sys
import threading

import pytest

from diamond_types_tpu.analysis import (make_lock, run_lint,
                                        witness_assert_acyclic,
                                        witness_disable, witness_enable,
                                        witness_reset, witness_snapshot)
from diamond_types_tpu.analysis.lint import (SEVERITY, render_human,
                                             render_json)

pytestmark = pytest.mark.analysis

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "analysis")


def _lint_fixture(name):
    return run_lint(paths=[os.path.join(FIXTURES, name)])


@pytest.fixture(autouse=True)
def _witness_clean():
    witness_reset()
    yield
    witness_disable()
    witness_reset()


# ---- rules on seeded fixtures --------------------------------------------

@pytest.mark.parametrize("fixture,rule,line,severity", [
    ("bad_lock_order.py", "lock-order", 12, "error"),
    ("bad_hydration_lock_order.py", "lock-order", 14, "error"),
    ("bad_read_lock_order.py", "lock-order", 15, "error"),
    ("bad_rebalance_lock_order.py", "lock-order", 14, "error"),
    ("bad_writergroup_lock_order.py", "lock-order", 15, "error"),
    ("bad_qos_lock_order.py", "lock-order", 17, "error"),
    ("bad_ts_lock_order.py", "lock-order", 15, "error"),
    ("bad_incident_lock_order.py", "lock-order", 15, "error"),
    ("bad_wire_lock_order.py", "lock-order", 14, "error"),
    ("bad_arena_lock_order.py", "lock-order", 15, "error"),
    ("bad_steer_lock_order.py", "lock-order", 15, "error"),
    ("bad_unsorted_locks.py", "unsorted-locks", 15, "error"),
    ("bad_device_under_lock.py", "device-under-lock", 13, "error"),
    ("bad_unfenced_mutation.py", "unfenced-mutation", 15, "error"),
    ("bad_jit_impurity.py", "jit-impurity", 14, "warn"),
    ("bad_jit_cache_key.py", "jit-cache-key", 13, "warn"),
    ("bad_blocking_call.py", "blocking-call-under-lock", 14, "warn"),
    ("bad_unguarded_acquire.py", "unguarded-acquire", 12, "error"),
    ("bad_metrics_drift.py", "metrics-schema-drift", 11, "error"),
    ("bad_qos_metrics_drift.py", "metrics-schema-drift", 12, "error"),
    ("bad_incident_metrics_drift.py", "metrics-schema-drift", 13, "error"),
    ("bad_exemplar_drift.py", "metrics-schema-drift", 9, "error"),
    ("bad_stale_suppression.py", "stale-suppression", 11, "warn"),
    # the two historical bugs PR 7's tree repairs fixed, re-expressed
    # as seeded fixtures so the rules that caught them stay honest
    ("bad_unsorted_flush_window.py", "unsorted-locks", 18, "error"),
    ("bad_read_under_oplog.py", "device-under-lock", 16, "error"),
])
def test_rule_fires_on_seeded_fixture(fixture, rule, line, severity):
    report = _lint_fixture(fixture)
    assert not report["ok"]
    assert report["by_rule"][rule] >= 1, render_human(report)
    v = next(v for v in report["violations"] if v["rule"] == rule)
    assert v["line"] == line
    assert v["severity"] == severity
    assert v["path"].endswith(fixture)
    # no cross-talk: the fixture seeds exactly one rule
    assert {v["rule"] for v in report["violations"]} == {rule}


def test_severity_split_counts():
    report = run_lint(paths=[FIXTURES])
    assert report["errors"] == sum(
        1 for v in report["violations"] if v["severity"] == "error")
    assert report["warnings"] == len(report["violations"]) \
        - report["errors"]
    assert report["errors"] >= 4 and report["warnings"] >= 3
    doc = json.loads(render_json(report))
    assert doc["by_rule"] == report["by_rule"]


def test_same_line_suppression_silences():
    report = _lint_fixture("suppressed_ok.py")
    assert report["ok"], render_human(report)


def test_skip_file_suppression_silences():
    report = _lint_fixture("skipped_file.py")
    assert report["ok"], render_human(report)


def test_disable_flag_drops_rule():
    report = run_lint(paths=[os.path.join(FIXTURES, "bad_lock_order.py")],
                      disable=["lock-order"])
    assert report["ok"]


# ---- the tree itself lints clean -----------------------------------------

def test_clean_tree_lints_zero():
    """The repaired tree is the fixture for 'exit 0': every rule runs
    over serve/, replicate/, tpu/, parallel/, tools/ and finds
    nothing."""
    report = run_lint()
    assert report["files"] >= 30
    assert report["ok"], render_human(report)
    assert set(report["by_rule"]) == set(SEVERITY)


def test_cli_dt_lint_gate():
    """Tier-1 gate: `cli dt-lint --fail-on warn` exits 0 on the tree,
    nonzero when a seeded fixture is in scope."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    base = [sys.executable, "-m", "diamond_types_tpu.tools.cli",
            "dt-lint", "--fail-on", "warn"]
    clean = subprocess.run(base, capture_output=True, text=True,
                           env=env)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    assert "0 errors, 0 warnings" in clean.stdout
    for name in sorted(os.listdir(FIXTURES)):
        if not name.startswith("bad_"):
            continue
        bad = subprocess.run(
            base + ["--json", os.path.join(FIXTURES, name)],
            capture_output=True, text=True, env=env)
        assert bad.returncode == 1, name
        doc = json.loads(bad.stdout)
        assert sum(doc["by_rule"].values()) >= 1, name


def test_cli_fail_on_error_ignores_warnings():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    warn_only = subprocess.run(
        [sys.executable, "-m", "diamond_types_tpu.tools.cli",
         "dt-lint", "--fail-on", "error",
         os.path.join(FIXTURES, "bad_jit_impurity.py")],
        capture_output=True, text=True, env=env)
    assert warn_only.returncode == 0, warn_only.stdout


# ---- runtime lock witness ------------------------------------------------

def test_witness_records_order_edges():
    witness_enable()
    g = make_lock("w.global", "global")
    s = make_lock("w.shard", "shard")
    with g:
        with s:
            pass
    snap = witness_snapshot()
    assert snap["edges"] == {"global->shard": 1}
    assert snap["acquires"] == 2
    assert snap["acyclic"]
    witness_assert_acyclic()


def test_witness_detects_cycle():
    witness_enable()
    g = make_lock("w.global", "global")
    s = make_lock("w.shard", "shard")
    with g:
        with s:
            pass
    with s:
        with g:     # backwards: closes the global<->shard cycle
            pass
    snap = witness_snapshot()
    assert not snap["acyclic"]
    assert any("global" in c and "shard" in c for c in snap["cycles"])
    with pytest.raises(AssertionError):
        witness_assert_acyclic()


def test_witness_same_class_rank_monotonicity():
    witness_enable()
    a = make_lock("shard[0]", "shard", rank=0)
    b = make_lock("shard[1]", "shard", rank=1)
    with a:
        with b:     # ascending rank: fine
            pass
    assert witness_snapshot()["violation_count"] == 0
    with b:
        with a:     # descending rank within one class: flagged
            pass
    snap = witness_snapshot()
    assert snap["violation_count"] == 1
    assert snap["violations"][0]["kind"] == "unsorted-same-class"
    with pytest.raises(AssertionError):
        witness_assert_acyclic()


def test_witness_disabled_is_noop():
    lk = make_lock("w.off", "global")
    inner = make_lock("w.off2", "shard")
    with lk:
        with inner:
            pass
    snap = witness_snapshot()
    assert not snap["enabled"]
    assert snap["acquires"] == 0
    assert snap["edge_count"] == 0
    assert snap["acyclic"]


def test_witness_reentrant_and_threaded():
    witness_enable()
    r = make_lock("w.re", "repl.leases", reentrant=True)
    leaf = make_lock("w.leaf", "leaf")
    with r:
        with r:                 # same-object re-acquire: no edge
            with leaf:
                pass
    snap = witness_snapshot()
    assert snap["edges"] == {"repl.leases->leaf": 1}

    def worker():
        with r:
            with leaf:
                pass
    ts = [threading.Thread(target=worker) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    witness_assert_acyclic()


# ---- regression pins for this PR's tree repairs --------------------------

def _tree_report(*parts):
    from diamond_types_tpu.analysis.lint import repo_root
    return run_lint(paths=[os.path.join(repo_root(), *parts)])


def test_flush_window_device_locks_stay_sorted():
    """Regression: scheduler._flush_window acquires its device locks
    via the sorted-shards comprehension; reintroducing an unsorted
    acquisition loop (or a dispatch under the global lock) trips the
    lint again."""
    report = _tree_report("serve", "scheduler.py")
    assert report["by_rule"]["unsorted-locks"] == 0, render_human(report)
    assert report["by_rule"]["lock-order"] == 0
    assert report["by_rule"]["device-under-lock"] == 0


def test_read_path_stays_fenced_and_lock_clean():
    """Regression: scheduler.text serves unadmitted docs from the
    durable oplog tip (admit gate) and bank.text splits the oplog read
    from the device fetch — neither dispatches under the oplog
    guard."""
    for parts in (("serve", "scheduler.py"), ("serve", "bank.py")):
        report = _tree_report(*parts)
        assert report["by_rule"]["device-under-lock"] == 0, \
            render_human(report)
        assert report["by_rule"]["unfenced-mutation"] == 0


def test_text_unadmitted_doc_serves_oplog_tip():
    """Behavioral half of the admit-gate repair: a doc the ownership
    gate rejects is still readable — served from the durable oplog
    tip, with no device session ever built for it."""
    from diamond_types_tpu.serve.scheduler import MergeScheduler
    from diamond_types_tpu.text.oplog import OpLog
    ol = OpLog()
    ol.doc_id = "d0"
    a = ol.get_or_create_agent_id("a")
    ol.add_insert(a, 0, "hello")
    sched = MergeScheduler(1, resolve=lambda d: ol, engine="host",
                           flush_workers=False,
                           admit=lambda d: False)
    assert sched.text("d0") == "hello"
    assert sched.banks[0].sessions.get("d0") is None
