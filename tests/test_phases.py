"""Phase clocks (diamond_types_tpu/obs/phases.py): rows that add up,
steps that cover their root, lock waits and holds by site, the clock a
WitnessLock takes, the served path's own phases through a live server,
their exports, the slow-request event, the profiler's capture and the
replay program's scopes."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import tracemalloc
import urllib.request

import numpy as np
import pytest

from diamond_types_tpu.analysis import witness
from diamond_types_tpu.analysis.witness import make_lock
from diamond_types_tpu.obs import Observability
from diamond_types_tpu.obs import phases as phases_mod
from diamond_types_tpu.obs.phases import NOOP_PHASE, PhaseTable, phase
from diamond_types_tpu.obs.prom import render_metrics
from diamond_types_tpu.serve.metrics import ServeMetrics

pytestmark = pytest.mark.obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EDIT_STEPS = ("edit.parse", "edit.checkout", "edit.apply", "edit.publish",
              "edit.submit", "edit.respond")


# ---- the table ---------------------------------------------------------------

def test_a_row_adds_up_across_threads():
    table = PhaseTable()

    def work():
        for _ in range(200):
            with table.phase("w") as ph:
                ph.count("ops", 2)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    row = table.snapshot()["phases"]["w"]
    assert row["count"] == 1600 and row["counts"] == {"ops": 3200}
    assert 0 < row["max_s"] <= row["sum_s"]


def test_steps_cover_their_root_and_other_is_what_is_left():
    table = PhaseTable()
    with table.phase("root") as root:
        time.sleep(0.01)                  # before the first step: `.other`
        root.step("root.a")
        time.sleep(0.01)
        root.step("root.b")
        time.sleep(0.01)
    snap = table.snapshot()["phases"]
    parts = snap["root.a"]["sum_s"] + snap["root.b"]["sum_s"]
    assert snap["root.other"]["count"] == 1
    assert snap["root"]["sum_s"] == pytest.approx(
        parts + snap["root.other"]["sum_s"], abs=1e-9)
    assert snap["root.other"]["sum_s"] >= 0.009
    assert "other_s" not in snap["root"]
    # a phase that never had a child reports no `.other`
    assert "root.a.other" not in snap


def test_steps_are_contiguous_and_a_raise_closes_them():
    table = PhaseTable()
    with pytest.raises(KeyError):
        with table.phase("r") as root:
            root.step("r.a")
            root.step("r.b")
            raise KeyError("x")
    snap = table.snapshot()["phases"]
    assert {n: snap[n]["count"] for n in ("r", "r.a", "r.b")} \
        == {"r": 1, "r.a": 1, "r.b": 1}
    assert phases_mod._tls.stack == []
    # a.end == b.start: nothing between two steps goes uncounted
    assert snap["r"]["sum_s"] - snap["r.other"]["sum_s"] == pytest.approx(
        snap["r.a"]["sum_s"] + snap["r.b"]["sum_s"], abs=1e-9)


def test_module_level_phase_needs_an_open_root():
    assert phase("plan.tail") is NOOP_PHASE
    with phase("plan.tail") as ph:          # records nowhere, raises nothing
        ph.step("plan.xf")
        ph.count("rows", 3)
        ph.note("http.accept_wait", 0.1)
        walk = iter([1, 2])
        assert ph.timed(walk, "plan.xf") is walk
    a, b = PhaseTable(), PhaseTable()
    with a.phase("root"):
        with phase("child"):
            pass
    assert "child" in a.snapshot()["phases"]
    assert b.snapshot()["phases"] == {}


def test_lock_wait_and_hold_go_to_the_innermost_phase_and_to_other():
    table = PhaseTable()
    lk = make_lock("t.lock", "oplog", clocked=True)
    lk.attach_clock(table)
    held = threading.Event()
    release = threading.Event()

    def holder():
        with lk:                              # no phase open: site `other`
            held.set()
            release.wait(timeout=10)

    t = threading.Thread(target=holder)
    t.start()
    assert held.wait(timeout=10)
    threading.Timer(0.05, release.set).start()
    with table.phase("root") as root:
        root.step("root.leaf")
        with lk:                              # waits about 50 ms
            time.sleep(0.02)
    t.join(timeout=10)
    assert not t.is_alive()
    snap = table.snapshot()
    cell = snap["locks"]["t.lock"]
    assert set(cell) == {"other", "root.leaf"}
    assert cell["root.leaf"]["acquires"] == 1
    assert cell["root.leaf"]["wait_s"] >= 0.04
    assert cell["root.leaf"]["hold_s"] >= 0.02
    assert cell["root.leaf"]["wait_max_s"] == cell["root.leaf"]["wait_s"]
    assert cell["other"]["wait_s"] == 0.0 and cell["other"]["hold_s"] >= 0.05
    ph = snap["phases"]
    # in the leaf's row and, through it, in the root's
    for name in ("root.leaf", "root"):
        assert ph[name]["lock_wait_s"] == pytest.approx(
            cell["root.leaf"]["wait_s"])
        assert ph[name]["lock_hold_s"] == pytest.approx(
            cell["root.leaf"]["hold_s"])
    assert ph["other"]["lock_hold_s"] == pytest.approx(
        cell["other"]["hold_s"])


def test_a_hold_that_outlives_its_phase_is_still_counted():
    table = PhaseTable()
    lk = make_lock("t.lock2", "oplog", clocked=True)
    lk.attach_clock(table)
    with table.phase("outer"):
        with table.phase("inner"):
            lk.acquire()
        time.sleep(0.01)
        lk.release()
    snap = table.snapshot()
    assert snap["locks"]["t.lock2"]["inner"]["hold_s"] >= 0.01
    assert snap["phases"]["inner"]["lock_hold_s"] >= 0.01
    assert snap["phases"]["outer"]["lock_hold_s"] >= 0.01


@pytest.mark.parametrize("clocked", [False, True])
def test_a_lock_without_a_clock_allocates_nothing(clocked):
    """A plain WitnessLock is what it was; a ClockedLock with no clock
    attached pays one attribute check more: tracemalloc attributes no
    allocation to witness.py or phases.py across 200 acquire/release
    pairs (the idiom of tests/test_obs.py)."""
    lk = make_lock("t.plain", "leaf", clocked=clocked)
    assert type(lk) is (witness.ClockedLock if clocked
                        else witness.WitnessLock)
    assert getattr(lk, "clock", None) is None

    def cycle():
        for _ in range(200):
            lk.acquire()
            lk.release()

    cycle()
    files = {witness.__file__, phases_mod.__file__}
    grew = []
    tracemalloc.start()
    for _attempt in range(3):
        before = tracemalloc.take_snapshot()
        cycle()
        after = tracemalloc.take_snapshot()
        grew = [st for st in after.compare_to(before, "lineno")
                if st.size_diff > 0
                and st.traceback[0].filename in files
                and st.traceback[0].lineno > 0]
        if not grew:
            break
    tracemalloc.stop()
    assert not grew, [str(g) for g in grew]


def test_a_reentrant_lock_takes_no_clock():
    with pytest.raises(ValueError):
        make_lock("t.re", "leaf", reentrant=True, clocked=True)
    assert not hasattr(make_lock("t.re", "leaf", reentrant=True),
                       "attach_clock")


def test_a_timed_acquire_that_gives_up_holds_nothing():
    table = PhaseTable()
    lk = make_lock("t.lock3", "oplog", clocked=True)
    lk.attach_clock(table)
    assert lk.acquire()
    got = []
    t = threading.Thread(target=lambda: got.append(
        (lk.acquire(blocking=False), lk.acquire(timeout=0.01))))
    t.start()
    t.join(timeout=10)
    assert got == [(False, False)]
    lk.release()
    assert table.snapshot()["locks"]["t.lock3"]["other"]["acquires"] == 1


def test_a_root_writes_the_table_once_at_its_close():
    """Steps, the phases under a root, lock events and notes sit in the
    root's own list until it closes: one update, under one lock."""
    table = PhaseTable()
    lk = make_lock("t.lock4", "oplog", clocked=True)
    lk.attach_clock(table)
    taken = []
    real = table._lock

    class Counting:
        def __enter__(self):
            taken.append(1)
            return real.__enter__()

        def __exit__(self, *exc):
            return real.__exit__(*exc)

    table._lock = Counting()
    root = table.phase("root")
    root.note("http.accept_wait", 0.002)
    with root:
        root.step("root.a")
        with lk:
            pass
        with phase("child") as child:
            child.step("child.a")
            child.count("docs", 3)
        root.step("root.b")
        assert taken == []
    assert taken == [1]
    snap = table.snapshot()
    ph = snap["phases"]
    assert {n: ph[n]["count"] for n in ph} == {
        "root": 1, "root.a": 1, "root.b": 1, "root.other": 1, "child": 1,
        "child.a": 1, "child.other": 1, "http.accept_wait": 1}
    assert ph["child"]["counts"] == {"docs": 3}
    assert ph["http.accept_wait"]["sum_s"] == 0.002
    assert snap["locks"]["t.lock4"]["root.a"]["acquires"] == 1
    # the child sits inside the step `root.a`: no part of `.other`
    assert ph["root"]["sum_s"] == pytest.approx(
        ph["root.a"]["sum_s"] + ph["root.b"]["sum_s"]
        + ph["root.other"]["sum_s"], abs=1e-9)


def test_a_timed_walk_stays_lazy_and_has_a_step_of_its_own():
    table = PhaseTable()
    pulled = []

    def walk():
        for i in range(3):
            time.sleep(0.01)
            pulled.append(i)
            yield i

    with table.phase("plan.tail") as ph:
        ph.step("plan.rows")
        it = ph.timed(walk(), "plan.xf")
        assert pulled == []                   # nothing ran ahead
        for i in it:
            assert pulled == list(range(i + 1))
            time.sleep(0.005)
    rows = table.snapshot()["phases"]
    assert rows["plan.xf"]["count"] == 1
    assert rows["plan.xf"]["sum_s"] >= 0.03
    assert 0.015 <= rows["plan.rows"]["sum_s"] < rows["plan.xf"]["sum_s"]
    assert rows["plan.tail"]["sum_s"] == pytest.approx(
        rows["plan.xf"]["sum_s"] + rows["plan.rows"]["sum_s"]
        + rows["plan.tail.other"]["sum_s"], abs=1e-9)
    # a walk that raises hands its seconds over too
    def bad():
        yield 1
        raise KeyError("x")
    with pytest.raises(KeyError):
        with table.phase("plan.tail") as ph:
            ph.step("plan.rows")
            for _ in ph.timed(bad(), "plan.xf"):
                pass
    assert table.snapshot()["phases"]["plan.xf"]["count"] == 2


def test_an_adopted_histogram_is_exported_not_copied():
    from diamond_types_tpu.obs.hist import Histogram
    table, h = PhaseTable(), Histogram()
    table.adopt("sched.queue_wait", h)
    h.record(0.5)
    h.record(0.25)
    row = table.snapshot()["phases"]["sched.queue_wait"]
    assert (row["count"], row["sum_s"], row["max_s"]) == (2, 0.75, 0.5)


def test_observe_all_writes_several_rows_under_one_take_of_the_lock():
    table = PhaseTable()
    takes = []

    class Counted:
        def __init__(self, inner):
            self.inner = inner

        def __enter__(self):
            takes.append(1)
            return self.inner.__enter__()

        def __exit__(self, *exc):
            return self.inner.__exit__(*exc)
    table._lock = Counted(table._lock)
    table.observe_all([("http.thread_start", 0.001),
                       ("http.thread_after", 0.002),
                       ("http.thread_cpu", 0.003)])
    table.observe_all(iter([("http.thread_cpu", 0.005)]))
    assert len(takes) == 2
    ph = table.snapshot()["phases"]
    assert {k: (r["count"], r["sum_s"]) for k, r in ph.items()} == {
        "http.thread_start": (1, 0.001), "http.thread_after": (1, 0.002),
        "http.thread_cpu": (2, 0.008)}
    assert ph["http.thread_cpu"]["max_s"] == 0.005


def test_a_tally_sums_its_samples_and_keeps_its_maxima():
    table = PhaseTable()
    for depth in (3, 17, 5):
        table.tally("http.listen_wait",
                    {"listen_depth": depth, "listen_samples": 1},
                    {"listen_depth_max": depth})
    row = table.snapshot()["phases"]["http.listen_wait"]
    assert row["counts"] == {"listen_depth": 25, "listen_samples": 3,
                             "listen_depth_max": 17}
    # samples are no closes of the phase: the row's own count is what
    # `note` / `observe_all` wrote, and a root's counts land beside them
    assert row["count"] == 0 and row["sum_s"] == 0.0
    with table.phase("http.edit") as ph:
        ph.note("http.listen_wait", 0.004)
    row = table.snapshot()["phases"]["http.listen_wait"]
    assert (row["count"], row["sum_s"]) == (1, 0.004)
    assert row["counts"]["listen_depth_max"] == 17


def test_a_closed_phase_knows_when_it_closed():
    table = PhaseTable()
    before = time.perf_counter()
    with table.phase("r") as root:
        assert not root.done and root.t1 == 0.0
    assert root.done and before <= root.t0 <= root.t1 <= time.perf_counter()
    assert NOOP_PHASE.done is False


# ---- through a live server ---------------------------------------------------

def _serve(**kw):
    from diamond_types_tpu.tools.server import serve
    kw.setdefault("obs_opts", {"sample_rate": 0.0})
    httpd = serve(port=0, engine="host", serve_shards=1, **kw)
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, addr


def _stop(*httpds):
    for h in httpds:
        h.shutdown()
        h.server_close()


def _edit(addr, doc, text="x", agent="a"):
    req = urllib.request.Request(
        f"http://{addr}/doc/{doc}/edit",
        data=json.dumps({"agent": agent, "version": None,
                         "ops": [{"kind": "ins", "pos": 0,
                                  "text": text}]}).encode("utf8"))
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.loads(r.read())


def _get(addr, path):
    with urllib.request.urlopen(f"http://{addr}{path}", timeout=10) as r:
        return r.read()


def _hold(lock, seconds):
    """Take `lock` on a thread of its own and keep it for `seconds`;
    returns once it is held."""
    held = threading.Event()

    def run():
        with lock:
            held.set()
            time.sleep(seconds)

    threading.Thread(target=run, daemon=True).start()
    assert held.wait(timeout=10)


def _wait_for(cond, timeout=5.0):
    """A handler's root closes after the response is on the wire."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


def test_an_edit_and_a_get_leave_their_parts(tmp_path):
    httpd, addr = _serve(data_dir=str(tmp_path))
    try:
        for i in range(5):
            _edit(addr, "d", text="hello")
        assert _get(addr, "/doc/d") == b"hello" * 5
        table = httpd.store.obs.phases
        assert _wait_for(lambda: "http.get" in table.snapshot()["phases"]
                         and table.snapshot()["phases"]["http.edit"]["count"]
                         == 5)
        httpd.store.scheduler.drain()
        httpd.store.flush(force=True)
        snap = table.snapshot()
        ph = snap["phases"]
        for name in EDIT_STEPS:
            assert ph[name]["count"] == 5, name
        covered = sum(ph[n]["sum_s"] for n in EDIT_STEPS)
        assert ph["http.edit"]["sum_s"] == pytest.approx(
            covered + ph["http.edit.other"]["sum_s"], abs=1e-6)
        assert ph["get.checkout"]["count"] == ph["get.respond"]["count"] == 1
        assert ph["http.accept_wait"]["count"] >= 6
        assert httpd.accepted_at == {}
        # the autosave pass: what it saves fixed under one hold of the
        # lock and encoded outside it, then the file loop
        assert ph["autosave.pass"]["count"] >= 1
        assert ph["autosave.pass"]["counts"] == {
            "docs": 1, "docs_unlocked": 1, "docs_locked": 0}
        assert ph["autosave.encode"]["count"] == ph["autosave.write"]["count"]
        # the flush path's root and what hangs under it
        assert ph["sched.flush"]["count"] >= 1
        assert ph["bank.resolve"]["count"] == ph["adopt"]["count"] \
            == ph["sched.flush"]["count"]
        assert ph["sched.queue_wait"]["count"] == httpd.store.scheduler \
            .metrics.queue_wait_latency.count >= 1
        # who held the store lock, by site
        sites = snap["locks"]["store.oplog"]
        # an edit's ONE hold (validate, add, the dirty flag); the first
        # push made the document under the lock, `store.get`'s miss;
        # nothing is taken for the dirty flag or a condition any more
        assert sites["edit.checkout"]["acquires"] == 5
        assert sites["edit.parse"]["acquires"] == 1
        assert "edit.publish" not in sites
        assert sites["autosave.encode"]["acquires"] >= 1
        assert sites["get.checkout"]["acquires"] == 1     # the checkout
        # the flush path resolves a resident document without the lock
        assert "adopt" in sites and "bank.resolve" not in sites
        # the one clocked lock: the scheduler's own pay one branch
        assert set(snap["locks"]) == {"store.oplog"}
        assert type(httpd.store.scheduler.lock) is witness.WitnessLock
        total_hold = sum(c["hold_s"] for c in sites.values())
        assert 0 < total_hold < 60
    finally:
        _stop(httpd)


def test_an_autosave_pass_holds_the_store_lock_once_at_its_encode(tmp_path):
    """A pass fixes what it saves under ONE hold of the store lock,
    filed under the site `autosave.encode` whatever the number of
    documents, and encodes them outside it; its root says how many it
    encoded where."""
    httpd, addr = _serve(data_dir=str(tmp_path))
    try:
        store = httpd.store
        store.stop_flusher()            # the one pass is this test's
        for i in range(6):
            _edit(addr, f"d{i}", text="hello")
        table = store.obs.phases
        assert _wait_for(lambda: table.snapshot()["phases"].get(
            "http.edit", {}).get("count") == 6)
        store.scheduler.drain()
        assert "autosave.pass" not in table.snapshot()["phases"]
        store.flush(force=True)
        snap = table.snapshot()
        ph, sites = snap["phases"], snap["locks"]["store.oplog"]
        assert ph["autosave.pass"]["count"] == 1
        assert ph["autosave.pass"]["counts"] == {
            "docs": 6, "docs_unlocked": 6, "docs_locked": 0}
        assert sites["autosave.encode"]["acquires"] == 1
        assert 0 < sites["autosave.encode"]["hold_s"] \
            <= ph["autosave.encode"]["sum_s"]
        # no file's write took the lock: no document had a failure
        # streak to end
        assert "autosave.write" not in sites
        assert ph["autosave.pass"]["lock_wait_s"] \
            == ph["autosave.encode"]["lock_wait_s"]
        assert len(list(tmp_path.glob("*.dt"))) == 6
    finally:
        _stop(httpd)


def test_exports_metrics_json_obs_snapshot_and_prometheus(monkeypatch):
    from diamond_types_tpu.tools import server as server_mod
    monkeypatch.setattr(server_mod, "CLOCKED_EVERY", 1)
    httpd, addr = _serve()
    try:
        _edit(addr, "p")
        table = httpd.store.obs.phases
        assert _wait_for(
            lambda: "http.edit" in table.snapshot()["phases"])
        mj = httpd.store.scheduler.metrics_json()
        assert mj["version"] == ServeMetrics.SCHEMA_VERSION
        assert "router_counts" in mj
        assert mj["phases"]["version"] == 1
        assert "http.edit" in mj["phases"]["phases"]
        assert "phases" not in httpd.store.scheduler.metrics.snapshot()
        doc = json.loads(_get(addr, "/metrics"))
        assert doc["obs"]["phases"]["phases"].keys() \
            >= mj["phases"]["phases"].keys()
        text = _get(addr, "/metrics?format=prom").decode("utf8")
        for want in ('dt_phase_seconds_total{phase="http.edit"} ',
                     'dt_phase_total{phase="edit.checkout"} 1',
                     'dt_lock_wait_seconds_total{lock="store.oplog",'
                     'site="edit.checkout"} ',
                     'dt_lock_hold_seconds_total{lock="store.oplog",'
                     'site="edit.checkout"} ',
                     "# TYPE dt_phase_seconds_total counter",
                     "# TYPE dt_lock_hold_seconds_total counter"):
            assert want in text, want
        assert text.count("# TYPE dt_phase_total ") == 1
        assert render_metrics({"obs": {"phases": {}}}) == "\n"
        # a push's rows outside any phase go out the same three ways,
        # once its thread's last line has written them
        assert _wait_for(lambda: "http.thread_cpu"
                         in table.snapshot()["phases"]
                         and "gil.wait" in table.snapshot()["phases"])
        mj = httpd.store.scheduler.metrics_json()["phases"]
        doc = json.loads(_get(addr, "/metrics"))["obs"]["phases"]
        text = _get(addr, "/metrics?format=prom").decode("utf8")
        for name in ("http.thread_start", "http.thread_cpu",
                     "http.thread_after", "gil.wait"):
            assert name in mj["phases"] and name in doc["phases"], name
            assert 'dt_phase_seconds_total{phase="%s"} ' % name in text
        if os.path.isdir("/proc/self/task"):
            assert mj["cpu"].keys() == doc["cpu"].keys() >= {
                "process_s", "exited_s", "accept_loop_s", "native_s"}
    finally:
        _stop(httpd)


def test_two_servers_in_one_process_keep_their_own_rows():
    a, addr_a = _serve()
    b, addr_b = _serve()
    try:
        for _ in range(3):
            _edit(addr_a, "x")
        _edit(addr_b, "y")
        ta, tb = a.store.obs.phases, b.store.obs.phases

        def edits(t):
            return t.snapshot()["phases"].get("http.edit", {}).get("count")
        assert _wait_for(lambda: edits(ta) == 3 and edits(tb) == 1)
        a.store.scheduler.drain()
        b.store.scheduler.drain()
        la = ta.snapshot()["locks"]["store.oplog"]["edit.checkout"]
        lb = tb.snapshot()["locks"]["store.oplog"]["edit.checkout"]
        assert (la["acquires"], lb["acquires"]) == (3, 1)
        assert a.store.lock.clock is ta and b.store.lock.clock is tb
    finally:
        _stop(a, b)


def test_a_server_with_no_bundle_records_nothing_and_serves():
    """A DocStore and a scheduler with no bundle: one branch, as before
    — no clock on the lock, no accept stamps, no `phases` block."""
    from diamond_types_tpu.serve.scheduler import MergeScheduler
    from diamond_types_tpu.tools.server import (DocStore, SyncHandler,
                                                _Server)
    store = DocStore(None)
    sched = MergeScheduler(1, resolve=store.get, engine="host",
                           sync_lock=store.lock)
    store.attach_scheduler(sched)
    handler = type("Handler", (SyncHandler,), {"store": store})
    httpd = _Server(("127.0.0.1", 0), handler)
    httpd.store = store
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        _edit(addr, "n", text="abc")
        assert _get(addr, "/doc/n") == b"abc"
        sched.drain()
        assert store.lock.clock is None
        assert httpd.accepted_at == {}
        assert "phases" not in sched.metrics_json()
        doc = json.loads(_get(addr, "/metrics"))
        assert "obs" not in doc and "phases" not in doc["serve"]
    finally:
        _stop(httpd)


def test_a_sampled_edit_shows_its_parts_under_the_request_trace():
    httpd, addr = _serve(obs_opts={"sample_rate": 1.0})
    try:
        _edit(addr, "t")
        tracer = httpd.store.obs.tracer
        assert _wait_for(lambda: any(
            s["name"] == "http.doc_edit" for s in tracer.spans()))
        root = next(s for s in tracer.spans()
                    if s["name"] == "http.doc_edit")
        got = json.loads(_get(addr, "/debug/trace/" + root["trace"]))
        parts = {s["name"]: s for s in got["spans"]
                 if s["name"] in EDIT_STEPS}
        assert set(parts) == set(EDIT_STEPS)
        assert all(s["parent"] == root["span"] for s in parts.values())
    finally:
        _stop(httpd)
    # an unsampled request pays nothing for it: no span at all
    httpd, addr = _serve(obs_opts={"sample_rate": 0.0})
    try:
        _edit(addr, "u")
        table = httpd.store.obs.phases
        assert _wait_for(
            lambda: "http.edit" in table.snapshot()["phases"])
        assert httpd.store.obs.tracer.spans() == []
    finally:
        _stop(httpd)


def test_a_slow_request_writes_one_event_with_its_parts():
    httpd, addr = _serve()
    try:
        _edit(addr, "s")                    # the document exists
        rec = httpd.store.obs.recorder
        assert not [e for e in rec.dump() if e["kind"] == "slow_request"]
        _hold(httpd.store.lock, 0.3)
        _edit(addr, "s")                    # waits for the lock: >= 250 ms
        assert _wait_for(lambda: any(
            e["kind"] == "slow_request" for e in rec.dump()))
        evs = [e for e in rec.dump() if e["kind"] == "slow_request"]
        assert len(evs) == 1
        ev = evs[0]
        assert ev["endpoint"] == "http.edit" and ev["doc"] == "s"
        assert ev["total_ms"] >= 250 and ev["lock_wait_ms"] >= 250
        assert set(EDIT_STEPS) <= set(ev["parts"])
        assert "http.edit.other" in ev["parts"]
        waited = max(ev["parts"], key=lambda k: ev["parts"][k]
                     .get("lock_wait_ms", 0.0))
        # a resident document comes without the lock: the push's one
        # wait is for the hold that validates and adds its ops
        assert waited == "edit.checkout"
        assert ev["parts"][waited]["lock_wait_ms"] >= 250
        assert ev["handler_ms"] == pytest.approx(
            sum(p["ms"] for p in ev["parts"].values()), abs=0.05)
        assert httpd.store.obs.phases.snapshot()["slow_requests"] == 1
        # a second one inside the same second is counted, not written:
        # a saturated server must not flush the recorder's ring
        _hold(httpd.store.lock, 0.3)
        _edit(addr, "s")
        table = httpd.store.obs.phases
        assert _wait_for(
            lambda: table.snapshot()["slow_requests"] == 2)
        assert len([e for e in rec.dump()
                    if e["kind"] == "slow_request"]) == 1
        assert ev["unwritten_before"] == 0
        # but one twice as slow as the last written is: the slowest of
        # a burst must be in the ring
        _hold(httpd.store.lock, 0.75)
        _edit(addr, "s")
        assert _wait_for(lambda: len(
            [e for e in rec.dump() if e["kind"] == "slow_request"]) == 2)
        last = [e for e in rec.dump() if e["kind"] == "slow_request"][-1]
        assert last["total_ms"] >= 700 and last["unwritten_before"] == 1
    finally:
        _stop(httpd)


def test_a_host_engine_server_never_imports_jax_for_this():
    code = (
        "import sys, json, threading, time, urllib.request\n"
        "from diamond_types_tpu.tools.server import serve\n"
        "h = serve(port=0, engine='host', serve_shards=1)\n"
        "threading.Thread(target=h.serve_forever, daemon=True).start()\n"
        "req = urllib.request.Request(\n"
        "    'http://127.0.0.1:%d/doc/j/edit' % h.server_address[1],\n"
        "    data=json.dumps({'agent': 'a', 'version': None, 'ops': [\n"
        "        {'kind': 'ins', 'pos': 0, 'text': 'x'}]}).encode())\n"
        "urllib.request.urlopen(req, timeout=10).read()\n"
        "h.store.scheduler.drain()\n"
        "for _ in range(500):        # a root closes after its response\n"
        "    rows = h.store.obs.phases.snapshot()['phases']\n"
        "    if 'http.edit' in rows: break\n"
        "    time.sleep(0.01)\n"
        "h.shutdown(); h.server_close()\n"
        "assert 'edit.checkout' in rows and 'sched.flush' in rows, rows\n"
        "assert 'jax' not in sys.modules\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


# ---- the profiler's clock ----------------------------------------------------

def test_with_a_profiler_session_the_names_are_in_the_capture(tmp_path):
    """"Tracing on" is "a profiler session is running": the phases and
    the lock waits land on host threads of the profiler's own trace."""
    import glob

    import jax
    from jax.profiler import ProfileData
    table = PhaseTable()
    lk = make_lock("store.oplog", "oplog", clocked=True)
    lk.attach_clock(table)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _hold(lk, 0.02)
        with table.phase("http.edit") as root:
            root.step("edit.checkout")
            with lk:
                pass
            with phase("plan.tail"):
                pass
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert files
    names = {ev.name for plane in ProfileData.from_file(files[0]).planes
             for line in plane.lines for ev in line.events}
    assert {"http.edit", "edit.checkout", "plan.tail",
            "lock_wait:store.oplog"} <= names
    # and the counters are the same phases
    assert table.snapshot()["phases"]["edit.checkout"]["lock_wait_s"] > 0.01


# ---- the replay program's names ----------------------------------------------

def _plain_replay_body(mi):
    """`make_replay_body` as it was before it had scopes or a name."""
    import jax
    import jax.numpy as jnp

    from diamond_types_tpu.tpu.batch import _apply_ops_batched

    def run(docs, lens, pos, dlen, ilen, chars):
        bad = (dlen > mi) | (ilen > mi)
        dlen = jnp.where(bad, 0, dlen)
        ilen = jnp.where(bad, 0, ilen)
        bad_doc = jnp.any(bad, axis=1)

        def step(carry, op):
            d, l, p, dl, il, c = carry + op
            d, l = _apply_ops_batched(d, l, p, dl, il, c)
            return (d, l), None

        ops = (jnp.swapaxes(pos, 0, 1), jnp.swapaxes(dlen, 0, 1),
               jnp.swapaxes(ilen, 0, 1), jnp.swapaxes(chars, 0, 1))
        (docs, lens), _ = jax.lax.scan(step, (docs, lens), ops)
        return docs, jnp.where(bad_doc, -1, lens)

    return run


def test_scopes_change_names_only():
    import jax
    import jax.numpy as jnp

    from diamond_types_tpu.tpu import flush_fuse
    b, n, mi, cap = 4, 8, 4, 128
    rng = np.random.default_rng(5)
    lens = rng.integers(20, 60, size=b).astype(np.int32)
    docs = np.zeros((b, cap), np.int32)
    for i in range(b):
        docs[i, :lens[i]] = rng.integers(97, 123, size=lens[i])
    pos = rng.integers(0, 20, size=(b, n)).astype(np.int32)
    ilen = rng.integers(0, mi + 1, size=(b, n)).astype(np.int32)
    dlen = np.where(ilen == 0, rng.integers(1, mi + 1, size=(b, n)),
                    0).astype(np.int32)
    dlen[1, 3] = mi + 3                     # poisons document 1 only
    chars = rng.integers(97, 123, size=(b, n, mi)).astype(np.int32)
    args = [jnp.asarray(a) for a in (docs, lens, pos, dlen, ilen, chars)]
    want_docs, want_lens = jax.jit(_plain_replay_body(mi))(*args)
    keys = set(flush_fuse._fused_jit_cache)
    fn = flush_fuse._fused_fn(b, n, mi, cap)
    assert set(flush_fuse._fused_jit_cache) == keys | {(b, n, mi, cap)}
    text = fn.lower(*args).as_text(debug_info=True)
    got_docs, got_lens = fn(*[jnp.asarray(a) for a in
                              (docs, lens, pos, dlen, ilen, chars)])
    assert np.array_equal(np.asarray(got_docs), np.asarray(want_docs))
    assert np.array_equal(np.asarray(got_lens), np.asarray(want_lens))
    assert int(got_lens[1]) == -1 and (np.asarray(got_lens)[[0, 2, 3]]
                                       >= 0).all()
    assert "jit_dt_fused_replay" in text
    for scope in ("dt.replay.sanitize", "dt.replay.scan",
                  "dt.replay.apply"):
        assert scope in text, scope
    assert flush_fuse.make_replay_body(mi).__name__ == "dt_fused_replay"


@pytest.mark.parametrize("engine", ["native", "python"])
def test_plan_tail_and_fused_replay_report_their_steps(engine, monkeypatch):
    """Under an open root the replay rungs' phases record; their
    signatures (the harness patches them by name) are what they were.
    The transform runs on the oplog's native mirror, or, with no native
    engine, as the lazy Python walk."""
    import inspect

    if engine == "python":
        monkeypatch.setenv("DT_TPU_NO_NATIVE", "1")

    from diamond_types_tpu.text.oplog import OpLog
    from diamond_types_tpu.tpu import flush_fuse
    assert list(inspect.signature(flush_fuse.fused_replay).parameters) \
        == ["sessions", "plans"]
    assert list(inspect.signature(
        flush_fuse.FusedDocSession.plan_tail).parameters) == ["self"]
    ol = OpLog()
    agent = ol.get_or_create_agent_id("a")
    ol.add_insert_at(agent, [], 0, "hello world")
    sess = flush_fuse.FusedDocSession(ol, cap=64, max_ins=4)
    ol.add_insert_at(agent, list(ol.version), 5, ", dear")
    bare = sess.plan_tail()                 # no root: records nowhere
    table = PhaseTable()
    with table.phase("sched.flush"):
        plan = sess.plan_tail()
        assert plan.n_ops == bare.n_ops == 2 and plan.new_len == 17
        ok, _dev = flush_fuse.fused_replay([sess], [plan])
    assert ok == [True] and sess.text() == "hello, dear world"
    ph = table.snapshot()["phases"]
    # the native transform is one call. The Python walk's `plan.xf`
    # closes twice a plan: the graph's diff, then the lazy walk's
    # seconds, taken out of `plan.rows`
    assert ph["plan.xf"]["count"] == (1 if engine == "native" else 2)
    for name in ("plan.rows", "plan.pack", "replay.pack",
                 "replay.stack", "replay.dispatch", "replay.fence",
                 "replay.adopt"):
        assert ph[name]["count"] == 1, name
    # which engine walked and, for the native one, how its mirror
    # followed the oplog: the walk outside the root had synced it
    # and (since PR 36) the plan rows it made, both of them pieces of
    # an insert longer than `max_ins`; the replay says how many scan
    # steps its call was padded to (2, or a warm class's the steer
    # table snapped it to)
    assert ph.pop("plan.tail")["counts"] == dict(
        {"xf_native": 1, "mirror_appended": 0, "mirror_rebuilt": 0,
         "mirror_busy_waits": 0}
        if engine == "native" else {"xf_python": 1},
        rows=2, block_rows=2)
    assert ph.pop("replay")["counts"]["scan_steps"] >= 2
    assert all("counts" not in row for row in ph.values())
    ph = table.snapshot()["phases"]
    for root, steps in (("plan.tail", ("plan.xf", "plan.rows", "plan.pack")),
                        ("replay", ("replay.pack", "replay.stack",
                                    "replay.dispatch", "replay.fence",
                                    "replay.adopt"))):
        assert ph[root]["sum_s"] == pytest.approx(
            sum(ph[s]["sum_s"] for s in steps)
            + ph[root + ".other"]["sum_s"], abs=1e-6)
    bundle = Observability()
    assert bundle.snapshot()["phases"]["phases"] == {}
