"""Tests for the sharded multi-document merge scheduler (serve/).

Fast CPU-only tier-1 tests: the device-engine cases run on simulated
shards (conftest pins JAX_PLATFORMS=cpu with an 8-device virtual mesh)
and share session shapes across docs so the whole fleet reuses one jit
cache entry per micro-tape length — the e2e parity test stays seconds,
not minutes.
"""

import json
import threading
import urllib.request

import pytest

from diamond_types_tpu.serve import (AdmissionQueue, Backpressure,
                                     MergeScheduler, SessionBank,
                                     ServeMetrics, ShardRouter,
                                     shape_bucket)
from diamond_types_tpu.text.oplog import OpLog

pytestmark = pytest.mark.serve


def _mk_oplog(doc_id: str, text: str = "hello") -> OpLog:
    ol = OpLog()
    ol.doc_id = doc_id
    agent = ol.get_or_create_agent_id("a")
    if text:
        ol.add_insert_at(agent, [], 0, text)
    return ol


# ---- router ---------------------------------------------------------------

def test_router_deterministic_across_instances():
    ids = [f"doc{i}" for i in range(64)]
    r1, r2 = ShardRouter(8), ShardRouter(8)
    assert [r1.shard_of(d) for d in ids] == [r2.shard_of(d) for d in ids]
    # assignment is pure in doc_id: repeated queries never move a doc
    assert [r1.shard_of(d) for d in ids] == [r1.shard_of(d) for d in ids]


def test_router_rough_balance():
    r = ShardRouter(4)
    for i in range(400):
        r.assign(f"doc{i:04d}")
    counts = r.counts()
    assert sum(counts) == 400
    # rendezvous hashing: every shard takes a meaningful share
    assert min(counts) > 400 / 4 * 0.5
    assert max(counts) < 400 / 4 * 1.6


def test_router_rebalance_moves_only_required_subset():
    r = ShardRouter(4)
    ids = [f"doc{i:04d}" for i in range(200)]
    before = {d: r.assign(d) for d in ids}
    moved = r.rebalance(3)
    # rendezvous property: exactly the docs whose TOP shard was removed
    # move; everyone else keeps their assignment
    for d in ids:
        if d in moved:
            old, new = moved[d]
            assert old == before[d] and old == 3 and new != 3
        else:
            assert r.shard_of(d) == before[d]
    assert 0 < len(moved) < len(ids)
    # growing back re-adopts the original assignment (pure hash)
    r2 = ShardRouter(4)
    assert all(r2.shard_of(d) == before[d] for d in ids)


def test_router_rebalance_pinned_assignments():
    """Pinned blake2b rendezvous placements: these exact values must
    hold in every process and across PRs — replication's doc-ownership
    (replicate/ownership.py) derives host placement from the same
    scoring, so silent drift here would strand leases cluster-wide."""
    docs = [f"doc-{i}" for i in range(12)]
    pinned = {
        8: [5, 7, 1, 5, 6, 3, 6, 0, 7, 7, 6, 5],
        5: [2, 3, 1, 2, 3, 3, 0, 0, 4, 0, 2, 4],
        3: [2, 0, 1, 2, 0, 0, 0, 0, 0, 0, 2, 0],
    }
    for n, want in pinned.items():
        assert [ShardRouter(n).shard_of(d) for d in docs] == want
    # minimal rendezvous delta on shrink: exactly the docs whose top
    # shard was removed (8-shard placement >= 5) move, nobody else
    r = ShardRouter(8)
    for d in docs:
        r.assign(d)
    moved = r.rebalance(5)
    assert sorted(moved) == sorted(d for d, s in zip(docs, pinned[8])
                                   if s >= 5)
    for d, (old, new) in moved.items():
        assert old == pinned[8][docs.index(d)]
        assert new == pinned[5][docs.index(d)]
    for d in docs:
        assert r.assignments[d] == pinned[5][docs.index(d)]
    # growing back is a clean inverse: the same set returns home
    moved_back = r.rebalance(8)
    assert sorted(moved_back) == sorted(moved)
    assert [r.assignments[d] for d in docs] == pinned[8]


# ---- admission queue ------------------------------------------------------

def test_shape_bucket_pow2():
    assert [shape_bucket(n) for n in (0, 1, 2, 3, 4, 5, 9, 64)] == \
        [1, 1, 2, 4, 4, 8, 16, 64]


def test_flush_trigger_size():
    q = AdmissionQueue(1, flush_docs=3, flush_deadline_s=10.0)
    t = 100.0
    q.submit(0, "a", 2, t)
    q.submit(0, "b", 2, t)
    assert q.due(t) == []          # 2 of 3 docs, deadline far away
    q.submit(0, "c", 2, t)
    assert q.due(t) == [(0, 2, "size")]
    items = q.take(0, 2)
    assert [i.doc_id for i in items] == ["a", "b", "c"]   # FIFO
    assert q.due(t) == [] and q.depth(0) == 0


def test_flush_trigger_deadline():
    q = AdmissionQueue(1, flush_docs=8, flush_deadline_s=0.05)
    t = 100.0
    q.submit(0, "a", 1, t)
    assert q.due(t + 0.04) == []
    assert q.due(t + 0.06) == [(0, 1, "deadline")]


def test_coalescing_keeps_deadline_and_depth():
    q = AdmissionQueue(1, max_pending=4, flush_docs=8,
                       flush_deadline_s=0.05)
    t = 100.0
    b = q.submit(0, "a", 1, t)
    assert b == 1
    # re-submit coalesces: depth unchanged, ops accumulate, the entry
    # migrates to the larger shape bucket, the ORIGINAL clock survives
    b = q.submit(0, "a", 3, t + 0.03)
    assert b == 4 and q.depth(0) == 1
    assert q.due(t + 0.06) == [(0, 4, "deadline")]
    (item,) = q.take(0, 4)
    assert item.n_ops == 4 and item.enqueued_at == t


def test_backpressure_bounds_depth():
    q = AdmissionQueue(1, max_pending=3, flush_docs=100,
                       flush_deadline_s=0.05)
    t = 100.0
    for d in ("a", "b", "c"):
        q.submit(0, d, 1, t)
    with pytest.raises(Backpressure) as ei:
        q.submit(0, "d", 1, t)
    assert ei.value.retry_after > 0
    assert q.depth(0) == 3          # rejected submit added nothing
    q.submit(0, "a", 1, t)          # coalescing is NOT new depth
    assert q.depth(0) == 3


def test_scheduler_reject_surfaces_retry_after_and_bound_holds():
    ols = {f"d{i}": _mk_oplog(f"d{i}") for i in range(12)}
    sched = MergeScheduler(1, resolve=ols.__getitem__, engine="host",
                           max_pending=4, flush_docs=100,
                           flush_deadline_s=60.0)
    results = [sched.submit(d) for d in ols]
    accepted = [r for r in results if r["accepted"]]
    rejected = [r for r in results if not r["accepted"]]
    assert len(accepted) == 4 and len(rejected) == 8
    assert all(r["retry_after"] > 0 for r in rejected)
    snap = sched.metrics_json()
    assert snap["totals"]["rejects"] == 8
    assert snap["queue_bound_violations"] == 0
    assert snap["max_depth_seen"] <= 4
    # after a drain the rejected docs resubmit fine
    sched.drain()
    assert all(sched.submit(d)["accepted"] for d in list(ols)[:4])


# ---- session bank ---------------------------------------------------------

def test_bank_lru_eviction_accounting():
    m = ServeMetrics(1, flush_docs=4, max_pending=16)
    bank = SessionBank(0, max_sessions=2, engine="host", metrics=m)
    ols = {d: _mk_oplog(d) for d in ("a", "b", "c")}
    for d in ("a", "b"):
        bank.sync_doc(d, ols[d])
    assert set(bank.sessions) == {"a", "b"}
    bank.sync_doc("a", ols["a"])            # refresh a's LRU slot
    bank.sync_doc("c", ols["c"])            # evicts b, the LRU victim
    assert set(bank.sessions) == {"a", "c"}
    assert m.shard[0]["evictions"] == 1 and m.shard[0]["builds"] == 3
    # the evicted doc rebuilds on its next merge
    bank.sync_doc("b", ols["b"])
    assert m.shard[0]["builds"] == 4 and m.shard[0]["evictions"] == 2
    # every resident session still holds its document
    for d, sess in bank.sessions.items():
        assert sess.text() == ols[d].checkout_tip().snapshot()


def test_bank_slot_budget_eviction_device():
    # device-engine bank with a slot budget sized for ~1 tiny session:
    # the second build must evict the first (capacity, not count)
    m = ServeMetrics(1, flush_docs=4, max_pending=16)
    bank = SessionBank(0, max_sessions=8, engine="device", metrics=m)
    ols = {d: _mk_oplog(d) for d in ("a", "b")}
    bank.sync_doc("a", ols["a"])
    fp = bank.footprint_slots()
    assert fp > 0                    # footprint accounting is live
    bank.max_slots = int(fp * 1.5)   # room for one, not two
    bank.sync_doc("b", ols["b"])
    assert set(bank.sessions) == {"b"}
    assert m.shard[0]["evictions"] == 1
    assert bank.read_row(bank.sessions["b"])[0] == "hello"


def test_bank_host_fallback_on_fence_failure(monkeypatch):
    """A DATA fault (poisoned / drifting replay length) evicts the
    session to the host oracle and is counted as a host fallback."""
    from diamond_types_tpu.tpu.flush_fuse import FenceFailure
    m = ServeMetrics(1, flush_docs=4, max_pending=16)
    bank = SessionBank(0, engine="device", metrics=m)
    ol = _mk_oplog("a")

    class Boom:
        def sync(self):
            raise FenceFailure("poisoned length")

        def footprint_slots(self):
            return 0

    monkeypatch.setattr(bank, "_build", lambda doc_id, oplog: Boom())
    r = bank.sync_doc("a", ol)
    assert r["engine"] == "host" and "error" in r
    assert m.shard[0]["host_fallbacks"] == 1
    assert m.shard[0]["device_errors"] == 0
    assert bank.sessions == {}       # broken session evicted


def test_bank_device_failure_is_not_a_fallback(monkeypatch):
    """A compiler or runtime failure is never treated as a data fault:
    it is counted, recorded with its text, and raised."""
    from diamond_types_tpu.obs.recorder import FlightRecorder
    m = ServeMetrics(1, flush_docs=4, max_pending=16)
    bank = SessionBank(0, engine="device", metrics=m)
    bank.recorder = FlightRecorder()
    ol = _mk_oplog("a")

    class Boom:
        def sync(self):
            raise RuntimeError("Mosaic failed to compile")

        def footprint_slots(self):
            return 0

    monkeypatch.setattr(bank, "_build", lambda doc_id, oplog: Boom())
    with pytest.raises(RuntimeError, match="Mosaic"):
        bank.sync_doc("a", ol)
    assert m.shard[0]["host_fallbacks"] == 0
    assert m.shard[0]["device_errors"] == 1
    ev = [e for e in bank.recorder.dump() if e["kind"] == "device_error"]
    assert ev and ev[0]["rung"] == "per_doc"
    assert "Mosaic failed to compile" in ev[0]["error"]

    def no_build(doc_id, oplog):
        raise RuntimeError("no device memory")

    bank.sessions.clear()
    monkeypatch.setattr(bank, "_build", no_build)
    with pytest.raises(RuntimeError, match="no device memory"):
        bank.sync_doc("a", ol)
    assert m.shard[0]["device_errors"] == 2
    assert [e["rung"] for e in bank.recorder.dump()
            if e["kind"] == "device_error"] == ["per_doc", "build"]


def test_reads_counted_by_source():
    """A read at the tip says where it was answered from: the resident
    device session, brought to the oplog's tip where it was behind; the
    host where there is no session, or a host engine."""
    ols = {"a": _mk_oplog("a")}
    sched = MergeScheduler(1, resolve=ols.__getitem__, engine="device",
                           flush_workers=False)
    m, ol = sched.metrics, ols["a"]
    assert sched.text("a") == "hello"                # no session yet
    assert (m.shard[0]["reads_from_host"],
            m.shard[0]["reads_from_device"]) == (1, 0)
    sched.banks[0].sync_doc("a", ol)
    assert sched.text("a") == "hello"                # resident, synced
    assert (m.shard[0]["reads_from_host"],
            m.shard[0]["reads_from_device"]) == (1, 1)
    a = ol.get_or_create_agent_id("alice")
    ol.add_insert(a, 5, "!")
    assert sched.read_tip("a")[0] == "hello!"        # behind: synced first
    assert sched.banks[0].sessions["a"].synced_to == len(ol)
    assert (m.shard[0]["reads_from_host"],
            m.shard[0]["reads_from_device"]) == (1, 2)
    host = MergeScheduler(1, resolve=ols.__getitem__, engine="host",
                          flush_workers=False)
    host.banks[0].sync_doc("a", ol)
    assert host.read_tip("a") is None and host.text("a") == "hello!"
    assert host.metrics.shard[0]["reads_from_host"] == 2


# ---- one flush path: what selects the replay, what is refused -----------------

@pytest.mark.parametrize("kw", ["device_plan", "pallas", "fused",
                                "session_opts"])
def test_retired_flush_switches_are_refused(kw):
    """The switches that selected the retired flush paths are no
    keywords of the scheduler or the bank: a configuration that still
    states one fails at boot (`serve()` hands `sched_opts` straight to
    `MergeScheduler`) and never runs another path silently."""
    value = {} if kw == "session_opts" else True
    with pytest.raises(TypeError, match=kw):
        MergeScheduler(1, resolve={}.__getitem__, engine="host",
                       **{kw: value})
    with pytest.raises(TypeError, match=kw):
        SessionBank(0, engine="host", **{kw: value})


@pytest.mark.parametrize("layout", ["per_shard", "mesh_window"])
def test_the_layout_decides_the_rung(layout, monkeypatch):
    """Counting stand-ins in both replay functions' places: a one-shard
    scheduler replays through `fused_replay` only, a four-shard
    `mesh_window` one through `mesh_fused_replay` only; either way the
    device rows are the oplogs' text."""
    from diamond_types_tpu.parallel import mesh as pm
    from diamond_types_tpu.tpu import flush_fuse as ff
    calls = {"fused": 0, "mesh": 0}
    real_fused, real_mesh = ff.fused_replay, pm.mesh_fused_replay

    def fused(sessions, plans):
        calls["fused"] += 1
        return real_fused(sessions, plans)

    def mesh(m, sessions, plans):
        calls["mesh"] += 1
        return real_mesh(m, sessions, plans)

    monkeypatch.setattr(ff, "fused_replay", fused)
    monkeypatch.setattr(pm, "mesh_fused_replay", mesh)
    mesh_window = layout == "mesh_window"
    ols = {f"d{i}": _mk_oplog(f"d{i}") for i in range(8)}
    sched = MergeScheduler(4 if mesh_window else 1,
                           resolve=ols.__getitem__, engine="device",
                           fused_opts={"cap": 256, "max_ins": 4},
                           flush_docs=8, flush_deadline_s=10.0,
                           flush_workers=False, mesh_window=mesh_window)
    for rnd in range(3):         # round 0 builds the sessions at the tip
        for d, ol in ols.items():
            ol.add_insert(ol.get_or_create_agent_id("a"), 0, f"r{rnd} ")
            assert sched.submit(d, n_ops=1)["accepted"]
        sched.pump(force=True)
    assert calls["mesh" if mesh_window else "fused"] >= 2
    assert calls["fused" if mesh_window else "mesh"] == 0
    sessions = [s for b in sched.banks for s in b.sessions.values()]
    assert len(sessions) == len(ols)
    assert all(isinstance(s, ff.FusedDocSession) for s in sessions)
    for d, ol in ols.items():
        assert sched.text(d) == ol.checkout_tip().snapshot()
    t = sched.metrics_json()["totals"]
    assert t["reads_from_device"] == len(ols) and t["reads_from_host"] == 0


# ---- scheduler (host engine) ----------------------------------------------

def test_scheduler_host_end_to_end_with_rebalance():
    ols = {f"d{i}": _mk_oplog(f"d{i}", "") for i in range(10)}
    agents = {d: ol.get_or_create_agent_id("w") for d, ol in ols.items()}
    sched = MergeScheduler(4, resolve=ols.__getitem__, engine="host",
                           flush_docs=3, flush_deadline_s=0.01)
    for step in range(3):
        for i, (d, ol) in enumerate(ols.items()):
            ol.add_insert_at(agents[d], list(ol.version), 0,
                             f"{d}:{step} ")
            assert sched.submit(d)["accepted"]
        sched.pump(force=True)
    moved = sched.rebalance(3)
    assert all(old == 3 for (old, _new) in moved.values())
    for d, ol in ols.items():
        assert sched.text(d) == ol.checkout_tip().snapshot()
    snap = sched.metrics_json()
    assert snap["totals"]["flushes"] > 0
    assert snap["queue_bound_violations"] == 0
    assert sum(snap["router_counts"]) == len(ols)
    assert all(s != 3 for s in
               (sched.router.shard_of(d) for d in ols))


def test_scheduler_read_flushes_pending():
    ol = _mk_oplog("d0", "abc")
    agent = ol.get_or_create_agent_id("w")
    sched = MergeScheduler(2, resolve=lambda d: ol, engine="device",
                           flush_docs=100, flush_deadline_s=60.0)
    assert sched.submit("d0")["accepted"]
    sched.drain()                       # resident, at the tip
    ol.add_insert_at(agent, list(ol.version), 0, "xyz")
    assert sched.submit("d0")["accepted"]
    # no pump ran — the read itself must flush the doc's bucket
    assert sched.text("d0") == "xyzabc"
    snap = sched.metrics_json()
    assert snap["flush_reasons"].get("read", 0) == 1
    assert snap["totals"]["flushed_docs"] == 2
    assert snap["totals"]["reads_from_device"] == 1


# ---- one batch a shard, and a worker that paces its host work -------------

def test_a_busy_shard_keeps_its_bucket_queued_and_coalescing():
    """While a shard's worker has a batch, `pump()` leaves that shard's
    due buckets in the queue (re-submits coalesce there, no second
    batch goes stale behind the first); it takes them once the worker
    is free, and a forced pump takes them at once."""
    ols = {f"d{i}": _mk_oplog(f"d{i}") for i in range(3)}
    sched = MergeScheduler(1, resolve=ols.__getitem__, engine="host",
                           flush_docs=1, flush_deadline_s=60.0)
    held, entered = threading.Event(), threading.Event()
    flush_items = sched._flush_items

    def slow_flush(shard, reason, items):
        entered.set()
        assert held.wait(10)
        return flush_items(shard, reason, items)
    sched._flush_items = slow_flush
    assert sched.submit("d0")["accepted"] and sched.pump() == 1
    assert entered.wait(10) and sched._busy == [1]
    for _ in range(3):
        assert sched.submit("d1")["accepted"]
    assert sched.pump() == 0 and sched.queue.depth(0) == 1
    assert sched.metrics_json()["totals"]["coalesced"] == 2
    assert sched.submit("d2")["accepted"]
    assert sched.pump(force=True) == 2 and sched.queue.depth(0) == 0
    held.set()
    sched.drain()
    assert sched._busy == [0]
    assert sched.submit("d1")["accepted"] and sched.pump() == 1
    sched.drain()
    sched.stop_workers()
    assert sched.metrics_json()["totals"]["flushed_docs"] == 4


@pytest.mark.parametrize("reason,wall_s,device_s,pause", [
    ("size", 0.020, 0.0, 0.140),        # all host work: seven parts out
    ("deadline", 0.030, 0.010, 0.130),  # the device's wait counts as out
    ("size", 0.300, 0.270, None),       # device-bound: never held back
    ("size", 2.000, 0.0, 0.350),        # a build or a compile: capped
    ("force", 0.020, 0.0, None),        # somebody waits for it: no pause
])
def test_a_flush_worker_paces_its_host_work(reason, wall_s, device_s,
                                            pause):
    from diamond_types_tpu.serve import scheduler as sched_mod
    assert sched_mod.FLUSH_HOST_SHARE == 8
    sched = MergeScheduler(1, resolve=lambda d: _mk_oplog(d),
                           engine="host")
    waits = []

    class Stop:
        def wait(self, timeout):
            waits.append(timeout)
    sched._pump_stop = Stop()
    sched._flush_items = lambda shard, why, items: (wall_s, device_s)
    sched._dispatch(0, reason, ["item"])
    sched._wait_idle()
    sched.stop_workers()
    assert waits == ([] if pause is None else [pytest.approx(pause)])


# ---- e2e parity on simulated shards (the acceptance gate) -----------------

def test_serve_bench_device_parity_4_shards():
    from diamond_types_tpu.serve.driver import run_serve_bench
    report = run_serve_bench(shards=4, docs=8, txns=8, engine="device",
                             mode="trace", flush_docs=4,
                             flush_deadline_s=0.02)
    assert report["parity_ok"], report["parity_mismatches"]
    m = report["metrics"]
    assert m["batch_occupancy"] > 0
    assert m["queue_bound_violations"] == 0
    assert m["totals"]["flushes"] > 0
    # work really spread across the shard fleet
    active = [s for s in m["per_shard"] if s["syncs"] > 0]
    assert len(active) >= 2
    # the device engine actually served the merges (CPU-simulated chip)
    assert m["host_fallback_ratio"] < 0.5


def test_serve_bench_concurrent_mode_host():
    from diamond_types_tpu.serve.driver import run_serve_bench
    report = run_serve_bench(shards=4, docs=6, txns=10, engine="host",
                             mode="concurrent", place_on_devices=False)
    assert report["parity_ok"], report["parity_mismatches"]
    assert report["total_ops"] > 0
    assert report["metrics"]["queue_bound_violations"] == 0


# ---- server + cli integration ---------------------------------------------

def test_docstore_scheduler_integration(tmp_path):
    from diamond_types_tpu.tools.server import serve
    httpd = serve(port=0, data_dir=str(tmp_path), engine="host", serve_shards=2)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        base = f"http://127.0.0.1:{port}"

        def post(path, obj):
            req = urllib.request.Request(base + path,
                                         data=json.dumps(obj).encode())
            return json.loads(urllib.request.urlopen(req).read())

        v = post("/doc/d1/edit", {"agent": "a1", "version": [], "ops":
                                  [{"kind": "ins", "pos": 0,
                                    "text": "hello"}]})
        post("/doc/d1/edit", {"agent": "a1", "version": v["version"],
                              "ops": [{"kind": "ins", "pos": 5,
                                       "text": " world"}]})
        sched = httpd.store.scheduler
        assert sched is not None
        sched.drain()
        assert sched.text("d1") == "hello world"
        m = json.loads(urllib.request.urlopen(base + "/metrics").read())
        assert m["serve"]["totals"]["submits"] == 2
        assert m["serve"]["queue_bound_violations"] == 0
    finally:
        httpd.shutdown()
        httpd.server_close()
        t.join(timeout=5)


def test_cli_serve_bench_dry_run(capsys):
    from diamond_types_tpu.tools import cli
    assert cli.main(["serve-bench", "--dry-run", "--json"]) == 0
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["parity_ok"]
    assert report["config"]["engine"] == "host"
