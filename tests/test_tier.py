"""Tiered doc residency: crash-safe snapshot store + hydration.

Covers the tiered_residency PR top to bottom:
  * crash-mid-compaction recovery at EVERY fsync point for both
    durable formats (PagedDocFile's 3-step tmp/replace/dirsync swap,
    DocFile's baseline-then-WAL-reset ordering) — old-or-new content,
    never torn, no stale rewrite left behind, still appendable;
  * TieredStore: per-doc compaction policy, typed DocQuarantined
    containment (one rotten home never poisons a neighbor's load);
  * Hydrator: timeout -> jittered retry -> success, sync-resolve
    exhaustion quarantine, flush-gate classification (warm keeps,
    quarantined drops, cold defers), defer-budget give-up;
  * eviction-to-snapshot parity: randomized churn through an
    undersized warm tier byte-compares against an always-resident
    control oplog (the eviction path must never drop an appended op);
  * SessionBank eviction: pending-op count + snapshot routing in the
    flight-recorder event;
  * ServeMetrics v7: hydration counter block + cold-start histogram,
    prom rendering of the dt_serve_hydration_* families;
  * the storage soak (storage/soak.py) as a small seeded smoke with
    every fault class on.
"""

import os
import random
import time
from types import SimpleNamespace

import pytest

from diamond_types_tpu.serve.hydrate import Hydrator
from diamond_types_tpu.serve.metrics import HYDRATION_KEYS, ServeMetrics
from diamond_types_tpu.storage.pages import PagedDocFile
from diamond_types_tpu.storage.store import DocFile
from diamond_types_tpu.storage.tier import (DocQuarantined, StorageFaults,
                                            TieredStore)
from diamond_types_tpu.text.oplog import OpLog

pytestmark = pytest.mark.storage


class _Boom(Exception):
    pass


def _crash_at(point):
    def hook(p):
        if p == point:
            raise _Boom(p)
    return hook


def _mk_oplog(text_parts, agent="a"):
    ol = OpLog()
    a = ol.get_or_create_agent_id(agent)
    pos = 0
    for part in text_parts:
        ol.add_insert(a, pos, part)
        pos += len(part)
    return ol


# ---- crash-mid-compaction (satellite 1) ----------------------------------

@pytest.mark.parametrize("point",
                         ["snapshot_written", "replaced", "dir_synced"])
def test_paged_compact_crash_recovers_old_or_new(tmp_path, point):
    path = str(tmp_path / "doc.pages")
    f = PagedDocFile(path)
    f.append_from(_mk_oplog(["hello ", "world ", "again "]))
    want = f.oplog.checkout_tip().snapshot()
    with pytest.raises(_Boom):
        f.compact(_crash=_crash_at(point))
    f.close()
    # never a torn mix, never a stale rewrite left to be appended onto
    assert not os.path.exists(path + ".compact")
    g = PagedDocFile(path)
    assert g.oplog.checkout_tip().snapshot() == want
    # the recovered file is a working home, not a read-only husk
    more = _mk_oplog(["hello ", "world ", "again ", "post-crash"])
    g.append_from(more)
    g.close()
    h = PagedDocFile(path)
    assert h.oplog.checkout_tip().snapshot() \
        == more.checkout_tip().snapshot()
    h.close()


@pytest.mark.parametrize("point", ["baseline_written", "wal_reset"])
def test_docfile_compact_crash_recovers(tmp_path, point):
    path = str(tmp_path / "doc.dt")
    f = DocFile(path)
    f.append_from(_mk_oplog(["alpha ", "beta "]))
    want = f.oplog.checkout_tip().snapshot()
    with pytest.raises(_Boom):
        f.compact(_crash=_crash_at(point))
    f.close()
    # a crash between baseline write and WAL reset replays the stale
    # WAL onto the new baseline; idempotent decode dedups it
    g = DocFile(path)
    assert g.oplog.checkout_tip().snapshot() == want
    g.close()


def test_stale_compact_rewrite_is_removed_on_open(tmp_path):
    path = str(tmp_path / "doc.pages")
    f = PagedDocFile(path)
    f.append_from(_mk_oplog(["content"]))
    f.close()
    with open(path + ".compact", "wb") as s:
        s.write(b"half-built rewrite from a dead process")
    g = PagedDocFile(path)
    assert not os.path.exists(path + ".compact")
    assert g.oplog.checkout_tip().snapshot() == "content"
    g.close()


# ---- TieredStore ---------------------------------------------------------

def test_tier_roundtrip_and_compaction_policy(tmp_path):
    store = TieredStore(str(tmp_path), compact_patch_records=3)
    ol = OpLog()
    a = ol.get_or_create_agent_id("w")
    for i in range(5):
        ol.add_insert(a, 0, f"r{i}.")
        store.save("d", ol)
    got = store.load("d")
    assert got is not ol       # a FRESH oplog the caller owns
    assert got.checkout_tip().snapshot() \
        == ol.checkout_tip().snapshot()
    c = store.counters()
    assert c["saves"] == 5 and c["compactions"] >= 1
    # a doc that never existed hydrates as a brand-new empty oplog
    assert len(store.load("never-saved")) == 0
    assert store.counters()["fresh_docs"] == 1


def test_tier_quarantine_is_per_doc(tmp_path):
    store = TieredStore(str(tmp_path))
    for d in ("good", "bad"):
        ol = OpLog()
        ol.add_insert(ol.get_or_create_agent_id("w"), 0, f"{d} text")
        store.save(d, ol)
    with open(store.path("bad"), "r+b") as f:
        f.write(b"\xff" * os.path.getsize(store.path("bad")))
    with pytest.raises(DocQuarantined) as ei:
        store.load("bad")
    assert ei.value.doc_id == "bad"
    assert store.is_quarantined("bad") is not None
    # sticky: the second load rejects without touching the disk again
    with pytest.raises(DocQuarantined):
        store.load("bad")
    # containment: the neighbor is untouched
    assert store.load("good").checkout_tip().snapshot() == "good text"
    c = store.counters()
    assert c["quarantines"] == 1 and c["quarantined_docs"] == 1


# ---- Hydrator ------------------------------------------------------------

class _SlowNTimes(StorageFaults):
    """Delay larger than the attempt timeout for the first `n` loads,
    then a healthy disk — the timeout->retry->success ladder."""

    def __init__(self, n, slow_s=5.0):
        super().__init__(seed=0, slow_rate=0.0)
        self._left = n
        self._slow = slow_s

    def load_delay(self, doc_id):
        if self._left > 0:
            self._left -= 1
            return self._slow
        return 0.0


def _mk_store_with_doc(tmp_path, doc="d", text="persisted", **kw):
    store = TieredStore(str(tmp_path), **kw)
    ol = OpLog()
    ol.add_insert(ol.get_or_create_agent_id("w"), 0, text)
    store.save(doc, ol)
    return store


def test_hydration_timeout_then_retry_succeeds(tmp_path):
    store = _mk_store_with_doc(tmp_path)
    store.faults = _SlowNTimes(2, slow_s=5.0)
    hyd = Hydrator(store, workers=1, attempt_timeout_s=0.02,
                   max_attempts=4, sync_wait_s=5.0)
    try:
        ol = hyd.resolve("d")
        assert ol.checkout_tip().snapshot() == "persisted"
        c = hyd.counters_snapshot()
        assert c["timeouts"] == 2 and c["retries"] >= 2
        assert c["hydrations"] == 1 and c["quarantined"] == 0
        assert hyd.cold_start.count == 1
        assert hyd.status("d") == "warm"
    finally:
        hyd.stop(checkpoint=False)


def test_sync_resolve_exhaustion_quarantines(tmp_path):
    store = _mk_store_with_doc(tmp_path)
    store.faults = _SlowNTimes(100, slow_s=5.0)   # never recovers
    hyd = Hydrator(store, workers=1, attempt_timeout_s=0.01,
                   max_attempts=2, sync_wait_s=0.05)
    try:
        with pytest.raises(DocQuarantined) as ei:
            hyd.resolve("d")
        assert ei.value.reason == "hydration_timeout"
        assert hyd.status("d") == "quarantined"
        assert hyd.counters_snapshot()["quarantined"] == 1
    finally:
        hyd.stop(checkpoint=False)


def test_flush_gate_classifies_warm_quarantined_cold(tmp_path):
    store = TieredStore(str(tmp_path))
    for d in ("warm", "cold", "bad"):
        ol = OpLog()
        ol.add_insert(ol.get_or_create_agent_id("w"), 0, d)
        store.save(d, ol)
    store.quarantine("bad", "seeded")
    # keep "cold" cold: every async attempt overruns its budget
    store.faults = _SlowNTimes(100, slow_s=5.0)
    hyd = Hydrator(store, workers=1, attempt_timeout_s=0.01,
                   max_attempts=1, gate_wait_s=0.001,
                   defer_budget_s=10.0)
    try:
        store.faults = None
        assert hyd.resolve("warm") is not None
        store.faults = _SlowNTimes(100, slow_s=5.0)
        items = [SimpleNamespace(doc_id=d, n_ops=1, epoch=-1, trace=None)
                 for d in ("warm", "cold", "bad")]
        keep, defer, dropped = hyd.flush_gate(0, items)
        assert [i.doc_id for i in keep] == ["warm"]
        assert [i.doc_id for i in defer] == ["cold"]
        assert [i.doc_id for i in dropped] == ["bad"]
        c = hyd.counters_snapshot()
        assert c["quarantined_drops"] == 1 and c["deferrals"] == 1
    finally:
        hyd.stop(checkpoint=False)


def test_second_gate_visit_escalates_to_sync_hydration(tmp_path):
    # async hydration never lands (worker loads overrun the attempt
    # budget) but the SYNC path recovers: the first gate visit defers,
    # the second hydrates in-flush instead of livelocking the drain
    import threading

    class _SlowWorkersOnly(StorageFaults):
        def __init__(self):
            super().__init__(seed=0, slow_rate=0.0)

        def load_delay(self, doc_id):
            t = threading.current_thread().name
            return 5.0 if t.startswith("hydrate-worker") else 0.0

    store = _mk_store_with_doc(tmp_path, doc="d", text="slow home")
    store.faults = _SlowWorkersOnly()
    hyd = Hydrator(store, workers=1, attempt_timeout_s=0.01,
                   max_attempts=1, gate_wait_s=0.001,
                   sync_wait_s=5.0, defer_budget_s=10.0)
    try:
        item = SimpleNamespace(doc_id="d", n_ops=1, epoch=-1, trace=None)
        keep, defer, dropped = hyd.flush_gate(0, [item])
        assert defer and not keep and not dropped
        keep, defer, dropped = hyd.flush_gate(0, [item])
        assert keep and not defer and not dropped
        assert hyd.status("d") == "warm"
        c = hyd.counters_snapshot()
        assert c["defer_escalations"] == 1 and c["deferrals"] == 1
        assert hyd.resolve("d").checkout_tip().snapshot() == "slow home"
    finally:
        hyd.stop(checkpoint=False)


def test_defer_budget_exhaustion_quarantines(tmp_path):
    store = _mk_store_with_doc(tmp_path, doc="stuck")
    store.faults = _SlowNTimes(100, slow_s=5.0)
    hyd = Hydrator(store, workers=1, attempt_timeout_s=0.01,
                   max_attempts=1, gate_wait_s=0.001,
                   defer_budget_s=0.02)
    try:
        item = SimpleNamespace(doc_id="stuck", n_ops=1, epoch=-1,
                               trace=None)
        keep, defer, dropped = hyd.flush_gate(0, [item])
        assert defer and not keep and not dropped
        time.sleep(0.05)       # let the defer budget lapse
        keep, defer, dropped = hyd.flush_gate(0, [item])
        assert dropped and not keep and not defer
        assert store.is_quarantined("stuck") == "hydration_stuck"
        assert hyd.counters_snapshot()["defer_gave_up"] == 1
    finally:
        hyd.stop(checkpoint=False)


# ---- eviction-to-snapshot churn parity (satellite 3) ---------------------

def test_eviction_churn_byte_parity_vs_resident_control(tmp_path):
    rng = random.Random(11)
    docs = [f"d{i}" for i in range(8)]
    store = TieredStore(str(tmp_path), compact_patch_records=4)
    for d in docs:
        store.save(d, _mk_oplog([f"[{d}] "]))
    hyd = Hydrator(store, workers=2, warm_max=3, evict_grace_s=0.0,
                   sync_wait_s=5.0)
    # always-resident control: the same edits applied to oplogs that
    # are NEVER evicted — any byte the eviction path drops shows here
    control = {d: _mk_oplog([f"[{d}] "]) for d in docs}
    try:
        for step in range(120):
            d = rng.choice(docs)
            tok = f"e{step}."
            live = hyd.resolve(d)
            pos = rng.randint(0, len(
                control[d].checkout_tip().snapshot()))
            for ol in (live, control[d]):
                ol.add_insert(ol.get_or_create_agent_id("ed"), pos, tok)
            if rng.random() < 0.2:
                # evict mid-churn, not just at LRU pressure
                hyd.evict_to_snapshot(rng.choice(docs), why="test")
        assert hyd.counters_snapshot()["evictions_to_snapshot"] > 0
        for d in docs:
            assert hyd.resolve(d).checkout_tip().snapshot() \
                == control[d].checkout_tip().snapshot(), d
        # ... and the same holds re-hydrated from disk after shutdown
        hyd.stop(checkpoint=True)
        fresh = TieredStore(str(tmp_path))
        for d in docs:
            assert fresh.load(d).checkout_tip().snapshot() \
                == control[d].checkout_tip().snapshot(), d
    finally:
        hyd.stop(checkpoint=False)


def test_eviction_aborts_when_append_races_the_snapshot(tmp_path):
    store = _mk_store_with_doc(tmp_path, doc="d", text="base ")

    class _RacingStore:
        """Proxy whose save() appends to the live oplog AFTER the
        snapshot encode returns — the exact race eviction must detect
        via the persisted-op-count recheck."""

        def __init__(self, inner):
            self._inner = inner
            self.racer = None

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def save(self, doc_id, oplog, oplog_lock=None):
            n = self._inner.save(doc_id, oplog, oplog_lock=oplog_lock)
            if self.racer is not None:
                self.racer(oplog)
            return n

    proxy = _RacingStore(store)
    hyd = Hydrator(proxy, workers=1, sync_wait_s=5.0)
    try:
        ol = hyd.resolve("d")

        def racer(target):
            target.add_insert(
                target.get_or_create_agent_id("late"), 0, "racing-op ")

        proxy.racer = racer
        assert hyd.evict_to_snapshot("d", why="test") is False
        proxy.racer = None
        c = hyd.counters_snapshot()
        assert c["eviction_aborts"] == 1
        # the doc stayed warm: the racing op is still resident
        assert hyd.resolve("d") is ol
        assert "racing-op" in ol.checkout_tip().snapshot()
        # with the race gone the next eviction lands and persists it
        assert hyd.evict_to_snapshot("d", why="test") is True
        assert "racing-op" in \
            store.load("d").checkout_tip().snapshot()
    finally:
        hyd.stop(checkpoint=False)


# ---- SessionBank eviction routing (satellite 6) --------------------------

class _Recorder:
    def __init__(self):
        self.events = []

    def record(self, kind, **fields):
        self.events.append((kind, fields))


def test_bank_evict_reports_pending_ops_and_snapshot_routing():
    from diamond_types_tpu.serve.bank import SessionBank
    bank = SessionBank(0, max_sessions=4, engine="host")
    bank.recorder = _Recorder()
    requested = []
    bank.snapshot_hook = lambda d, pending: (
        requested.append((d, pending)) or True)
    ol = _mk_oplog(["pending state "])
    bank.session("doc", ol)
    assert bank.evict("doc") is True
    assert requested and requested[0][0] == "doc"
    assert requested[0][1] >= 0
    evs = [f for k, f in bank.recorder.events if k == "session_evicted"]
    assert evs and evs[0]["doc"] == "doc"
    assert evs[0]["snapshotted"] is True
    assert evs[0]["pending_ops"] == requested[0][1]
    # hook failure must not wedge the eviction path
    bank.session("doc2", ol)
    bank.snapshot_hook = lambda d, pending: 1 / 0
    assert bank.evict("doc2") is True


# ---- metrics v7 + prom (satellite 5) -------------------------------------

def test_metrics_v7_hydration_block_and_prom_families():
    m = ServeMetrics(2, 4, 64)
    m.record_hydration("prefetches")
    m.record_hydration("evictions_to_snapshot", 3)
    m.observe_cold_start(0.012)
    snap = m.snapshot()
    assert snap["version"] == ServeMetrics.SCHEMA_VERSION
    assert set(HYDRATION_KEYS) <= set(snap["hydration"])
    assert snap["hydration"]["prefetches"] == 1
    assert snap["hydration"]["evictions_to_snapshot"] == 3
    assert snap["latencies"]["hydration_cold_start"]["count"] == 1
    from diamond_types_tpu.obs.prom import render_metrics
    text = render_metrics({"serve": snap})
    assert "dt_serve_hydration_prefetches_total 1" in text
    assert "dt_serve_hydration_evictions_to_snapshot_total 3" in text
    assert "hydration_cold_start" in text


# ---- scheduler integration + soak smoke ----------------------------------

def test_scheduler_rejects_quarantined_and_flushes_rest(tmp_path):
    from diamond_types_tpu.serve.scheduler import MergeScheduler
    store = TieredStore(str(tmp_path))
    for d in ("a", "b", "bad"):
        store.save(d, _mk_oplog([f"[{d}] "]))
    with open(store.path("bad"), "r+b") as f:
        f.write(b"\xff" * os.path.getsize(store.path("bad")))
    hyd = Hydrator(store, workers=1, sync_wait_s=5.0)
    sched = MergeScheduler(2, hyd.resolve, engine="host",
                           flush_deadline_s=0.01)
    sched.attach_hydrator(hyd)
    try:
        # quarantine is discovered at hydration time...
        assert sched.submit("bad")["accepted"] is True
        sched.drain()
        # ...after which admission itself rejects, typed
        time.sleep(0.05)
        r = sched.submit("bad")
        assert r == {"accepted": False, "shard": r["shard"],
                     "reason": "quarantined"}
        for d in ("a", "b"):
            ol = hyd.resolve(d)
            ol.add_insert(ol.get_or_create_agent_id("ed"),
                          len(ol.checkout_tip().snapshot()), "edited")
            assert sched.submit(d)["accepted"] is True
        sched.drain()
        for d in ("a", "b"):
            assert sched.text(d) == f"[{d}] edited"
        assert hyd.counters_snapshot()["flush_leaks"] == 0
    finally:
        sched.stop_pump(drain=False)
        hyd.stop(checkpoint=False)


def test_storage_soak_smoke_all_faults():
    from diamond_types_tpu.storage.soak import run_storage_soak
    rep = run_storage_soak(docs=16, warm=4, rounds=3,
                           edits_per_round=10, shards=2, seed=5,
                           compact_every=6, churn=True, crash=True,
                           slow=True)
    assert rep["ok"], rep
    assert rep["byte_mismatches"] == 0
    assert rep["quarantine_match"] and rep["quarantine_leaks"] == 0
    assert rep["crashes"] == 1 and rep["compaction_kills"] == 3
    assert rep["lock_witness"]["acyclic"]
    assert rep["lock_witness"]["violation_count"] == 0
