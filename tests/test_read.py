"""Follower reads (read/): staleness contract, cache, acceptance.

Covers the follower-read PR top to bottom:
  * FollowerIndex — advert/reconcile evidence, tightest-bound
    staleness, per-peer isolation, lag accounting;
  * CheckoutCache — LRU bound, per-doc invalidation, single-flight
    coalescing under a real thread flash-crowd;
  * ReadMetrics — fixed key surface (typos raise), snapshot shape,
    prom rendering of the dt_read_* families;
  * the two-server acceptance story: a follower serves within its
    staleness bound, refuses (or proxies) when a partition starves its
    evidence, and honors an X-DT-Min-Version token again after heal;
  * a tiny end-to-end run of the read-bench harness.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from diamond_types_tpu.read import (CheckoutCache, FollowerIndex,
                                    READ_KEYS, ReadMetrics)
from diamond_types_tpu.read.cache import frontier_key
from diamond_types_tpu.read.follower import frontier_known
from diamond_types_tpu.replicate import FaultInjector, attach_replication

pytestmark = pytest.mark.read


# ---- FollowerIndex -------------------------------------------------------

def test_index_no_evidence_is_unbounded():
    idx = FollowerIndex()
    assert idx.staleness("d", "owner", lambda fr: True) is None
    assert idx.lag("d", "owner", lambda fr: True) is None


def test_index_advert_bounds_staleness_only_when_dominated():
    idx = FollowerIndex()
    idx.note_advert("d", "owner", [["a", 3]], as_of=100.0)
    # local oplog dominates the advert: bounded by now - as_of
    st = idx.staleness("d", "owner", lambda fr: True, now=100.5)
    assert st == pytest.approx(0.5)
    # local oplog does NOT dominate: the advert proves nothing
    assert idx.staleness("d", "owner", lambda fr: False,
                         now=100.5) is None


def test_index_reconcile_floor_needs_no_dominance():
    idx = FollowerIndex()
    idx.note_reconciled("d", "owner", as_of=200.0)
    st = idx.staleness("d", "owner", lambda fr: False, now=201.0)
    assert st == pytest.approx(1.0)
    # floors only ratchet forward
    idx.note_reconciled("d", "owner", as_of=150.0)
    assert idx.staleness("d", "owner", lambda fr: False,
                         now=201.0) == pytest.approx(1.0)


def test_index_takes_tightest_bound_and_clamps():
    idx = FollowerIndex()
    idx.note_reconciled("d", "owner", as_of=100.0)
    idx.note_advert("d", "owner", [["a", 1]], as_of=104.0)
    st = idx.staleness("d", "owner", lambda fr: True, now=105.0)
    assert st == pytest.approx(1.0)        # advert, not the reconcile
    # evidence "from the future" (sub-RTT slop) clamps to zero
    assert idx.staleness("d", "owner", lambda fr: True,
                         now=103.0) == 0.0


def test_index_adverts_are_per_peer():
    """A stale lease holder's late advert must not clobber the real
    owner's — evidence is keyed by peer and filtered at query time."""
    idx = FollowerIndex()
    idx.note_advert("d", "old-owner", [["a", 9]], as_of=300.0)
    idx.note_advert("d", "owner", [["a", 2]], as_of=310.0)
    fr, as_of = idx.advert_of("d", "owner")
    assert fr == [["a", 2]] and as_of == 310.0
    assert idx.staleness("d", "owner", lambda fr: True,
                         now=311.0) == pytest.approx(1.0)
    # an older advert from the same peer never replaces a newer one
    idx.note_advert("d", "owner", [["a", 1]], as_of=305.0)
    assert idx.advert_of("d", "owner")[1] == 310.0


def test_index_lag_counts_missing_heads():
    idx = FollowerIndex()
    idx.note_advert("d", "owner", [["a", 5], ["b", 2]], as_of=1.0)
    have = {("a", 5)}
    lag = idx.lag("d", "owner",
                  lambda fr: tuple((h[0], h[1]) for h in fr)[0] in have)
    assert lag == 1
    have.add(("b", 2))
    assert idx.lag("d", "owner",
                   lambda fr: (fr[0][0], fr[0][1]) in have) == 0
    idx.forget("d")
    assert idx.lag("d", "owner", lambda fr: True) is None


def test_frontier_known_against_real_oplog():
    from diamond_types_tpu.text.oplog import OpLog
    ol = OpLog()
    a = ol.get_or_create_agent_id("alice")
    ol.add_insert(a, 0, "hey")
    remote = ol.cg.local_to_remote_frontier(ol.version)
    assert frontier_known(ol, remote)
    agent, seq = remote[0][0], int(remote[0][1])
    assert not frontier_known(ol, [[agent, seq + 1]])
    assert not frontier_known(ol, [["nobody", 0]])


# ---- CheckoutCache -------------------------------------------------------

def test_cache_hit_miss_and_lru_eviction():
    m = ReadMetrics()
    c = CheckoutCache(capacity=2, metrics=m)
    k = frontier_key([["a", 1]])
    assert c.get("d0", k, lambda: "v0") == ("v0", "miss")
    assert c.get("d0", k, lambda: "BOOM") == ("v0", "hit")
    c.get("d1", k, lambda: "v1")
    c.get("d0", k, lambda: "BOOM")          # refresh d0's recency
    c.get("d2", k, lambda: "v2")            # evicts d1 (LRU)
    assert c.get("d1", k, lambda: "v1b") == ("v1b", "miss")
    snap = m.snapshot()["counters"]
    assert snap["cache_hits"] == 2
    assert snap["cache_misses"] == 4
    assert snap["cache_evictions"] >= 1


def test_cache_invalidate_drops_every_frontier_of_doc():
    m = ReadMetrics()
    c = CheckoutCache(capacity=8, metrics=m)
    for seq in (1, 2, 3):
        c.get("d0", frontier_key([["a", seq]]), lambda: f"v{seq}")
    c.get("other", frontier_key([["a", 1]]), lambda: "keep")
    assert c.invalidate("d0") == 3
    assert len(c) == 1
    assert c.invalidate("d0") == 0
    assert c.get("other", frontier_key([["a", 1]]),
                 lambda: "BOOM") == ("keep", "hit")
    assert m.snapshot()["counters"]["invalidated_entries"] == 3


def test_cache_single_flight_coalesces_flash_crowd():
    m = ReadMetrics()
    c = CheckoutCache(capacity=8, metrics=m)
    k = frontier_key([["a", 1]])
    entered = threading.Event()
    release = threading.Event()
    calls = []

    def materialize():
        calls.append(1)
        entered.set()
        release.wait(5)
        return "value"

    results = []

    def leader():
        results.append(c.get("d", k, materialize))

    def waiter():
        results.append(c.get("d", k, lambda: "WRONG"))

    lt = threading.Thread(target=leader)
    lt.start()
    assert entered.wait(5)
    ws = [threading.Thread(target=waiter) for _ in range(3)]
    for w in ws:
        w.start()
    time.sleep(0.05)        # waiters parked on the flight event
    release.set()
    lt.join(5)
    for w in ws:
        w.join(5)
    assert len(calls) == 1
    assert {r[0] for r in results} == {"value"}
    outcomes = sorted(r[1] for r in results)
    assert outcomes == ["coalesced", "coalesced", "coalesced", "miss"]
    assert m.snapshot()["counters"]["cache_coalesced"] == 3


def test_cache_leader_failure_releases_waiters():
    c = CheckoutCache(capacity=8, flight_timeout_s=2.0)
    k = frontier_key([["a", 1]])
    entered = threading.Event()
    outcome = []

    def bad():
        entered.set()
        time.sleep(0.1)
        raise RuntimeError("materialize failed")

    def leader():
        with pytest.raises(RuntimeError):
            c.get("d", k, bad)

    lt = threading.Thread(target=leader)
    lt.start()
    assert entered.wait(5)
    # waiter sees the leader's failure and materializes for itself
    outcome.append(c.get("d", k, lambda: "mine"))
    lt.join(5)
    assert outcome[0] == ("mine", "timeout")
    assert len(c) == 0      # failed flight cached nothing


# ---- ReadMetrics ---------------------------------------------------------

def test_metrics_fixed_keys_and_snapshot_shape():
    m = ReadMetrics()
    with pytest.raises(KeyError):
        m.bump("no_such_counter")
    m.bump("reads", 4)
    m.bump("local", 3)
    m.bump("proxied_staleness")
    m.observe_staleness(0.25)
    snap = m.snapshot()
    assert snap["version"] == 2
    assert set(snap["counters"]) == set(READ_KEYS)
    assert snap["proxied"] == 1
    assert snap["local_ratio"] == pytest.approx(0.75)
    assert snap["staleness"]["count"] == 1
    assert ReadMetrics().snapshot()["local_ratio"] is None


def test_prom_renders_read_families():
    from diamond_types_tpu.obs.prom import render_metrics
    m = ReadMetrics()
    m.bump("reads", 2)
    m.bump("local", 2)
    m.observe_staleness(0.1)
    m.observe_wait(0.02)
    text = render_metrics({"read": m.snapshot()})
    assert "dt_read_reads_total 2" in text
    assert "dt_read_local_total 2" in text
    assert "dt_read_local_ratio 1" in text
    assert "dt_read_staleness_seconds_count 1" in text
    assert "dt_read_wait_latency_seconds_count 1" in text
    # inside a ServeMetrics v8 snapshot the same families render once
    from diamond_types_tpu.serve.metrics import ServeMetrics
    sm = ServeMetrics(n_shards=1, flush_docs=8, max_pending=64)
    sm.read = m
    text2 = render_metrics({"serve": sm.snapshot()})
    assert text2.count("dt_read_reads_total 2") == 1


# ---- two-server acceptance -----------------------------------------------

def _mesh2(faults=None, read_opts=None):
    from diamond_types_tpu.read import attach_follower_reads
    from diamond_types_tpu.tools.server import serve
    httpds, addrs, nodes = [], [], []
    for _ in range(2):
        httpd = serve(port=0, engine="host", serve_shards=1)
        httpds.append(httpd)
        addrs.append(f"127.0.0.1:{httpd.server_address[1]}")
    for i, httpd in enumerate(httpds):
        nodes.append(attach_replication(
            httpd, addrs[i], [a for a in addrs if a != addrs[i]],
            faults=faults, lease_ttl_s=30.0, timeout_s=0.5,
            backoff_base_s=0.01, backoff_cap_s=0.05))
        attach_follower_reads(httpd.store, **(read_opts or {}))
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
    return httpds, nodes, addrs


def _teardown(httpds):
    for h in httpds:
        h.shutdown()
        h.server_close()


def _step(nodes, rounds=1):
    for _ in range(rounds):
        for n in nodes:
            n.table.probe_once()
            n.maintain()
        for n in nodes:
            n.antientropy.run_round()


def _edit(addr, doc, agent, version, text):
    req = urllib.request.Request(
        f"http://{addr}/doc/{doc}/edit",
        data=json.dumps({"agent": agent, "version": version,
                         "ops": [{"kind": "ins", "pos": 0,
                                  "text": text}]}).encode("utf8"))
    with urllib.request.urlopen(req, timeout=5) as r:
        return json.loads(r.read())["version"]


def _read(addr, doc, max_staleness=None, token=None):
    """Returns (status, headers, body-dict-or-None)."""
    url = f"http://{addr}/doc/{doc}/state"
    if max_staleness is not None:
        url += f"?max_staleness={max_staleness}"
    headers = {}
    if token is not None:
        headers["X-DT-Min-Version"] = json.dumps(token)
    req = urllib.request.Request(url, headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=5) as r:
            return r.status, dict(r.headers), json.loads(r.read())
    except urllib.error.HTTPError as e:
        body = e.read()
        return e.code, dict(e.headers), \
            (json.loads(body) if body else None)


def _settle_owner(nodes, doc):
    """Step until exactly one node holds the ACTIVE lease; returns
    (owner, follower)."""
    for _ in range(200):
        _step(nodes)
        holders = [n for n in nodes if n.leases.active_epoch(doc) > 0]
        if len(holders) == 1:
            owner = holders[0]
            follower = next(n for n in nodes if n is not owner)
            if follower.route_mutation(doc) == owner.self_id:
                return owner, follower
        time.sleep(0.02)
    raise AssertionError("lease never settled")


def _dominated(headers, token):
    heads = {a: int(s)
             for a, s in json.loads(headers["X-DT-Frontier"])}
    return all(heads.get(a, -1) >= int(s) for a, s in token)


def test_follower_partition_refuses_then_honors_token_after_heal():
    """The acceptance story: a partitioned follower whose evidence has
    aged past the bound refuses (proxy unreachable) instead of serving
    out of contract, and serves a write's min-version token locally
    again after heal + anti-entropy."""
    faults = FaultInjector(seed=3)
    httpds, nodes, addrs = _mesh2(
        faults=faults, read_opts={"max_wait_s": 0.05})
    try:
        doc = "accept0"
        _edit(addrs[0], doc, "w", [], "hello ")
        owner, follower = _settle_owner(nodes, doc)
        _step(nodes, rounds=2)      # fresh adverts + reconcile floors

        # 1) healthy mesh: the follower serves locally, in contract,
        #    and says how stale it might be
        st, hdr, body = _read(follower.self_id, doc, max_staleness=10.0)
        assert st == 200
        assert hdr["X-DT-Read-Source"] == "local"
        assert float(hdr["X-DT-Staleness"]) <= 10.0
        assert hdr["Cache-Control"] == "no-store"
        assert "hello" in body["text"]

        # 2) an unsatisfiable bound on a healthy mesh falls back to
        #    the owner proxy instead of refusing
        st, hdr, _ = _read(follower.self_id, doc, max_staleness=0.0)
        assert st == 200
        assert hdr["X-DT-Read-Source"] == "proxied"

        # 3) partition: evidence ages past the bound and the proxy
        #    path is dead -> the follower must refuse, not serve
        faults.partition(owner.self_id, follower.self_id)
        time.sleep(0.25)
        st, _, body = _read(follower.self_id, doc, max_staleness=0.01)
        assert st == 503
        assert body["error"] == "read contract unsatisfiable"

        # 4) a write lands at the owner during the partition (client
        #    traffic is not fault-injected, only the peer mesh is);
        #    its token is unsatisfiable at the follower
        token = _edit(owner.self_id, doc, "w", None, "more ")
        st, _, _ = _read(follower.self_id, doc, max_staleness=10.0,
                         token=token)
        assert st == 503
        fm = follower.store.reads.metrics.snapshot()["counters"]
        assert fm["refused"] >= 2
        assert fm["catchup_timeouts"] >= 1

        # 5) heal: circuits close, anti-entropy reconciles, and the
        #    same token is served locally with a dominating frontier
        faults.heal(owner.self_id, follower.self_id)
        for _ in range(50):
            _step(nodes)
            st, hdr, body = _read(follower.self_id, doc,
                                  max_staleness=10.0, token=token)
            if st == 200 and hdr["X-DT-Read-Source"] == "local":
                break
            time.sleep(0.02)
        assert st == 200
        assert hdr["X-DT-Read-Source"] == "local"
        assert _dominated(hdr, token)
        assert "more" in body["text"]
        fm = follower.store.reads.metrics.snapshot()["counters"]
        assert fm["local"] >= 2
        assert fm["adverts"] >= 1
    finally:
        _teardown(httpds)


def test_owner_side_of_proxy_never_loops():
    """X-DT-Proxied marks the owner side of a hop: it serves locally
    (still honoring the token) and refuses rather than re-proxying."""
    httpds, nodes, addrs = _mesh2(read_opts={"max_wait_s": 0.05})
    try:
        doc = "loop0"
        _edit(addrs[0], doc, "w", [], "x")
        owner, follower = _settle_owner(nodes, doc)
        # a forced-local read on the FOLLOWER with an unsatisfiable
        # token must refuse (503), never hop again
        bogus = [["w", 10_000]]
        req = urllib.request.Request(
            f"http://{follower.self_id}/doc/{doc}/state",
            headers={"X-DT-Proxied": "1",
                     "X-DT-Min-Version": json.dumps(bogus)})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=5)
        assert ei.value.code == 503
        ei.value.read()
        snap = follower.store.reads.metrics.snapshot()["counters"]
        assert snap["proxied_forced"] >= 1
        assert snap["refused"] >= 1
    finally:
        _teardown(httpds)


def test_read_bench_smoke_end_to_end():
    """Tiny end-to-end run of the A/B harness: settles, verifies every
    response, reports both phases and per-node read metrics."""
    from diamond_types_tpu.read.bench import run_read_bench
    report = run_read_bench(docs=2, readers=2, reads_per_reader=10,
                            seed=11, doc_bytes=2048, min_speedup=None)
    assert report["settled"]
    assert report["violations"] == 0
    assert report["errors"] == 0
    assert report["control"]["reads"] == 20
    assert report["follower"]["reads"] == 20
    assert report["follower"]["local"] == 20
    assert report["control"]["proxied"] == 20
    for snap in report["read_metrics"].values():
        assert snap["version"] == 2
