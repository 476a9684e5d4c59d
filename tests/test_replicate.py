"""Cross-host replication tests: peer mesh, leases, anti-entropy,
fault injection (diamond_types_tpu/replicate/). Tier-1 safe: every
server is in-process on an ephemeral localhost port, no TPU, no
background control-plane threads (tests step probes/rounds inline for
determinism)."""

import json
import threading
import urllib.error
import urllib.request

import pytest

from diamond_types_tpu.replicate import (Backoff, CircuitOpen,
                                         FaultDrop, FaultInjector,
                                         PeerTable, ReplicaJournal,
                                         attach_replication,
                                         call_with_retries, owner_of)
from diamond_types_tpu.replicate.metrics import ReplicationMetrics
from diamond_types_tpu.replicate.ownership import (ACTIVE, GRANTED,
                                                   RELEASED,
                                                   LeaseManager)
from diamond_types_tpu.serve.metrics import ServeMetrics

pytestmark = pytest.mark.replicate


# ---- helpers -------------------------------------------------------------

def _mesh(n, tmp_path=None, serve_shards=2, faults=None,
          lease_ttl_s=5.0, **opts):
    """N wired in-process servers. Returns (httpds, nodes, addrs).
    Breaker backoff is tightened so circuits opened by injected faults
    half-open within one paced test round instead of seconds."""
    from diamond_types_tpu.tools.server import serve
    opts.setdefault("backoff_base_s", 0.01)
    opts.setdefault("backoff_cap_s", 0.05)
    httpds, addrs = [], []
    for i in range(n):
        data_dir = str(tmp_path / f"s{i}") if tmp_path else None
        httpd = serve(port=0, data_dir=data_dir,
                      engine="host", serve_shards=serve_shards)
        httpds.append(httpd)
        addrs.append(f"127.0.0.1:{httpd.server_address[1]}")
    nodes = []
    for i, httpd in enumerate(httpds):
        nodes.append(attach_replication(
            httpd, addrs[i], [a for a in addrs if a != addrs[i]],
            faults=faults, lease_ttl_s=lease_ttl_s, **opts))
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


def _text(addr, doc):
    with urllib.request.urlopen(f"http://{addr}/doc/{doc}",
                                timeout=5) as r:
        return r.read().decode("utf8")


def _metrics(addr):
    with urllib.request.urlopen(f"http://{addr}/metrics",
                                timeout=5) as r:
        return json.loads(r.read())


# ---- unit: backoff / retries / faults ------------------------------------

def test_backoff_deterministic_and_bounded():
    a = Backoff(base_s=0.1, cap_s=2.0, seed=3, key="x")
    b = Backoff(base_s=0.1, cap_s=2.0, seed=3, key="x")
    da = [a.delay(i) for i in range(12)]
    db = [b.delay(i) for i in range(12)]
    assert da == db                       # seeded: replays exactly
    assert all(0.05 <= d <= 2.0 for d in da)   # jitter in [0.5,1.0)*nominal
    assert da[0] < 0.1 <= da[4]           # actually grows
    # huge attempts must not overflow (DocStore backoff regression class)
    assert 1.0 <= Backoff(base_s=0.1, cap_s=2.0).delay(5000) <= 2.0


def test_backoff_delay_jitter_bounds():
    """Satellite: the jitter window is exactly [0.5, 1.0) of the capped
    nominal delay, per attempt."""
    b = Backoff(base_s=0.1, cap_s=2.0, seed=9, key="jit")
    for attempt in range(12):
        nominal = min(0.1 * (2 ** attempt), 2.0)
        d = b.delay(attempt)
        assert nominal * 0.5 <= d < nominal, (attempt, d, nominal)
    # negative attempts clamp to the base delay's window
    d = Backoff(base_s=0.2, cap_s=2.0, seed=1).delay(-5)
    assert 0.1 <= d < 0.2


def test_circuit_open_retry_at_monotonic():
    """Satellite: consecutive failures re-open the circuit with
    strictly growing retry_at deadlines (exponential backoff), and the
    refusal carries the live deadline."""
    t = PeerTable("self:0", ["127.0.0.1:9"], fail_threshold=3,
                  backoff_base_s=0.05, backoff_cap_s=60.0, seed=4)
    st = t.peers["127.0.0.1:9"]
    opens = []
    for _ in range(9):
        t._record_failure(st)
        if st.open_until:
            opens.append(st.open_until)
    assert len(opens) == 7          # opens at the 3rd failure
    assert all(b2 > a for a, b2 in zip(opens, opens[1:]))
    with pytest.raises(CircuitOpen) as ei:
        t.call("127.0.0.1:9", "/replicate/ping")
    assert ei.value.peer_id == "127.0.0.1:9"
    assert ei.value.retry_at == st.open_until


def test_call_with_retries_transient_vs_client_error():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("transient")
        return "ok"

    assert call_with_retries(flaky, retries=3,
                             sleep=lambda s: None) == "ok"
    assert len(calls) == 3

    def always_fails():
        raise ConnectionError("down")

    with pytest.raises(ConnectionError):
        call_with_retries(always_fails, retries=2, sleep=lambda s: None)

    n4xx = []

    def client_error():
        n4xx.append(1)
        raise urllib.error.HTTPError("u", 400, "bad", {}, None)

    with pytest.raises(urllib.error.HTTPError):
        call_with_retries(client_error, retries=3, sleep=lambda s: None)
    assert len(n4xx) == 1                 # 4xx: no retry


def test_fault_injector_deterministic_and_partition():
    a = FaultInjector(seed=11, drop_rate=0.3, dup_rate=0.2)
    b = FaultInjector(seed=11, drop_rate=0.3, dup_rate=0.2)

    def schedule(inj):
        out = []
        for _ in range(40):
            try:
                out.append("dup" if inj.before_call("x", "y") else "ok")
            except FaultDrop:
                out.append("drop")
        return out

    sa, sb = schedule(a), schedule(b)
    assert sa == sb and "drop" in sa and "ok" in sa
    inj = FaultInjector(seed=0)
    inj.partition("a", "b")
    with pytest.raises(FaultDrop):
        inj.before_call("a", "b")
    with pytest.raises(FaultDrop):
        inj.before_call("b", "a")         # partitions are bidirectional
    inj.before_call("a", "c")             # unrelated link unaffected
    inj.heal("a", "b")
    inj.before_call("a", "b")
    assert inj.snapshot()["partition_blocks"] == 2


def test_fault_injector_oneway_partition_latency_skew():
    """Satellite: asymmetric (one-way) partitions, per-link latency
    with jitter, and clock-skew bookkeeping — all in the snapshot."""
    inj = FaultInjector(seed=5)
    inj.partition("a", "b", oneway=True)
    with pytest.raises(FaultDrop):
        inj.before_call("a", "b")       # forward direction cut
    inj.before_call("b", "a")           # reverse still flows
    assert inj.partitioned("a", "b") and not inj.partitioned("b", "a")
    snap = inj.snapshot()
    assert snap["oneway_partitions"] == [["a", "b"]]
    assert snap["partitions"] == [["a", "b"]]
    inj.heal("a", "b")                  # heal clears both directions
    inj.before_call("a", "b")
    assert inj.snapshot()["oneway_partitions"] == []
    # per-link latency is directed and deterministic
    t0 = __import__("time").monotonic()
    inj.set_link_latency("a", "c", 0.01, jitter_s=0.005)
    inj.before_call("a", "c")
    assert __import__("time").monotonic() - t0 >= 0.01
    inj.before_call("c", "a")           # reverse direction: no sleep
    snap = inj.snapshot()
    assert snap["link_delays"] == 1
    assert snap["link_latency"] == {
        "a->c": {"latency_s": 0.01, "jitter_s": 0.005}}
    inj.set_link_latency("a", "c", 0.0)     # zero clears
    assert inj.snapshot()["link_latency"] == {}
    # clock skew is bookkeeping for expiry reasoning, not scheduling
    inj.set_clock_skew("b", 0.75)
    assert inj.now("b") > inj.now("a")
    assert inj.snapshot()["clock_skew"] == {"b": 0.75}
    # identical seeds replay identically with a jittered link enabled
    def schedule(j):
        j.set_link_latency("x", "y", 0.0001, jitter_s=0.0001)
        out = []
        for _ in range(30):
            try:
                out.append(j.before_call("x", "y"))
            except FaultDrop:
                out.append("drop")
        return out
    s1 = schedule(FaultInjector(seed=8, drop_rate=0.3, dup_rate=0.2))
    s2 = schedule(FaultInjector(seed=8, drop_rate=0.3, dup_rate=0.2))
    assert s1 == s2 and "drop" in s1


# ---- unit: ownership -----------------------------------------------------

def test_owner_rendezvous_process_independent():
    hosts = ["127.0.0.1:8001", "127.0.0.1:8002", "127.0.0.1:8003"]
    # pinned: blake2b rendezvous must never drift across processes/PRs
    assert {d: owner_of(d, hosts) for d in
            ("doc-0", "doc-1", "doc-2", "doc-3", "doc-4", "doc-5")} == {
        "doc-0": "127.0.0.1:8001", "doc-1": "127.0.0.1:8001",
        "doc-2": "127.0.0.1:8001", "doc-3": "127.0.0.1:8003",
        "doc-4": "127.0.0.1:8003", "doc-5": "127.0.0.1:8001"}
    # order-independent, and removing a non-owner never moves a doc
    assert owner_of("doc-3", list(reversed(hosts))) == "127.0.0.1:8003"
    assert owner_of("doc-3", ["127.0.0.1:8002", "127.0.0.1:8003"]) \
        == "127.0.0.1:8003"


def test_lease_state_machine_and_takeover():
    a = LeaseManager("hostA", ttl_s=60.0)
    b = LeaseManager("hostB", ttl_s=60.0)
    # desired owner acquires; non-desired host never does
    assert a.ensure_local("d", True)
    assert not b.ensure_local("d", False)
    assert a.get("d").state == ACTIVE and a.get("d").epoch == 1
    # B learns A's live lease -> even as desired owner it must wait
    b.observe_remote("d", "hostA", 1, ACTIVE, ttl_s=60.0)
    assert not b.ensure_local("d", True)
    # ... until the lease expires: takeover bumps the epoch
    b.observe_remote("d", "hostA", 2, ACTIVE, ttl_s=0.0)
    assert b.ensure_local("d", True)
    assert b.get("d").epoch == 3 and b.get("d").holder == "hostB"
    # handoff sender walk: ACTIVE -> GRANTING -> ... -> RELEASED
    epoch = a.begin_handoff("d")
    assert epoch == 2
    assert not a.ensure_local("d", True)     # no merges mid-handoff
    a.abort_handoff("d")
    assert a.ensure_local("d", True)         # rollback restores ACTIVE
    # receiver side: grant is not active until activated
    assert b.accept_grant("e", 5, ttl_s=60.0)
    assert b.get("e").state == GRANTED
    assert not b.ensure_local("e", True)
    assert b.activate_grant("e", 5)
    assert b.activate_grant("e", 5)          # idempotent
    assert not b.activate_grant("e", 4)      # stale epoch refused
    assert b.ensure_local("e", True)


def test_observe_remote_equal_epoch_tie_break():
    """Satellite (bugfix): two differing holders at one epoch resolve
    deterministically and symmetrically — smaller id wins regardless of
    arrival order — and each arbitration is counted."""
    m = ReplicationMetrics()
    c = LeaseManager("hostC", ttl_s=60.0, metrics=m)
    c.observe_remote("d", "hostB", 4, ACTIVE, ttl_s=60.0)
    c.observe_remote("d", "hostA", 4, ACTIVE, ttl_s=60.0)
    assert c.get("d").holder == "hostA"
    assert m.get("leases", "tie_breaks") == 1
    c2 = LeaseManager("hostC", ttl_s=60.0)
    c2.observe_remote("d", "hostA", 4, ACTIVE, ttl_s=60.0)
    c2.observe_remote("d", "hostB", 4, ACTIVE, ttl_s=60.0)
    assert c2.get("d").holder == "hostA"     # opposite order, same pick
    # a peer's echo of OUR lease must never shorten our TTL
    a = LeaseManager("hostA", ttl_s=60.0)
    assert a.ensure_local("x", True)
    exp = a.get("x").expires_at
    a.observe_remote("x", "hostA", 1, ACTIVE, ttl_s=0.0)
    assert a.get("x").expires_at == exp


def test_promise_protocol_exclusive_and_fencing():
    """A voter promises (doc, epoch) to at most one holder ever, and
    every promise raises the fencing floor."""
    m = ReplicationMetrics()
    v = LeaseManager("voter", ttl_s=60.0, metrics=m)
    ok, why = v.promise("d", 3, "hostA")
    assert ok and why == "promised"
    ok, _ = v.promise("d", 3, "hostA")       # same holder: idempotent
    assert ok
    ok, why = v.promise("d", 3, "hostB")     # exclusivity
    assert not ok and why == "promise_conflict"
    assert m.get("quorum", "promise_conflicts") == 1
    ok, why = v.promise("d", 2, "hostB")     # floor is 3 now
    assert not ok and why == "stale_epoch"
    ok, why = v.promise("d", 4, "hostB")     # higher epoch: fresh slot
    assert ok
    assert v.max_epoch_of("d") == 4
    # a live unexpired lease blocks a same-epoch proposer
    v.observe_remote("e", "hostA", 5, ACTIVE, ttl_s=60.0)
    ok, why = v.promise("e", 5, "hostB")
    assert not ok and why == "live_lease"
    # fencing floor revokes a superseded self-held ACTIVE lease
    h = LeaseManager("hostA", ttl_s=60.0, metrics=ReplicationMetrics())
    assert h.ensure_local("f", True) and h.get("f").epoch == 1
    ok, _ = h.promise("f", 9, "hostB")       # we vote for a successor
    assert ok and h.max_epoch_of("f") == 9
    assert not h.ensure_local("f", True)     # revoked, not renewed
    assert h.metrics.get("fencing", "stale_lease_revoked") == 1
    assert h.get("f") is None


def test_replica_journal_persist_restore(tmp_path):
    """Crash-restart durability: floors, promises and held leases
    survive an UNCLOSED journal (WAL replay), a closed one (compacted
    snapshot), and feed LeaseManager.restore so a restarted node never
    re-issues a stale epoch."""
    prefix = str(tmp_path / "rj")
    j = ReplicaJournal(prefix)
    assert not j.has_prior_state()
    j.note_incarnation(3)
    j.note_epoch("d", 7)
    j.note_epoch("d", 5)             # below the floor: deduped
    j.note_promise("d", 7, "hostA")
    j.note_lease("d", "me", 7, "active")
    j.note_lease("e", "me", 2, "active")
    j.drop_lease("e")
    # crash: no close() — reopen replays the WAL
    j2 = ReplicaJournal(prefix)
    assert j2.has_prior_state()
    assert j2.restored_incarnation() == 3
    assert j2.restored_max_epochs() == {"d": 7}
    assert j2.restored_promises() == {
        "d": {"epoch": 7, "holder": "hostA"}}
    assert j2.restored_leases() == {
        "d": {"holder": "me", "epoch": 7, "state": "active"}}
    j2.close()                       # graceful: compacts the snapshot
    j3 = ReplicaJournal(prefix)
    assert j3.restored_max_epochs() == {"d": 7}
    # restore: held lease comes back RELEASED; the next acquisition
    # plans PAST the restored floor (stale-epoch-reissue bugfix)
    lm = LeaseManager("me", ttl_s=60.0)
    lm.restore(j3)
    assert lm.max_epoch_of("d") == 7
    assert lm.get("d").state == RELEASED
    assert lm.ensure_local("d", True)
    assert lm.get("d").epoch == 8
    # ... and the re-acquisition was journaled for the NEXT restart
    j3.close()
    j4 = ReplicaJournal(prefix)
    assert j4.restored_max_epochs()["d"] == 8
    assert j4.restored_leases()["d"]["epoch"] == 8
    j4.close()


def test_membership_states_and_refutation():
    from diamond_types_tpu.replicate.membership import (ALIVE, DEAD,
                                                        LEFT, SUSPECT,
                                                        MembershipView)
    v = MembershipView("a", incarnation=2)
    v.add("b", state=ALIVE)
    v.add("c", state=ALIVE)
    assert v.universe() == ["a", "b", "c"]
    assert v.voters() == ["a", "b", "c"] and v.quorum_size() == 2
    # local health: short outage = SUSPECT, still in the universe
    v.note_health("b", 1.0, dead_after_s=5.0)
    assert v.state_of("b") == SUSPECT and "b" in v.universe()
    # past the takeover delay = DEAD: out of the universe, still a
    # voter (a minority partition cannot shrink the denominator)
    v.note_health("b", 6.0, dead_after_s=5.0)
    assert v.state_of("b") == DEAD
    assert v.universe() == ["a", "c"]
    assert v.voters() == ["a", "b", "c"] and v.quorum_size() == 2
    v.note_health("b", None, dead_after_s=5.0)
    assert v.state_of("b") == ALIVE
    # gossip: higher incarnation wins, equal-incarnation hearsay loses
    v.merge_remote({"b": {"state": DEAD, "incarnation": 0}})
    assert v.state_of("b") == ALIVE
    v.merge_remote({"b": {"state": DEAD, "incarnation": 9}})
    assert v.state_of("b") == DEAD
    # refutation: hearing ourselves SUSPECT bumps our incarnation
    inc = v.self_incarnation
    v.merge_remote({"a": {"state": SUSPECT, "incarnation": inc}})
    assert v.self_incarnation == inc + 1
    assert v.state_of("a") == ALIVE
    # explicit leave: out of BOTH sets; spreads at equal incarnation
    v.leave("c")
    assert v.state_of("c") == LEFT
    assert v.voters() == ["a", "b"] and v.quorum_size() == 2
    v2 = MembershipView("b")
    v2.add("c", state=ALIVE)
    v2.merge_remote(v.gossip_payload())
    assert v2.state_of("c") == LEFT


# ---- integration: two-server smoke (tier-1 gate) -------------------------

def test_two_server_smoke(tmp_path):
    """Two wired servers: ownership proxy routes mutations, anti-entropy
    converges the pair, /metrics exposes replication counters (schema
    v3: latency histograms + derived v2 keys) + the serve schema v4
    fields on both servers."""
    from diamond_types_tpu.tools.server import SyncClient
    httpds, nodes, addrs = _mesh(2, tmp_path)
    try:
        docs = ["alpha", "beta", "gamma"]
        for i, doc in enumerate(docs):
            c = SyncClient(f"http://{addrs[i % 2]}", doc, f"u{i}")
            c.insert(0, f"content of {doc}. ")
            c.sync()
        _step(nodes, rounds=2)
        for doc in docs:
            texts = {_text(a, doc) for a in addrs}
            assert len(texts) == 1, f"{doc} diverged: {texts}"
        # merges ran only on each doc's (unique) lease holder
        for doc in docs:
            mergers = [n.self_id for n in nodes
                       if doc in n.merged_docs]
            assert len(mergers) <= 1
            holder = nodes[0].leases.holder_of(doc)
            if mergers:
                assert mergers == [holder]
        for a in addrs:
            m = _metrics(a)
            assert m["replication"]["version"] == 8
            assert m["replication"]["leases"]["held"] >= 0
            assert m["replication"]["antientropy"]["rounds"] >= 1
            assert "promise_conflicts" in m["replication"]["quorum"]
            assert "rejected_writes" in m["replication"]["fencing"]
            assert m["replication"]["quorum_view"]["quorum"] == 2
            assert not m["replication"]["quorum_view"]["rejoining"]
            assert m["replication"]["membership_view"]["view_version"] >= 1
            # v3: histogram latencies + derived v2 keys
            assert "handoff" in m["replication"]["latencies"]
            assert m["replication"]["handoffs"]["latency_s_total"] >= 0
            assert m["serve"]["version"] == \
                ServeMetrics.SCHEMA_VERSION
            assert m["serve"]["uptime_s"] >= 0
            assert "denied" in m["serve"]["totals"]
            assert "fenced" in m["serve"]["totals"]
        # ping endpoint serves health probes
        with urllib.request.urlopen(
                f"http://{addrs[0]}/replicate/ping", timeout=5) as r:
            ping = json.loads(r.read())
        assert ping["ok"] and ping["id"] == addrs[0]
    finally:
        _teardown(httpds)


def test_mutation_proxy_routes_to_owner():
    from diamond_types_tpu.tools.server import SyncClient
    httpds, nodes, addrs = _mesh(2, serve_shards=2)
    try:
        doc = "proxied-doc"
        owner = nodes[0].desired_owner(doc)
        other = next(i for i, a in enumerate(addrs) if a != owner)
        c = SyncClient(f"http://{addrs[other]}", doc, "writer")
        c.insert(0, "written at the wrong server")
        c.sync()
        # the push was proxied: the OWNER admitted the merge, the
        # receiving server did not
        owner_node = next(n for n in nodes if n.self_id == owner)
        other_node = next(n for n in nodes if n.self_id != owner)
        assert doc in owner_node.merged_docs
        assert doc not in other_node.merged_docs
        assert other_node.metrics_json()["proxy"]["proxied"] >= 1
        # and the owner actually stores the doc without anti-entropy
        assert "wrong server" in _text(owner, doc)
    finally:
        _teardown(httpds)


def test_explicit_handoff_moves_active_merger():
    from diamond_types_tpu.tools.server import SyncClient
    httpds, nodes, addrs = _mesh(2, serve_shards=2)
    try:
        doc = "handoff-doc"
        owner = nodes[0].desired_owner(doc)
        src = next(n for n in nodes if n.self_id == owner)
        dst = next(n for n in nodes if n.self_id != owner)
        c = SyncClient(f"http://{owner}", doc, "writer")
        c.insert(0, "pre-handoff state")
        c.sync()
        assert src.owns(doc) and not dst.owns(doc)
        epoch_before = src.leases.get(doc).epoch
        assert src.handoff(doc, dst.self_id)
        # dst now holds the ACTIVE lease at a higher epoch; src released
        assert dst.leases.get(doc).state == ACTIVE
        assert dst.leases.get(doc).epoch == epoch_before + 1
        assert dst.owns(doc)
        assert not src.owns(doc)
        # the final patch transfer carried the doc bytes
        assert "pre-handoff" in _text(dst.self_id, doc)
        hm = src.metrics_json()["handoffs"]
        assert hm["completed"] == 1 and hm["latency_s_total"] > 0
    finally:
        _teardown(httpds)


def test_circuit_breaker_opens_and_recovers():
    httpds, nodes, addrs = _mesh(2, serve_shards=0)
    try:
        n0 = nodes[0]
        faults = FaultInjector(seed=1, drop_rate=1.0)   # kill the link
        n0.table.faults = faults
        for _ in range(n0.table.fail_threshold):
            n0.table.probe_once()
        assert not n0.table.is_healthy(addrs[1])
        assert n0.table.healthy_ids() == [addrs[0]]
        st = n0.table.state(addrs[1])
        assert st["circuit_open"] and st["consecutive_failures"] >= 3
        # ownership does NOT reassign while the outage is shorter than
        # the takeover delay (a short partition must not create a
        # second self-appointed owner) ...
        assert n0.takeover_after_s == 5.0     # defaults to lease TTL
        assert n0.ownership_ids() == sorted(addrs)
        # ... but once the holder's lease has provably expired, the
        # docs collapse onto the lone healthy host
        n0.takeover_after_s = 0.0
        assert n0.ownership_ids() == [addrs[0]]
        assert n0.desired_owner("any-doc") == addrs[0]
        n0.takeover_after_s = 5.0
        # heal: backoff window must lapse before the half-open probe
        n0.table.faults = None
        deadline = __import__("time").monotonic() + 10
        while not n0.table.is_healthy(addrs[1]):
            n0.table.probe_once()
            assert __import__("time").monotonic() < deadline
        assert n0.table.state(addrs[1])["consecutive_failures"] == 0
        m = n0.metrics_json()["probes"]
        assert m["circuit_opens"] == 1 and m["circuit_closes"] == 1
    finally:
        _teardown(httpds)


def test_peer_down_duration_across_probe_recovery():
    """Satellite: down_duration is None while healthy, grows while the
    circuit stays open, and returns to None once the probe loop
    recovers the peer."""
    import time
    httpds, nodes, addrs = _mesh(2, serve_shards=0)
    try:
        t = nodes[0].table
        peer = addrs[1]
        assert t.down_duration(peer) is None       # never failed
        assert t.down_duration(t.self_id) is None  # self: always None
        assert t.down_duration("unknown:1") == float("inf")
        t.probe_once()
        assert t.down_duration(peer) is None       # healthy probe
        t.faults = FaultInjector(seed=2, drop_rate=1.0)
        for _ in range(t.fail_threshold):
            t.probe_once()
        d1 = t.down_duration(peer)
        assert d1 is not None and d1 >= 0.0
        time.sleep(0.02)
        assert t.down_duration(peer) > d1          # grows while down
        # pinned `now` makes the duration arithmetic exact
        st = t.peers[peer]
        assert t.down_duration(peer, now=st.down_since + 1.5) == 1.5
        t.faults = None
        deadline = time.monotonic() + 10
        while t.down_duration(peer) is not None:   # recovery clears it
            t.probe_once()
            assert time.monotonic() < deadline
        assert t.is_healthy(peer)
    finally:
        _teardown(httpds)


def test_syncclient_retries_transient_failures(monkeypatch):
    """Satellite: SyncClient survives transient connection failures on
    pull/push via the shared backoff helper."""
    from diamond_types_tpu.tools import server as srv
    httpd = srv.serve(port=0)
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        real_urlopen = urllib.request.urlopen
        fail = {"n": 2}

        def flaky_urlopen(req, timeout=None):
            if fail["n"] > 0:
                fail["n"] -= 1
                raise ConnectionResetError("injected")
            return real_urlopen(req, timeout=timeout)

        monkeypatch.setattr(srv.urllib.request, "urlopen",
                            flaky_urlopen)
        c = srv.SyncClient(f"http://127.0.0.1:{port}", "retry-doc",
                           "amy", retries=3)
        c.insert(0, "survives flaky transport")
        c.sync()                      # would raise without retry
        fail["n"] = 2
        c.pull()
        assert c.text() == "survives flaky transport"
        # retries exhausted -> the error still surfaces
        fail["n"] = 99
        c.insert(0, "x")
        with pytest.raises(OSError):
            c.push()
    finally:
        _teardown([httpd])


# ---- acceptance: convergence under faults --------------------------------

def test_convergence_under_faults(tmp_path):
    """ISSUE acceptance: two in-process servers with injected faults
    (drops + a healed partition, fixed seed) end byte-identical on
    every doc, each doc's merges ran only on its lease holder, and
    GET /metrics exposes the replication counters on both servers."""
    from diamond_types_tpu.tools.server import SyncClient
    faults = FaultInjector(seed=1234, drop_rate=0.25, dup_rate=0.1)
    httpds, nodes, addrs = _mesh(2, tmp_path, serve_shards=2,
                                 faults=faults)
    try:
        docs = ["conv-0", "conv-1", "conv-2"]
        clients = {(i, d): SyncClient(f"http://{addrs[i]}", d,
                                      f"w{i}-{d}", retries=1)
                   for i in range(2) for d in docs}

        def edit(i, d, text):
            c = clients[(i, d)]
            try:
                c.pull()
            except OSError:
                pass
            c.insert(0, text)
            try:
                c.sync()
            except OSError:
                pass          # dropped mid-fault; reconciled later

        for i, d in [(0, docs[0]), (1, docs[1]), (0, docs[2])]:
            edit(i, d, f"seed {d}. ")
        _step(nodes)
        # partition the pair; both sides keep writing every doc
        faults.partition(addrs[0], addrs[1])
        for r in range(3):
            for d in docs:
                edit(0, d, f"left{r} ")
                edit(1, d, f"right{r} ")
            _step(nodes)
        faults.heal()
        # reconcile to convergence (bounded; fixed seed keeps it
        # tight). Paced so breaker backoff windows opened during the
        # partition can lapse between rounds.
        import time
        for _ in range(10):
            time.sleep(0.06)
            _step(nodes)
            if all(len({_text(a, d) for a in addrs}) == 1
                   for d in docs):
                break
        for d in docs:
            texts = {a: _text(a, d) for a in addrs}
            assert len(set(texts.values())) == 1, \
                f"{d} diverged: {texts}"
            assert "left" in texts[addrs[0]] \
                and "right" in texts[addrs[0]]
        # owner-only merges: at most one host ever admitted each doc
        for d in docs:
            mergers = [n.self_id for n in nodes if d in n.merged_docs]
            assert len(mergers) <= 1, f"{d} merged on {mergers}"
            if mergers:
                assert mergers[0] == nodes[0].desired_owner(d)
        # both servers expose the replication counters, and the fault
        # schedule actually exercised the mesh
        for a in addrs:
            rm = _metrics(a)["replication"]
            assert rm["antientropy"]["rounds"] >= 4
            assert rm["faults"]["drops"] >= 1
        assert faults.snapshot()["partition_blocks"] >= 1
    finally:
        _teardown(httpds)


def test_wire_mesh_frames_and_prom(tmp_path):
    """ISSUE 16: a wire-v1 pair converges with binary frames actually
    on the wire — per-channel counters land in /metrics (replication
    schema v7 "wire" group) and render as dt_wire_* prom families."""
    from diamond_types_tpu.tools.server import SyncClient
    httpds, nodes, addrs = _mesh(2, tmp_path)
    try:
        for i, doc in enumerate(["wire-a", "wire-b"]):
            c = SyncClient(f"http://{addrs[i]}", doc, f"w{i}")
            c.insert(0, f"framed content of {doc}. ")
            c.sync()
        _step(nodes, rounds=3)
        for doc in ("wire-a", "wire-b"):
            texts = {_text(a, doc) for a in addrs}
            assert len(texts) == 1, f"{doc} diverged: {texts}"
        # frames actually flowed: the docs listing + summary GETs are
        # framed from round one (header negotiation), so every node
        # both sent bytes and framed some of them
        wires = [_metrics(a)["replication"]["wire"] for a in addrs]
        assert all(w["antientropy_bytes_sent"] > 0 for w in wires)
        assert sum(w["antientropy_frames"] for w in wires) > 0
        assert sum(w["gossip_bytes_sent"] for w in wires) > 0
        for w in wires:
            assert w["antientropy_bytes_saved"] >= 0
        # prom rendering: labeled dt_wire_* families on both servers
        with urllib.request.urlopen(
                f"http://{addrs[0]}/metrics?format=prom",
                timeout=5) as r:
            prom = r.read().decode("utf8")
        assert 'dt_wire_bytes_sent_total{channel="antientropy"}' in prom
        assert 'dt_wire_frames_total{channel="proxy"}' in prom
    finally:
        _teardown(httpds)


def test_mixed_version_mesh_converges_on_json(tmp_path):
    """ISSUE 16 acceptance: a mixed-version mesh — one wire-v1 node,
    one JSON-pinned node emulating an old build mid-rolling-upgrade —
    converges byte-identically. The pinned node never advertises the
    capability (ping gossip) or the request header, so NO frames flow
    in either direction; both sides still account bytes_sent."""
    import threading as _threading

    from diamond_types_tpu.tools.server import SyncClient, serve
    httpds, addrs = [], []
    for i in range(2):
        httpd = serve(port=0, data_dir=str(tmp_path / f"s{i}"),
                      engine="host", serve_shards=2)
        httpds.append(httpd)
        addrs.append(f"127.0.0.1:{httpd.server_address[1]}")
    nodes = []
    for i, httpd in enumerate(httpds):
        nodes.append(attach_replication(
            httpd, addrs[i], [a for a in addrs if a != addrs[i]],
            backoff_base_s=0.01, backoff_cap_s=0.05,
            wire_enabled=(i == 0)))
        _threading.Thread(target=httpd.serve_forever,
                          daemon=True).start()
    try:
        assert nodes[0].wire.enabled and not nodes[1].wire.enabled
        doc = "mixed"
        c0 = SyncClient(f"http://{addrs[0]}", doc, "alice")
        c0.insert(0, "héllo ")
        c0.sync()
        c1 = SyncClient(f"http://{addrs[1]}", doc, "bob")
        c1.pull()
        c1.insert(len(c1.text()), "wörld ")
        c1.sync()
        _step(nodes, rounds=3)
        texts = {_text(a, doc) for a in addrs}
        assert len(texts) == 1, f"diverged: {texts}"
        # negotiation held: the old peer never saw (or sent) a frame
        w0 = nodes[0].metrics.wire_counters()
        w1 = nodes[1].metrics.wire_counters()
        for ch in ("antientropy", "proxy", "hydrate", "gossip"):
            assert w0[f"{ch}_frames"] == 0, (ch, w0)
            assert w1[f"{ch}_frames"] == 0, (ch, w1)
        # ...but transport accounting stayed on for both builds
        assert w0["antientropy_bytes_sent"] > 0
        assert w1["antientropy_bytes_sent"] > 0
        assert not nodes[0].wire.use_wire(addrs[1])
    finally:
        _teardown(httpds)
