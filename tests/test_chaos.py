"""Partition-safety chaos suite (`pytest -m chaos`).

Acceptance for the quorum/fencing PR: seeded soaks combining
asymmetric partitions, crash-restarts, and node join+leave must end
byte-identical across live replicas, the split-brain detector (which
scans EVERY node incarnation's lease activation history for two ACTIVE
holders sharing a (doc, epoch)) must report zero violations, and a
fenced stale-owner write must be observably REJECTED (counter > 0),
not merged.

Everything is in-process on ephemeral localhost ports and sized for
the tier-1 gate: tight TTLs, few rounds, seeded fault schedules.
"""

import json
import threading
import urllib.error
import urllib.request

import pytest

from diamond_types_tpu.replicate import attach_replication
from diamond_types_tpu.replicate.soak import run_replicate_soak

pytestmark = [pytest.mark.chaos, pytest.mark.replicate]


def _post(addr, path, obj, headers=None):
    req = urllib.request.Request(
        f"http://{addr}{path}",
        data=json.dumps(obj).encode("utf8") if isinstance(obj, dict)
        else obj)
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    with urllib.request.urlopen(req, timeout=5) as r:
        return r.status, r.read()


# ---- acceptance soaks ----------------------------------------------------

def test_asym_partition_crash_churn_soak_no_split_brain(tmp_path):
    """The headline acceptance run: one-way partitions + two
    crash-restarts + a join-then-leave, seeded. Live replicas end
    byte-identical and no (doc, epoch) ever had two ACTIVE holders."""
    r = run_replicate_soak(servers=3, docs=2, rounds=8,
                           edits_per_round=2, seed=5, drop_rate=0.05,
                           partition_rounds=3, reconcile_rounds=16,
                           lease_ttl_s=0.3, crash=True, asym=True,
                           churn=True, data_dir=str(tmp_path))
    assert r["converged"], r["doc_lengths"]
    assert r["zero_split_brain"], r["split_brain"]
    assert r["crashes"] == 2
    assert r["quorum"]["rounds_won"] >= 1       # leases went through
    assert r["quorum"]["rejoins_completed"] >= 1
    assert r["config"]["asym"] and r["config"]["churn"]
    assert r["faults"]["partition_blocks"] >= 1


def test_asym_partition_soak_converges(tmp_path):
    """Asymmetric-cut-only soak at a different seed: the TTL-takeover
    killer case (a cannot reach b, b still hears a)."""
    r = run_replicate_soak(servers=3, docs=2, rounds=6,
                           edits_per_round=2, seed=11, drop_rate=0.1,
                           partition_rounds=3, reconcile_rounds=16,
                           lease_ttl_s=0.3, asym=True,
                           data_dir=str(tmp_path))
    assert r["converged"], r["doc_lengths"]
    assert r["zero_split_brain"], r["split_brain"]
    assert r["faults"]["oneway_partitions"] == [] \
        or r["config"]["asym"]   # healed by report time


# ---- targeted scenarios --------------------------------------------------

def _mesh(n, tmp_path, lease_ttl_s=5.0, serve_shards=1):
    from diamond_types_tpu.tools.server import serve
    httpds, addrs = [], []
    for i in range(n):
        httpd = serve(port=0, data_dir=str(tmp_path / f"s{i}"),
                      engine="host", serve_shards=serve_shards)
        httpds.append(httpd)
        addrs.append(f"127.0.0.1:{httpd.server_address[1]}")
    nodes = []
    for i, httpd in enumerate(httpds):
        nodes.append(attach_replication(
            httpd, addrs[i], [a for a in addrs if a != addrs[i]],
            lease_ttl_s=lease_ttl_s, backoff_base_s=0.01,
            backoff_cap_s=0.05,
            journal_prefix=str(tmp_path / f"s{i}" / "_replica")))
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
    return httpds, nodes, addrs


def _teardown(httpds):
    for h in httpds:
        h.shutdown()
        h.server_close()


def _step(nodes):
    for n in nodes:
        n.table.probe_once()
        n.maintain()


def test_fenced_stale_owner_write_rejected(tmp_path):
    """Acceptance: a proxied mutation carrying a superseded lease epoch
    is rejected with 409 (fencing.rejected_writes > 0), never merged;
    the proxier counts the fenced relay and falls back local."""
    httpds, nodes, addrs = _mesh(2, tmp_path)
    try:
        _step(nodes)
        doc = "fence-doc"
        owner = nodes[0].desired_owner(doc)
        owner_node = next(n for n in nodes if n.self_id == owner)
        other_node = next(n for n in nodes if n.self_id != owner)
        assert owner_node.owns(doc)
        epoch = owner_node.leases.get(doc).epoch
        # a successor epoch gets promised on the owner (e.g. a takeover
        # during a partition): the floor passes the old lease
        ok, _ = owner_node.leases.promise(doc, epoch + 5,
                                          other_node.self_id)
        assert ok
        # a write claiming the OLD epoch must now bounce with 409
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(owner, f"/doc/{doc}/edit",
                  {"agent": "stale", "pos": 0, "insert": "ghost"},
                  headers={"X-DT-Proxied": "1",
                           "X-DT-Lease-Epoch": str(epoch)})
        assert ei.value.code == 409
        body = json.loads(ei.value.read())
        assert body["error"] == "fenced"
        assert body["max_epoch"] == epoch + 5
        assert owner_node.metrics.get("fencing",
                                      "rejected_writes") == 1
        # ... and nothing was merged
        with urllib.request.urlopen(f"http://{owner}/doc/{doc}",
                                    timeout=5) as r:
            assert b"ghost" not in r.read()
        # proxier side: a relay stamped with the stale epoch (the
        # other node still believes the old lease) gets fenced and
        # falls back local
        other_node.leases.observe_remote(doc, owner, epoch, "active",
                                         ttl_s=60.0)
        relay = other_node.proxy(
            owner, f"/doc/{doc}/edit",
            json.dumps({"agent": "relay", "pos": 0,
                        "insert": "via proxy"}).encode("utf8"),
            doc_id=doc)
        assert relay is None
        assert other_node.metrics.get("proxy", "fenced_relays") == 1
        # the owner's own next admit self-revokes the stale lease
        assert not owner_node.owns(doc)
        assert owner_node.metrics.get("fencing",
                                      "stale_lease_revoked") == 1
    finally:
        _teardown(httpds)


def test_crash_restart_rejoins_and_never_reissues_epoch(tmp_path):
    """Acceptance (bugfix satellite): a crashed-and-restarted node boots
    fenced (rejoining: every admit denied), must re-earn quorum, and
    its re-acquired lease epoch is STRICTLY ABOVE anything it issued in
    its previous life — even though the old lease was never released."""
    httpds, nodes, addrs = _mesh(3, tmp_path, lease_ttl_s=0.5)
    try:
        _step(nodes)
        # find a doc owned by node 0 so the crash hits the lease holder
        doc = next(f"crash-doc-{i}" for i in range(50)
                   if nodes[0].desired_owner(f"crash-doc-{i}")
                   == addrs[0])
        assert nodes[0].owns(doc)
        old_epoch = nodes[0].leases.get(doc).epoch
        old_inc = nodes[0].membership.self_incarnation
        crashed = nodes[0]
        # crash: tear down WITHOUT journal close (the WAL replays)
        crashed.journal = None
        crashed.leases.journal = None
        httpds[0].shutdown()
        httpds[0].server_close()
        # reboot on the same port + data dir
        from diamond_types_tpu.tools.server import serve
        httpd = serve(port=int(addrs[0].split(":")[1]),
                      data_dir=str(tmp_path / "s0"), engine="host",
                      serve_shards=1)
        httpds[0] = httpd
        node = attach_replication(
            httpd, addrs[0], [addrs[1], addrs[2]], lease_ttl_s=0.5,
            backoff_base_s=0.01, backoff_cap_s=0.05,
            journal_prefix=str(tmp_path / "s0" / "_replica"))
        nodes[0] = node
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        # restored: fenced rejoining state, bumped incarnation, floor
        assert node.rejoining
        assert node.membership.self_incarnation > old_inc
        assert node.leases.max_epoch_of(doc) >= old_epoch
        assert not node.owns(doc)              # denied while rejoining
        assert node.metrics.get("fencing", "rejoin_denials") >= 1
        # probes confirm a quorum of voters -> the fence lifts
        for _ in range(4):
            _step(nodes)
            if not node.rejoining:
                break
        assert not node.rejoining
        assert node.metrics.get("quorum", "rejoins_completed") == 1
        # re-acquisition goes through quorum at a FRESH epoch
        assert node.owns(doc)
        assert node.leases.get(doc).epoch > old_epoch
        # the detector over both incarnations sees no shared epoch
        hist = (crashed.leases.activation_history()
                + node.leases.activation_history())
        seen = {}
        for rec in hist:
            key = (rec["doc"], rec["epoch"])
            assert seen.setdefault(key, rec["holder"]) == rec["holder"]
        epochs = [rec["epoch"] for rec in hist if rec["doc"] == doc]
        assert len(epochs) == len(set(epochs))
    finally:
        _teardown(httpds)


def test_membership_join_leave_moves_ownership(tmp_path):
    """Dynamic membership: a joiner enters the universe via
    /replicate/join + gossip (docs migrate to it by handoff), and an
    explicit leave deterministically migrates them back."""
    httpds, nodes, addrs = _mesh(2, tmp_path, lease_ttl_s=5.0)
    try:
        _step(nodes)
        # boot a third server and join it through node 0
        from diamond_types_tpu.tools.server import serve
        httpd3 = serve(port=0, data_dir=str(tmp_path / "s2"),
                       engine="host", serve_shards=1)
        addr3 = f"127.0.0.1:{httpd3.server_address[1]}"
        node3 = attach_replication(
            httpd3, addr3, [], lease_ttl_s=5.0, backoff_base_s=0.01,
            backoff_cap_s=0.05,
            journal_prefix=str(tmp_path / "s2" / "_replica"))
        threading.Thread(target=httpd3.serve_forever,
                         daemon=True).start()
        assert node3.join_mesh(addrs[0])
        all_nodes = nodes + [node3]
        _step(all_nodes)        # gossip spreads the join
        for n in all_nodes:
            assert n.membership.universe() == sorted(addrs + [addr3])
            assert n.membership.quorum_size() == 2
        # ownership is computed over the grown universe on every node
        doc = next(f"churn-doc-{i}" for i in range(100)
                   if node3.desired_owner(f"churn-doc-{i}") == addr3)
        assert nodes[0].desired_owner(doc) == addr3
        assert node3.owns(doc)
        epoch_joined = node3.leases.get(doc).epoch
        # explicit leave (announced to node 0; gossip spreads LEFT)
        _post(addrs[0], "/replicate/leave", {"id": addr3})
        httpd3.shutdown()
        httpd3.server_close()
        _step(nodes)
        for n in nodes:
            assert addr3 not in n.membership.universe()
            assert addr3 not in n.membership.voters()
            assert n.membership.quorum_size() == 2
        # the doc deterministically re-homes among the survivors, at a
        # fenced (higher) epoch once the old lease expires
        new_owner = nodes[0].desired_owner(doc)
        assert new_owner in addrs
        owner_node = next(n for n in nodes if n.self_id == new_owner)
        owner_node.leases.observe_remote(doc, addr3, epoch_joined,
                                         "active", ttl_s=0.0)
        assert owner_node.owns(doc)
        assert owner_node.leases.get(doc).epoch > epoch_joined
    finally:
        _teardown(httpds)
