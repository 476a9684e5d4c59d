"""Writer groups (replicate/writergroup.py + the ReplicaNode wiring).

Two layers:

  * `WriterGroupTable` in isolation: install/refresh/drop semantics
    (floor fencing, replay guards), the floor-raise fence hook, and
    the crash-restart journal round-trip (entries restore EXPIRED,
    below-floor entries are not restored at all);
  * a live 3-server mesh: promotion runs a real quorum round and
    re-keys the leader's lease, members install the grant with their
    fencing floor raised and admit writes locally under the group
    epoch, a stale (superseded) grant is refused, a member that loses
    the leader self-fences to proxy-only, and demotion drains back to
    a single writer without losing the member's acked write.

The protocol's interleaving coverage lives in the model checker
(analysis/explore/ `writer-group` scenario + the `demote-without-
drain` / `promote-floor-drop` seeded mutations, tests/test_explore.py);
these tests pin the concrete object behavior those runs rely on.
"""

import threading
import time
import urllib.request

import pytest

from diamond_types_tpu.replicate import (FaultInjector, ReplicaJournal,
                                         attach_replication)
from diamond_types_tpu.replicate.writergroup import WriterGroupTable

pytestmark = pytest.mark.writergroup


# ---- helpers -------------------------------------------------------------

def _mesh(n, faults=None, **opts):
    from diamond_types_tpu.tools.server import serve
    opts.setdefault("backoff_base_s", 0.01)
    opts.setdefault("backoff_cap_s", 0.05)
    opts.setdefault("lease_ttl_s", 30.0)
    httpds, addrs = [], []
    for _ in range(n):
        httpd = serve(port=0, engine="host", serve_shards=1)
        httpds.append(httpd)
        addrs.append(f"127.0.0.1:{httpd.server_address[1]}")
    nodes = []
    for i, httpd in enumerate(httpds):
        nodes.append(attach_replication(
            httpd, addrs[i], [a for a in addrs if a != addrs[i]],
            faults=faults, **opts))
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


def _promote(nodes, doc):
    """Acquire `doc`'s lease at its rendezvous owner and promote it to
    a 2-writer group with one healthy peer. Returns (leader, member)."""
    _step(nodes)
    leader = next(n for n in nodes
                  if n.desired_owner(doc) == n.self_id)
    assert leader.owns(doc)
    member = next(n for n in nodes if n is not leader)
    assert leader.promote_writer_group(doc, [member.self_id])
    return leader, member


# ---- WriterGroupTable unit ----------------------------------------------

def test_install_fences_and_replays():
    t = WriterGroupTable("hostB", ttl_s=60.0)
    assert t.install("d", 5, ["hostA", "hostB"], "hostA", floor=5)
    assert t.get("d").epoch == 5
    assert t.get("d").quorum_size() == 2
    # below the caller's floor: a replayed grant from a superseded
    # group must not resurrect it
    assert not t.install("d", 4, ["hostA", "hostB"], "hostA", floor=5)
    # an older grant never clobbers a newer registration
    assert t.install("d", 7, ["hostA", "hostB"], "hostA", floor=5)
    assert not t.install("d", 6, ["hostA", "hostB"], "hostA", floor=5)
    assert t.get("d").epoch == 7
    # idempotent re-install at the current epoch = renewal
    assert t.install("d", 7, ["hostA", "hostB"], "hostA", floor=5)


def test_drop_at_or_below_guards_replayed_demotes():
    t = WriterGroupTable("hostB", ttl_s=60.0)
    t.install("d", 7, ["hostA", "hostB"], "hostA", floor=0)
    # a demote fencing epoch 5 must not drop the NEWER group at 7
    assert not t.drop("d", at_or_below=5)
    assert t.get("d") is not None
    assert t.drop("d", at_or_below=7)
    assert t.get("d") is None
    assert not t.drop("d")                      # idempotent


def test_fence_below_is_the_floor_raise_hook():
    t = WriterGroupTable("hostB", ttl_s=60.0)
    t.install("d", 7, ["hostA", "hostB"], "hostA", floor=0)
    t.fence_below("d", 7)                       # floor == epoch: keeps
    assert t.get("d") is not None
    t.fence_below("d", 8)                       # floor passed it: drops
    assert t.get("d") is None


def test_journal_round_trip_restores_expired_and_skips_fenced(tmp_path):
    """Crash-restart: registrations survive via the replica journal,
    come back EXPIRED (accepting again takes a renewal through the
    leader), and entries below the restored fencing floor are gone —
    their group was superseded while we were down."""
    prefix = str(tmp_path / "rj")
    j = ReplicaJournal(prefix)
    t = WriterGroupTable("hostB", ttl_s=60.0)
    t.journal = j
    t.install("d", 7, ["hostA", "hostB"], "hostA", floor=0)
    t.install("e", 3, ["hostA", "hostB"], "hostA", floor=0)
    t.install("gone", 2, ["hostA", "hostB"], "hostA", floor=0)
    t.drop("gone")
    # crash: no close() — reopen replays the WAL
    j2 = ReplicaJournal(prefix)
    assert set(j2.restored_groups()) == {"d", "e"}
    t2 = WriterGroupTable("hostB", ttl_s=60.0)
    # the floor passed e's epoch while we were down
    assert t2.restore(j2, {"d": 0, "e": 5}.get) == 1
    assert t2.get("e") is None
    g = t2.get("d")
    assert g.epoch == 7 and g.members == ("hostA", "hostB")
    # restored EXPIRED: the entry exists but cannot admit
    assert t2.clock() >= g.expires_at
    # a restore-then-renewal round trip re-arms it
    assert not t2.refresh("d", 6)               # wrong epoch refused
    assert t2.refresh("d", 7)
    assert t2.clock() < t2.get("d").expires_at
    j2.close()


# ---- live mesh -----------------------------------------------------------

def test_promotion_runs_quorum_and_rekeys_lease():
    httpds, nodes, addrs = _mesh(3)
    try:
        doc = "wg-promote"
        _step(nodes)
        leader = next(n for n in nodes
                      if n.desired_owner(doc) == n.self_id)
        assert leader.owns(doc)
        e0 = leader.leases.active_epoch(doc)
        member = next(n for n in nodes if n is not leader)

        # a refused quorum round refuses the promotion outright
        real = leader._run_quorum
        leader._run_quorum = lambda d, e, t: False
        assert not leader.promote_writer_group(doc, [member.self_id])
        assert leader.writergroups.get(doc) is None
        assert leader.leases.active_epoch(doc) == e0
        leader._run_quorum = real

        assert leader.promote_writer_group(doc, [member.self_id])
        g = leader.writergroups.get(doc)
        assert g.leader == leader.self_id
        assert set(g.members) == {leader.self_id, member.self_id}
        # the lease was re-keyed to the ratified group epoch
        assert g.epoch > e0
        assert leader.leases.active_epoch(doc) == g.epoch
        # the member installed the grant with its floor raised to it
        gm = member.writergroups.get(doc)
        assert gm is not None and gm.epoch == g.epoch
        assert member.leases.max_epoch_of(doc) >= g.epoch
        # ...and admits locally, stamped with the group epoch
        assert member.group_accepts(doc)
        assert member.owns(doc)
        assert member.active_epoch(doc) == g.epoch
        assert member.metrics.get("writergroup", "member_admits") == 1
    finally:
        _teardown(httpds)


def test_stale_grant_refused_after_demotion():
    httpds, nodes, addrs = _mesh(3)
    try:
        doc = "wg-stale"
        leader, member = _promote(nodes, doc)
        old = leader.writergroups.get(doc).epoch
        assert leader.can_demote(doc)           # all members healthy
        assert leader.demote_writer_group(doc)
        assert leader.writergroups.get(doc) is None
        # the demotion epoch fenced the member (floor > group epoch)
        assert member.writergroups.get(doc) is None
        assert member.leases.max_epoch_of(doc) > old
        assert not member.group_accepts(doc)
        # a replayed grant from the superseded group is refused
        rejected0 = member.metrics.get("writergroup",
                                       "stale_installs_rejected")
        assert not member.writergroups.install(
            doc, old, [leader.self_id, member.self_id],
            leader.self_id, floor=member.leases.max_epoch_of(doc))
        # ...including over the wire
        resp = member.leases  # silence lint on unused locals
        out = leader.table.call_json(
            member.self_id, "/replicate/lease",
            {"action": "group", "doc": doc, "epoch": old,
             "members": [leader.self_id, member.self_id],
             "leader": leader.self_id, "ttl_s": 30.0})
        assert out["ok"] is False
        assert member.metrics.get(
            "writergroup", "stale_installs_rejected") > rejected0
        assert resp.max_epoch_of(doc) > old
    finally:
        _teardown(httpds)


def test_member_self_fences_on_group_quorum_loss():
    faults = FaultInjector(seed=3)
    httpds, nodes, addrs = _mesh(3, faults=faults, group_ttl_s=1.0)
    try:
        doc = "wg-fence"
        leader, member = _promote(nodes, doc)
        assert member.group_accepts(doc)
        # cut the member off from the leader (both directions): in a
        # 2-writer group the leader IS the quorum, so the member must
        # degrade to proxy-only immediately — no operator action
        faults.partition(member.self_id, leader.self_id)
        for _ in range(4):
            member.table.probe_once()
        assert not member.table.is_healthy(leader.self_id)
        assert not member.group_accepts(doc)
        assert not member.owns(doc)             # proxy-only now
        # the maintain loop then drops the expired registration (the
        # renewal path is cut), completing the self-fence
        deadline = member.clock() + 3 * member.writergroups.ttl_s
        while member.clock() < deadline \
                and member.writergroups.get(doc) is not None:
            member.maintain()
            time.sleep(0.02)
        assert member.writergroups.get(doc) is None
        assert member.metrics.get("writergroup", "self_fenced") >= 1
    finally:
        _teardown(httpds)


def test_demote_drains_member_write_back_to_single_writer():
    httpds, nodes, addrs = _mesh(3)
    try:
        doc = "wg-drain"
        leader, member = _promote(nodes, doc)
        # the member ACCEPTS a write locally under the group epoch
        body = (b'{"agent": "wg-agent", "version": [], "ops": '
                b'[{"kind": "ins", "pos": 0, "text": "member-write "}]}')
        req = urllib.request.Request(
            f"http://{member.self_id}/doc/{doc}/edit", data=body)
        with urllib.request.urlopen(req, timeout=5) as r:
            assert r.status == 200
        assert member.metrics.get("writergroup", "member_admits") >= 1
        # demotion drains the group back to one writer...
        assert leader.demote_writer_group(doc)
        assert leader.writergroups.get(doc) is None
        assert member.writergroups.get(doc) is None
        assert leader.leases.active_epoch(doc) > 0
        assert not member.group_accepts(doc)
        # ...without losing the member's acked write: after
        # reconciliation every server shows it byte-identically
        _step(nodes, rounds=4)
        texts = set()
        for a in addrs:
            with urllib.request.urlopen(f"http://{a}/doc/{doc}",
                                        timeout=5) as r:
                texts.add(r.read().decode("utf8"))
        assert len(texts) == 1
        assert "member-write" in texts.pop()
    finally:
        _teardown(httpds)
