"""ReplicaNode: one server's membership in the replication mesh.

Composes the peer table (health), membership view (who is in the
mesh), lease manager (ownership + quorum voter state), quorum
coordinator (majority rounds), replica journal (crash durability) and
anti-entropy loop (convergence) around a DocStore, and implements the
protocols the HTTP tier delegates to it:

  * mutation routing — `route_mutation(doc_id)` names the host that
    should apply a write (current lease holder when known and healthy,
    rendezvous owner otherwise); `proxy()` forwards the raw request
    body there, stamping the lease epoch it routed by as a fencing
    token (`X-DT-Lease-Epoch`). A receiver whose fencing floor has
    passed that epoch answers 409 — the write is NOT merged under a
    stale lease; the proxier falls back to accepting locally and
    anti-entropy reconciles once the new epoch propagates. When the
    target is simply unreachable the server also falls back to
    accepting locally (availability over placement);

  * quorum — lease acquisition, takeover, and handoff activation all
    run the promise round (quorum.QuorumCoordinator) against the
    membership voter set before a lease becomes ACTIVE;

  * membership — `/replicate/join` and `/replicate/leave` mutate the
    view explicitly; the probe loop feeds local health evidence into
    it and gossips member tables on every ping (peers.PeerTable
    `on_ping` hook). Rendezvous ownership is computed over
    `membership.universe()`, so lease migration on view changes is
    deterministic — every host recomputes the same owner from the
    same view;

  * handoff — `handoff(doc_id, new_owner)` drives the sender side of
    the lease state machine (see ownership.py):
    grant → drain pending merges → final patch transfer → activate
    (the receiver runs the quorum round for the new epoch before
    flipping GRANTED → ACTIVE);

  * crash recovery — when constructed with a `journal_prefix`, fencing
    floors, promises, held leases and the membership incarnation are
    journaled (quorum.ReplicaJournal). A restart restores them, bumps
    the incarnation, and boots into a fenced `rejoining` state: every
    merge admit is denied until a quorum of voters has been confirmed
    reachable (`maintain` clears it), so a node that slept through a
    takeover cannot merge under its pre-crash beliefs.

`maintain()` is the periodic control step (piggybacked on the probe
loop): clear rejoining when earned, renew held leases, and hand off
docs whose rendezvous owner moved (peer recovered, view changed).
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
from typing import List, Optional, Set, Tuple

from ..causalgraph.summary import intersect_with_summary
from ..encoding.encode import ENCODE_PATCH, encode_oplog
from ..obs.trace import NOOP_SPAN, TRACE_HEADER, format_context
from ..wire import WIRE_VERSION, WireChannel, WireError
from ..wire.frames import FRAME_OPS, encode_frame, encode_ops
from .antientropy import AntiEntropy
from .faults import FaultInjector
from .membership import ALIVE, LEFT, MembershipView
from .metrics import ReplicationMetrics
from .ownership import ACTIVE, DRAINING, TRANSFER, LeaseManager, owner_of
from .peers import PeerTable
from .quorum import QuorumCoordinator, ReplicaJournal
from .rebalance import PlacementOverrides
from .writergroup import WriterGroupTable

MUTATION_ACTIONS = ("push", "edit", "ops")


class ReplicaNode:
    def __init__(self, store, self_id: str, peer_addrs: List[str],
                 seed: int = 0, lease_ttl_s: float = 2.0,
                 probe_interval_s: float = 0.5,
                 antientropy_interval_s: float = 0.5,
                 timeout_s: float = 2.0, fail_threshold: int = 3,
                 backoff_base_s: float = 0.1,
                 backoff_cap_s: float = 5.0,
                 takeover_after_s: Optional[float] = None,
                 faults: Optional[FaultInjector] = None,
                 journal_prefix: Optional[str] = None,
                 obs=None, clock=None, table=None,
                 journal=None, wire_enabled: Optional[bool] = None,
                 snapshot_ops_threshold: Optional[int] = None,
                 group_ttl_s: Optional[float] = None) -> None:
        self.store = store
        self.self_id = self_id
        # clock/table/journal are dependency seams: the model checker
        # (analysis/explore) substitutes a virtual clock, a synchronous
        # simulated transport, and an in-memory journal so the real
        # protocol code runs under exhaustive scheduling. Production
        # callers leave all three None and get wall time + PeerTable +
        # file-backed ReplicaJournal, exactly as before.
        self.clock = time.monotonic if clock is None else clock
        self.started_at = self.clock()
        # how long a peer must stay continuously down before it is
        # declared DEAD and ownership reassigns its docs; defaults to
        # the lease TTL so a takeover can only be PROPOSED after the
        # old holder's lease has expired (the quorum round is what
        # makes the proposal safe)
        self.takeover_after_s = (lease_ttl_s if takeover_after_s is None
                                 else takeover_after_s)
        self.metrics = ReplicationMetrics(self_id)
        # wire tier: binary framing + per-channel transport accounting.
        # Negotiated per peer off ping gossip; framing can be pinned
        # off (JSON fallback) while accounting stays on.
        wire_opts = {} if snapshot_ops_threshold is None \
            else {"snapshot_ops_threshold": snapshot_ops_threshold}
        self.wire = WireChannel(metrics=self.metrics,
                                enabled=wire_enabled, **wire_opts)
        self.faults = faults
        if table is not None:
            self.table = table
            self.table.metrics = self.metrics
        else:
            self.table = PeerTable(self_id, peer_addrs,
                                   timeout_s=timeout_s,
                                   fail_threshold=fail_threshold,
                                   seed=seed,
                                   backoff_base_s=backoff_base_s,
                                   backoff_cap_s=backoff_cap_s,
                                   faults=faults, metrics=self.metrics)
        self.leases = LeaseManager(self_id, ttl_s=lease_ttl_s,
                                   metrics=self.metrics,
                                   clock=self.clock)
        # obs.Observability bundle (usually the DocStore's, via
        # attach_replication): spans on proxy/handoff/quorum, flight
        # recorder for lease/fencing/circuit events
        self.obs = obs
        if obs is not None:
            self.table.recorder = obs.recorder
            self.leases.recorder = obs.recorder
            # live telemetry: replication counters + quorum/handoff
            # latencies double-write into the windowed TimeSeries
            self.metrics.ts = getattr(obs, "ts", None)
            # journey: a peer's frontier advert closing the loop on a
            # tracked edit stamps `advert_usable` (read/follower.py)
            reads = getattr(store, "reads", None)
            if reads is not None:
                reads.index.journey = getattr(obs, "journey", None)
        # ---- crash-restart restore ----
        self.journal: Optional[ReplicaJournal] = None
        self.rejoining = False
        incarnation = 1
        if journal is not None:
            self.journal = journal
        elif journal_prefix is not None:
            self.journal = ReplicaJournal(journal_prefix)
        if self.journal is not None:
            self.rejoining = self.journal.has_prior_state()
            incarnation = self.journal.restored_incarnation() + 1
            self.journal.note_incarnation(incarnation)
            self.leases.restore(self.journal)
        # writer groups (replicate/writergroup.py): hot-doc write
        # splitting. Restored after the lease floors so a registration
        # a journaled floor supersedes is never resurrected; restored
        # entries come back EXPIRED (accepting again takes a renewal).
        self.writergroups = WriterGroupTable(
            self_id,
            ttl_s=lease_ttl_s * 2 if group_ttl_s is None
            else group_ttl_s,
            metrics=self.metrics, clock=self.clock)
        if self.journal is not None:
            self.writergroups.restore(
                self.journal,
                lambda d: self.leases.max_epoch.get(d, 0))
        # fencing floor raises fence superseded group registrations in
        # the same lease-lock critical section (no admit can interleave)
        self.leases.on_floor_raise = self.writergroups.fence_below
        # seam for the model checker's demote-without-drain mutation:
        # the member-side demotion fence drains pending admissions into
        # the oplog before evicting its queue iff this flag stands
        self._group_demote_drains = True
        self.membership = MembershipView(self_id, incarnation,
                                         metrics=self.metrics)
        # bootstrap peers start ALIVE (assumed healthy until the probe
        # loop says otherwise — same optimism the static table had)
        for addr in self.table.peer_ids():
            self.membership.add(addr, state=ALIVE)
        self.quorum = QuorumCoordinator(self)
        self.leases.quorum = self._run_quorum
        self.table.on_ping = self._on_ping
        # elastic-mesh tier (replicate/rebalance.py): the placement-
        # override table layered over rendezvous hashing. Restored from
        # the journal, gossiped on pings, consulted by desired_owner —
        # so routing, the merge-admission gate and the maintain loop
        # all follow an override the moment it lands.
        self.overrides = PlacementOverrides(journal=self.journal,
                                            metrics=self.metrics)
        # gossiped held-lease counts (ping "load" field): the
        # rebalancer's target-selection signal. A just-joined host has
        # no entry and reads as load 0 — the preferred target.
        self.peer_load = {}
        # attach_rebalancer hangs the SLO-driven control loop here; the
        # probe loop ticks it after maintain()
        self.rebalancer = None
        # follower->follower frontier advert relay: doc -> (origin,
        # frontier, hops, heard_at). Entries at hops <= max_relay_hops
        # ride our ping bodies so a follower two hops from the owner
        # still gets staleness evidence without an owner link.
        self._relay_adverts = {}
        self.max_relay_hops = 1
        self.antientropy = AntiEntropy(
            self, interval_s=antientropy_interval_s)
        self.probe_interval_s = probe_interval_s
        # docs whose merges this host has admitted — the test surface
        # for the exactly-one-merger property
        self.merged_docs: Set[str] = set()
        from ..analysis.witness import make_lock
        self._maintain_lock = make_lock("repl.maintain", "repl.maintain")
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ---- quorum hook -----------------------------------------------------

    def _run_quorum(self, doc_id: str, epoch: int,
                    takeover: bool) -> bool:
        """LeaseManager's acquisition hook. A rejoining node may not
        propose — it must first re-earn its place (maintain clears the
        state once a quorum of voters is confirmed reachable)."""
        if self.rejoining:
            return False
        return self.quorum.acquire(doc_id, epoch, takeover)

    # ---- ownership -------------------------------------------------------

    def _sync_membership(self) -> None:
        """Fold the probe loop's health evidence into the membership
        view: reachable → ALIVE, down < takeover_after_s → SUSPECT
        (still in the rendezvous universe, so a short partition does
        not collapse each side's host set to itself), down past it →
        DEAD (out of the universe; its docs reassign — safely, because
        reassignment still needs a quorum)."""
        now = self.clock()
        for p in self.table.peer_ids():
            self.membership.note_health(
                p, self.table.down_duration(p, now),
                self.takeover_after_s)

    def ownership_ids(self) -> List[str]:
        """Hosts rendezvous ownership is computed over — the
        membership universe (ALIVE + SUSPECT + JOINING, always
        including self)."""
        self._sync_membership()
        return self.membership.universe()

    def desired_owner(self, doc_id: str) -> str:
        """Placement: the override table wins when its target is still
        in the ownership universe; rendezvous hashing otherwise. An
        override pointing at a DEAD/LEFT host is simply ignored, so a
        failed migration target never strands a doc."""
        ids = self.ownership_ids()
        override = self.overrides.target_of(doc_id)
        if override is not None and override in ids:
            return override
        return owner_of(doc_id, ids)

    def owns(self, doc_id: str) -> bool:
        """The scheduler's merge-admission gate: True iff this host
        holds (or can now acquire, quorum permitting) the doc's ACTIVE
        lease. Denied outright while rejoining after a crash."""
        if self.rejoining:
            self.metrics.bump("fencing", "rejoin_denials")
            self.metrics.bump("merge_gate", "denials")
            return False
        if self.group_accepts(doc_id):
            # writer-group member in good standing: admitted locally,
            # stamped with the group epoch (active_epoch below)
            self.metrics.bump("merge_gate", "admits")
            self.metrics.bump("writergroup", "member_admits")
            self.merged_docs.add(doc_id)
            return True
        ok = self.leases.ensure_local(
            doc_id, self.desired_owner(doc_id) == self.self_id)
        self.metrics.bump("merge_gate", "admits" if ok else "denials")
        if ok:
            self.merged_docs.add(doc_id)
        return ok

    def active_epoch(self, doc_id: str) -> int:
        """Scheduler fencing callback: epoch of the ACTIVE lease this
        host holds for the doc — or the group epoch when we write as a
        group member — 0 when neither stands."""
        epoch = self.leases.active_epoch(doc_id)
        if epoch:
            return epoch
        if self.group_accepts(doc_id):
            g = self.writergroups.get(doc_id)
            if g is not None:
                return g.epoch
        return 0

    # ---- writer groups (replicate/writergroup.py) ------------------------

    def group_accepts(self, doc_id: str) -> bool:
        """May this host accept writes for `doc_id` as a writer-group
        MEMBER? (The leader admits through its own ACTIVE lease.)
        Pure read — no state is mutated, so the model checker can use
        it as action enabledness. False once the registration expired
        un-renewed, once the fencing floor passed the group epoch, or
        when the leader plus a majority of the group is unreachable —
        the self-fence: a cut-off member degrades to proxy-only rather
        than accepting writes the group may already have fenced away."""
        if self.rejoining:
            return False
        g = self.writergroups.get(doc_id)
        if g is None or g.leader == self.self_id:
            return False
        if g.epoch < self.leases.max_epoch_of(doc_id):
            return False      # belt: fence_below drops these eagerly
        if self.clock() >= g.expires_at:
            return False
        if not self.table.is_healthy(g.leader):
            return False
        reach = sum(1 for m in g.members
                    if m == self.self_id or self.table.is_healthy(m))
        return reach >= g.quorum_size()

    def promote_writer_group(self, doc_id: str,
                             members: List[str]) -> bool:
        """Split `doc_id`'s write path: promote our single ACTIVE lease
        to a writer group of `members` (us included) at a bumped epoch.
        The epoch is planned exactly like any acquisition
        (`max(lease.epoch, floor) + 1`), ratified by a majority promise
        round, and committed by re-keying our lease; members get a
        directed group grant whose install raises their fencing floor
        to the group epoch. A member that misses its grant simply never
        co-writes — convergence does not depend on it."""
        if self.rejoining:
            return False
        member_set = sorted(set(members) | {self.self_id})
        if len(member_set) < 2:
            return False
        if self.writergroups.get(doc_id) is not None:
            return False
        with self.leases.lock:
            lease = self.leases.leases.get(doc_id)
            if lease is None or lease.holder != self.self_id \
                    or lease.state != ACTIVE:
                return False
            epoch = max(lease.epoch,
                        self.leases.max_epoch.get(doc_id, 0)) + 1
        if not self._run_quorum(doc_id, epoch, False):
            return False
        if not self.leases.promote_epoch(doc_id, epoch):
            return False     # revoked between the round and the rekey
        self.writergroups.install(
            doc_id, epoch, member_set, self.self_id,
            floor=self.leases.max_epoch_of(doc_id))
        self.metrics.bump("writergroup", "promotions")
        if self.obs is not None:
            self.obs.recorder.record("group_promoted", doc=doc_id,
                                     epoch=epoch, members=member_set)
        grant = {"action": "group", "doc": doc_id, "epoch": epoch,
                 "members": member_set, "leader": self.self_id,
                 "ttl_s": self.writergroups.ttl_s}
        for m in member_set:
            if m == self.self_id:
                continue
            try:
                self.table.call_json(m, "/replicate/lease", grant)
                self.metrics.bump("writergroup", "member_grants")
            except (OSError, KeyError, ValueError,
                    urllib.error.HTTPError):
                continue
        return True

    def can_demote(self, doc_id: str) -> bool:
        """Would `demote_writer_group` commit right now? True when we
        lead the group with an ACTIVE lease and every other member is
        reachable (drainable) or the registration TTL has provably
        expired (a silent member can no longer be accepting)."""
        g = self.writergroups.get(doc_id)
        if g is None or g.leader != self.self_id:
            return False
        lease = self.leases.get(doc_id)
        if lease is None or lease.holder != self.self_id \
                or lease.state != ACTIVE:
            return False
        if self.clock() >= g.expires_at:
            return True
        return all(self.table.is_healthy(m) for m in g.members
                   if m != self.self_id)

    def demote_writer_group(self, doc_id: str) -> bool:
        """Drain the group back to a single writer (us) — the
        robustness centerpiece. The demotion epoch `group_epoch + 1`
        wins a majority round, every reachable member is fenced (it
        drains pending admissions into its oplog, drops the
        registration and evicts its queue), and only then is our lease
        re-keyed. An unreachable member blocks the demotion until its
        registration TTL has expired: committing earlier would let a
        silent-but-alive member keep accepting writes under the
        superseded epoch."""
        g = self.writergroups.get(doc_id)
        if g is None or g.leader != self.self_id:
            return False
        now = self.clock()
        expired = now >= g.expires_at
        others = [m for m in g.members if m != self.self_id]
        if not expired:
            for m in others:
                if not self.table.is_healthy(m):
                    self.metrics.bump("writergroup", "demote_aborts")
                    return False
        with self.leases.lock:
            lease = self.leases.leases.get(doc_id)
            if lease is None or lease.holder != self.self_id \
                    or lease.state != ACTIVE:
                return False
            epoch = max(lease.epoch,
                        self.leases.max_epoch.get(doc_id, 0)) + 1
        if not self._run_quorum(doc_id, epoch, False):
            self.metrics.bump("writergroup", "demote_aborts")
            return False
        demote = {"action": "group-demote", "doc": doc_id,
                  "epoch": epoch, "leader": self.self_id}
        for m in others:
            try:
                self.table.call_json(m, "/replicate/lease", demote)
            except (OSError, KeyError, ValueError,
                    urllib.error.HTTPError):
                # unreachable member: its registration is past TTL
                # (checked above) or fenced by the quorum round's
                # floor raise the moment it reconnects
                continue
        if not self.leases.promote_epoch(doc_id, epoch):
            self.metrics.bump("writergroup", "demote_aborts")
            return False
        self.writergroups.drop(doc_id)
        self.metrics.bump("writergroup", "demotions")
        if self.obs is not None:
            self.obs.recorder.record("group_demoted", doc=doc_id,
                                     epoch=epoch)
        return True

    def _group_fence_local(self, doc_id: str,
                           epoch: Optional[int] = None) -> bool:
        """Member-side demotion fence: drain pending admissions into
        the oplog, drop the registration (only at or below `epoch` — a
        replayed demote must not fence a newer group), and evict the
        admission queue. The drain barrier is what `no-acked-loss`
        guards: eviction without it discards acked work (the
        demote-without-drain seeded mutation)."""
        g = self.writergroups.get(doc_id)
        if g is not None and epoch is not None and g.epoch > epoch:
            return False
        try:
            if self._group_demote_drains:
                sched = getattr(self.store, "scheduler", None)
                if sched is not None:
                    sched.drain()
        finally:
            # the fence completes whatever the drain did: a device
            # error out of an inline drain (mesh windows) surfaces to
            # the caller AFTER the registration is gone, so a demoted
            # member can never keep admitting (every admitted op is in
            # the oplog already; the drain only catches the device
            # session up)
            self.writergroups.drop(doc_id, at_or_below=epoch)
            pending = getattr(self.store, "pending", None)
            if pending is not None:
                pending.pop(doc_id, None)
        return True

    def route_mutation(self, doc_id: str) -> str:
        """The host a write for `doc_id` should land on."""
        holder = self.leases.holder_of(doc_id)
        if holder is not None and (holder == self.self_id
                                   or self.table.is_healthy(holder)):
            return holder
        return self.desired_owner(doc_id)

    # ---- proxy -----------------------------------------------------------

    def proxy(self, target: str, path: str, body: bytes,
              doc_id: Optional[str] = None,
              trace=None,
              qos: Optional[str] = None) -> Optional[Tuple[int, bytes]]:
        """Forward a mutation to its owner, stamping the lease epoch we
        routed by (the fencing token). Returns (status, body) to relay,
        or None when the caller should accept locally instead: target
        unreachable, or target fenced the epoch (our routing info was
        stale — anti-entropy reconciles once the new lease propagates).
        `trace` (obs SpanContext of the local HTTP span) rides the
        X-DT-Trace header so the owner's handling joins the trace;
        `qos` rides X-DT-QoS so the owner admits the work under the
        class the edge classified (a proxied hop must not be
        re-classified as replication traffic)."""
        headers = {"X-DT-Proxied": "1"}
        if qos is not None:
            headers["X-DT-QoS"] = qos
        if doc_id is not None:
            lease = self.leases.get(doc_id)
            if lease is not None and lease.holder == target:
                headers["X-DT-Lease-Epoch"] = str(lease.epoch)
        span = NOOP_SPAN
        if self.obs is not None:
            span = self.obs.tracer.start(
                "repl.proxy", parent=trace,
                attrs={"target": target, "doc": doc_id})
        ctx = span.context() if span.sampled else trace
        if ctx is not None:
            headers[TRACE_HEADER] = format_context(ctx)
        # wire tier: a JSON edit body proxied to a v1 peer rides as one
        # OPS frame (the receiver sniffs the magic). Any re-encode
        # hiccup just sends the original JSON — correctness never
        # depends on the frame path.
        send_body, framed = body, False
        if path.endswith("/edit") and self.wire.use_wire(target):
            try:
                frame = encode_frame(FRAME_OPS,
                                     encode_ops(json.loads(body)),
                                     compress=True)
                if len(frame) < len(body):
                    send_body, framed = frame, True
            except (ValueError, KeyError, TypeError, WireError):
                pass
        try:
            try:
                status, resp = self.table.call(target, path,
                                               data=send_body,
                                               headers=headers)
                self.wire.account("proxy", sent_bytes=len(send_body),
                                  json_bytes=len(body), framed=framed)
            except urllib.error.HTTPError as e:
                # owner answered with an application error: relay it
                status, resp = e.code, e.read()
            except OSError:
                self.metrics.bump("proxy", "fallback_local")
                span.annotate(outcome="fallback_local")
                return None
            if status == 409:
                try:
                    fenced = json.loads(resp or b"{}").get("error") \
                        == "fenced"
                except ValueError:
                    fenced = False
                if fenced:
                    self.metrics.bump("proxy", "fenced_relays")
                    span.annotate(outcome="fenced")
                    return None
            self.metrics.bump("proxy", "proxied")
            span.annotate(outcome="proxied", status=status)
            return status, resp
        finally:
            span.end()

    def check_write_fence(self, doc_id: str,
                          claimed_epoch: int) -> bool:
        """Receiver side of write fencing: may a proxied mutation
        claiming `claimed_epoch` be applied to `doc_id`? False when the
        fencing floor has passed the claim — the proxier routed by a
        lease that has been superseded."""
        floor = self.leases.max_epoch_of(doc_id)
        if claimed_epoch >= floor:
            return True
        self.metrics.bump("fencing", "rejected_writes")
        if self.obs is not None:
            self.obs.recorder.record("fencing_rejected", doc=doc_id,
                                     claimed_epoch=claimed_epoch,
                                     floor=floor)
        return False

    # ---- handoff (sender) ------------------------------------------------

    def handoff(self, doc_id: str, new_owner: str,
                override_version: Optional[int] = None) -> bool:
        """Move doc ownership to `new_owner` without ever having two
        active mergers: grant → drain → final patch → activate (the
        receiver's activate runs the quorum round for the new epoch).
        Any failure aborts back to ACTIVE (the remote GRANTED lease
        simply expires); one that is not a peer's or the wire's — a
        device error out of the drain — is re-raised after the abort.
        `override_version` (rebalancer migrations) ships the
        placement-override entry ON the grant message, so the receiver
        keeps the doc instead of rendezvous handing it back."""
        t0 = time.monotonic()
        new_epoch = self.leases.begin_handoff(doc_id)
        if new_epoch is None:
            return False
        self.metrics.bump("handoffs", "started")
        span = NOOP_SPAN
        if self.obs is not None:
            span = self.obs.tracer.start(
                "repl.handoff", attrs={"doc": doc_id, "to": new_owner,
                                       "epoch": new_epoch})

        def phase(name):
            # child span per handoff stage; the grant/activate calls
            # carry the trace header so the receiver's lease handling
            # joins the same trace
            if not span.sampled:
                return NOOP_SPAN
            return self.obs.tracer.start(name, parent=span.context())

        hdrs = {TRACE_HEADER: span.header()} if span.sampled else None
        try:
            # grant: the receiver records a not-yet-active lease (its
            # TTL covers the whole handoff, so a crashed sender leaves
            # a lease that expires rather than a stuck doc)
            with phase("repl.handoff.grant"):
                grant = {"action": "grant", "doc": doc_id,
                         "epoch": new_epoch,
                         "ttl_s": self.leases.ttl_s * 4}
                if override_version is not None:
                    grant["override"] = [doc_id, new_owner,
                                         int(override_version)]
                resp = self.table.call_json(
                    new_owner, "/replicate/lease", grant, headers=hdrs)
                if not resp.get("ok"):
                    raise ValueError(f"grant refused: {resp!r}")
            # drain: flush our pending merge work for the doc so the
            # final patch includes every admitted op
            with phase("repl.handoff.drain"):
                td = time.monotonic()
                self.leases.advance_handoff(doc_id, DRAINING)
                sched = getattr(self.store, "scheduler", None)
                if sched is not None:
                    sched.drain()
                self.metrics.observe_latency("rebalance_drain",
                                             time.monotonic() - td)
            # final patch transfer (from the receiver's common version)
            with phase("repl.handoff.transfer"):
                self.leases.advance_handoff(doc_id, TRANSFER)
                remote_summary = self.table.call_json(
                    new_owner, f"/doc/{doc_id}/summary")
                ol = self.store.get(doc_id)
                with self.store.lock:
                    common, _rem = intersect_with_summary(
                        ol.cg, remote_summary)
                    patch = None
                    if sorted(common) != sorted(ol.version):
                        patch = encode_oplog(ol, ENCODE_PATCH,
                                             from_version=common)
                if patch is not None:
                    self.table.call(new_owner, f"/doc/{doc_id}/push",
                                    data=patch)
            # activate: receiver runs the quorum round for new_epoch,
            # then flips GRANTED -> ACTIVE; we release
            with phase("repl.handoff.activate"):
                resp = self.table.call_json(
                    new_owner, "/replicate/lease",
                    {"action": "activate", "doc": doc_id,
                     "epoch": new_epoch},
                    headers=hdrs)
                if not resp.get("ok"):
                    raise ValueError(f"activate refused: {resp!r}")
            self.leases.finish_handoff(doc_id, new_owner, new_epoch)
            self.metrics.bump("handoffs", "completed")
            self.metrics.observe_handoff_latency(time.monotonic() - t0)
            span.end(outcome="completed")
            return True
        except Exception as e:
            self.leases.abort_handoff(doc_id)
            self.metrics.bump("handoffs", "failed")
            if self.obs is not None:
                self.obs.recorder.record(
                    "handoff_failed", doc=doc_id, to=new_owner,
                    epoch=new_epoch,
                    error=f"{e.__class__.__name__}: {e}"[:120])
            span.end(outcome="failed")
            if isinstance(e, (OSError, ValueError, KeyError,
                              urllib.error.HTTPError)):
                return False
            # anything else — a device error out of the inline drain —
            # is not a peer's refusal: the handoff is aborted back to
            # ACTIVE like any other (the doc stays with an owner whose
            # oplog is whole) and the error goes on to the caller
            raise

    # ---- lease wire handler (receiver) -----------------------------------

    def handle_lease_message(self, req: dict) -> dict:
        action = req.get("action")
        doc_id = req.get("doc")
        if not isinstance(doc_id, str) or not doc_id:
            return {"ok": False, "error": "bad doc"}
        epoch = int(req.get("epoch", 0))
        if action == "propose":
            holder = req.get("holder")
            if not isinstance(holder, str) or not holder:
                return {"ok": False, "error": "bad holder"}
            ok, reason = self.leases.promise(doc_id, epoch, holder)
            return {"ok": ok, "reason": reason,
                    "max_epoch": self.leases.max_epoch_of(doc_id)}
        if action == "grant":
            ok = self.leases.accept_grant(
                doc_id, epoch, float(req.get("ttl_s", 0.0)))
            if ok and req.get("override") is not None:
                # rebalancer migration rider: install the placement
                # override atomically with the grant, so our own
                # maintain loop keeps the doc once it activates
                self.overrides.merge([req["override"]])
            return {"ok": ok}
        if action == "activate":
            # the handoff's quorum round: the new epoch must win a
            # majority before this node becomes the active merger
            if not self._run_quorum(doc_id, epoch, False):
                return {"ok": False, "error": "quorum"}
            ok = self.leases.activate_grant(doc_id, epoch)
            if ok:
                self._pin_migrated_doc(doc_id)
            return {"ok": ok}
        if action == "group":
            # writer-group grant (leader -> member): fold the leader's
            # lease claim at the group epoch FIRST — that raises our
            # fencing floor to it — then register. A replayed grant
            # from a superseded group fails the install's floor check.
            members = req.get("members")
            leader = req.get("leader")
            if not isinstance(leader, str) or not leader \
                    or not isinstance(members, list) \
                    or self.self_id not in members:
                return {"ok": False, "error": "bad group"}
            self.leases.observe_remote(doc_id, leader, epoch, ACTIVE,
                                       float(req.get("ttl_s", 0.0)))
            ok = self.writergroups.install(
                doc_id, epoch, [str(m) for m in members], leader,
                floor=self.leases.max_epoch_of(doc_id))
            if ok:
                self.metrics.bump("writergroup", "member_grants")
            else:
                self.metrics.bump("writergroup",
                                  "stale_installs_rejected")
            return {"ok": ok}
        if action == "group-renew":
            # member -> leader: extend the member's registration while
            # the group at that epoch is still current on our side
            member = req.get("member")
            g = self.writergroups.get(doc_id)
            if g is None or g.leader != self.self_id \
                    or g.epoch != epoch or member not in g.members:
                self.metrics.bump("writergroup", "renewal_denials")
                return {"ok": False}
            self.writergroups.refresh(doc_id, epoch)
            self.metrics.bump("writergroup", "renewals")
            return {"ok": True, "ttl_s": self.writergroups.ttl_s}
        if action == "group-demote":
            # leader -> member: the demotion epoch has won its quorum
            # round. Raise our floor to it (the promise is idempotent;
            # a refusal means the floor already passed it) and fence:
            # drain, drop the registration, evict the queue.
            leader = req.get("leader")
            if isinstance(leader, str) and leader:
                self.leases.promise(doc_id, epoch, leader)
            self._group_fence_local(doc_id, epoch - 1)
            return {"ok": True}
        if action == "status":
            lease = self.leases.get(doc_id)
            g = self.writergroups.get(doc_id)
            return {"ok": True,
                    "lease": lease.as_json() if lease else None,
                    "desired": self.desired_owner(doc_id),
                    "max_epoch": self.leases.max_epoch_of(doc_id),
                    "group": g.as_json(self.clock())
                    if g is not None else None,
                    "rejoining": self.rejoining}
        return {"ok": False, "error": f"bad action {action!r}"}

    def _pin_migrated_doc(self, doc_id: str) -> None:
        """On activating a migrated doc, steer it onto this host's
        least-loaded shard (ShardRouter.pin). Rendezvous shard routing
        knows nothing about load, and a doc hot enough to migrate is
        hot enough to deserve the emptiest chip. Best-effort: no
        scheduler/router (raw stores, sims) means no pin."""
        if self.overrides.target_of(doc_id) != self.self_id:
            return
        sched = getattr(self.store, "scheduler", None)
        router = getattr(sched, "router", None)
        if router is None or router.n_shards < 2:
            return
        try:
            counts = router.counts()
            router.pin(doc_id, counts.index(min(counts)))
        except (ValueError, AttributeError):  # pragma: no cover
            pass

    # ---- membership wire handlers ----------------------------------------

    def ping_json(self) -> dict:
        """Body of `GET /replicate/ping` — health ack + gossip
        piggyback (the probe loop is the gossip transport)."""
        out = {"ok": True, "id": self.self_id,
               "uptime_s": round(self.clock() - self.started_at, 3),
               "incarnation": self.membership.self_incarnation,
               "view_version": self.membership.view_version,
               "rejoining": self.rejoining,
               # held-lease count: the rebalancer's load signal
               "load": self.leases.held_count(),
               "members": self.membership.gossip_payload()}
        # wire capability gossip: POST bodies can only be framed once
        # the sender KNOWS the receiver decodes frames, and ping is the
        # one channel every peer already exchanges
        if self.wire.enabled:
            out["wire"] = WIRE_VERSION
        overrides = self.overrides.gossip_payload()
        if overrides:
            out["overrides"] = overrides
        frontiers = self._owned_frontiers()
        if frontiers is not None:
            out["frontiers"] = frontiers
            relayed = self._relayed_frontiers()
            if relayed:
                out["relayed_frontiers"] = relayed
                self.metrics.bump("antientropy", "adverts_relayed",
                                  len(relayed))
        return out

    def _owned_frontiers(self, cap: int = 32):
        """Frontier advertisements for the follower-read tier: the
        current frontier of every doc whose ACTIVE lease we hold
        (capped — ping bodies must stay small). None when follower
        reads aren't attached anywhere, so the ping body is unchanged
        on meshes without the feature."""
        if getattr(self.store, "reads", None) is None:
            return None
        held = self.leases.held_ids()[:cap]
        if not held:
            return {}
        frontiers = {}
        with self.store.lock:
            for doc_id in held:
                ol = self.store.docs.get(doc_id)
                if ol is not None:
                    frontiers[doc_id] = \
                        ol.cg.local_to_remote_frontier(ol.version)
        return frontiers

    def _relayed_frontiers(self, cap: int = 32):
        """Follower->follower advert relay: re-advertise frontiers we
        heard DIRECTLY from their owners (hops <= max_relay_hops), so a
        follower without an owner link still accumulates staleness
        evidence. Entries age out after a few probe intervals — a
        relay must never outlive the evidence it carries."""
        now = self.clock()
        ttl = max(self.probe_interval_s * 6, 3.0)
        stale = [d for d, (_o, _f, _h, at) in
                 self._relay_adverts.items() if now - at > ttl]
        for d in stale:
            self._relay_adverts.pop(d, None)
        out = {}
        for doc_id, (origin, frontier, hops, _at) in \
                sorted(self._relay_adverts.items())[:cap]:
            if hops <= self.max_relay_hops:
                out[doc_id] = [origin, frontier, hops]
        return out

    def _on_ping(self, peer_id: str, body: dict) -> None:
        """Probe-loop gossip hook: fold the responder's member table,
        and open transport to any member we just learned about."""
        # wire capability: absent/0 = JSON-only peer (old build, or
        # framing pinned off) — every POST body to it stays JSON
        self.wire.note_peer(peer_id, body.get("wire"))
        members = body.get("members")
        if isinstance(members, dict):
            self.membership.merge_remote(members)
            for mid, info in members.items():
                if isinstance(info, dict) \
                        and info.get("state") != LEFT:
                    self.table.add_peer(mid)
        # rebalancer gossip: the responder's held-lease count (target
        # selection) and its placement-override table (LWW merge)
        load = body.get("load")
        if isinstance(load, int):
            self.peer_load[peer_id] = load
        overrides = body.get("overrides")
        if overrides:
            self.overrides.merge(overrides)
        # frontier advertisements for the follower-read tier: the
        # responder gossips the frontiers of docs it holds ACTIVE
        # leases on. Fold time stands in for send time (sub-RTT slop;
        # the staleness contract's useful bounds are >= hundreds of ms).
        frontiers = body.get("frontiers")
        reads = getattr(self.store, "reads", None)
        if reads is not None and isinstance(frontiers, dict):
            now = self.clock()
            for doc_id, frontier in frontiers.items():
                if frontier:
                    reads.index.note_advert(doc_id, peer_id, frontier)
                    # owner-direct advert: candidate for one relay hop
                    self._relay_adverts[doc_id] = (peer_id, frontier,
                                                   1, now)
            if frontiers:
                self.metrics.bump("antientropy", "frontier_adverts",
                                  len(frontiers))
        # relayed adverts: credit the ORIGIN owner, with the advert
        # aged by the relay hops (one probe interval per hop) so the
        # staleness contract stays conservative
        relayed = body.get("relayed_frontiers")
        if reads is not None and isinstance(relayed, dict):
            for doc_id, row in relayed.items():
                if not (isinstance(row, list) and len(row) == 3):
                    continue
                origin, frontier, hops = row
                if origin == self.self_id or not frontier:
                    continue
                age = self.probe_interval_s * max(int(hops), 1)
                reads.index.note_advert(
                    doc_id, origin, frontier,
                    as_of=time.monotonic() - age)

    def handle_join(self, req: dict) -> dict:
        """`POST /replicate/join` — a node announces itself (bootstrap
        or re-join after restart, with a bumped incarnation). Gossip
        spreads the new member from here; the response carries our
        member table so the joiner learns the mesh in one round trip."""
        member_id = req.get("id")
        if not isinstance(member_id, str) or not member_id:
            return {"ok": False, "error": "bad id"}
        incarnation = int(req.get("incarnation", 0))
        self.table.add_peer(member_id)
        self.membership.add(member_id, state=ALIVE,
                            incarnation=incarnation)
        return {"ok": True, "self": self.self_id,
                "members": self.membership.gossip_payload(),
                "peers": self.table.all_ids()}

    def handle_leave(self, req: dict) -> dict:
        """`POST /replicate/leave` — explicit, operator-driven removal:
        the ONLY operation that shrinks the quorum denominator."""
        member_id = req.get("id")
        if not isinstance(member_id, str) or not member_id:
            return {"ok": False, "error": "bad id"}
        left = self.membership.leave(member_id)
        self.table.remove_peer(member_id)
        return {"ok": True, "left": left}

    def join_mesh(self, seed_addr: str) -> bool:
        """Announce ourselves to `seed_addr` and adopt its view (used
        by `serve --join` and the chaos soak's churn phase)."""
        self.table.add_peer(seed_addr)
        self.membership.add(seed_addr, state=ALIVE)
        try:
            resp = self.table.call_json(
                seed_addr, "/replicate/join",
                {"id": self.self_id,
                 "incarnation": self.membership.self_incarnation})
        except (OSError, urllib.error.HTTPError, ValueError):
            return False
        if not resp.get("ok"):
            return False
        members = resp.get("members")
        if isinstance(members, dict):
            self._on_ping(seed_addr, {"members": members})
        return True

    # ---- periodic control ------------------------------------------------

    def _rejoin_check(self) -> None:
        """Clear the post-crash `rejoining` fence once a quorum of
        voters is confirmed reachable (probed OK at least once, circuit
        closed). Until then every merge admit is denied."""
        if not self.rejoining:
            return
        confirmed = 1       # self
        for v in self.membership.voters():
            if v == self.self_id:
                continue
            st = self.table.peers.get(v)
            if st is not None and st.last_ok is not None \
                    and st.open_until == 0.0:
                confirmed += 1
        if confirmed >= self.membership.quorum_size():
            self.rejoining = False
            self.metrics.bump("quorum", "rejoins_completed")

    def maintain(self) -> dict:
        """Clear rejoining when earned; renew held leases; hand off
        docs whose rendezvous owner moved to a healthy peer.
        Serialized (probe loop + manual test calls must not race two
        handoffs for one doc)."""
        out = {"renewed": 0, "handoffs": 0, "group_renewed": 0,
               "group_demotions": 0, "group_fenced": 0}
        with self._maintain_lock:
            self._sync_membership()
            self._rejoin_check()
            if self.rejoining:
                return out
            for doc_id in self.leases.held_ids():
                # a doc we lead a writer group for must NOT hand off on
                # rendezvous drift — the group is pinned to its leader;
                # demotion is the only exit
                g = self.writergroups.get(doc_id)
                desired = self.desired_owner(doc_id)
                if desired == self.self_id or (
                        g is not None and g.leader == self.self_id):
                    self.leases.ensure_local(doc_id, True)
                    out["renewed"] += 1
                elif self.table.is_healthy(desired):
                    if self.handoff(doc_id, desired):
                        out["handoffs"] += 1
            self._group_maintain(out)
        return out

    def _group_maintain(self, out: dict) -> None:
        """Writer-group upkeep on the maintain tick. Leaders demote
        groups with a crashed/partitioned member (the demote itself
        waits out the registration TTL when the member is silent — no
        operator action either way). Members renew their registration
        through the leader and self-fence once it expired un-renewed."""
        for doc_id, g in self.writergroups.entries():
            if g.leader == self.self_id:
                # member renewals are the group's liveness signal: an
                # expired registration means no member renewed for a
                # whole TTL even if probes look healthy (the asymmetric
                # partition — members can't reach us, we still hear
                # them), so it demotes exactly like an unhealthy member
                if self.clock() >= g.expires_at \
                        or any(not self.table.is_healthy(m)
                               for m in g.members
                               if m != self.self_id):
                    if self.demote_writer_group(doc_id):
                        out["group_demotions"] += 1
                continue
            renewed = False
            if self.table.is_healthy(g.leader):
                try:
                    resp = self.table.call_json(
                        g.leader, "/replicate/lease",
                        {"action": "group-renew", "doc": doc_id,
                         "epoch": g.epoch, "member": self.self_id})
                except (OSError, KeyError, ValueError,
                        urllib.error.HTTPError):
                    resp = None
                if resp is not None and resp.get("ok"):
                    self.writergroups.refresh(doc_id, g.epoch)
                    out["group_renewed"] += 1
                    renewed = True
                elif resp is not None:
                    # the leader no longer recognizes this group (it
                    # demoted, re-acquired, or restarted): fence now
                    self._group_fence_local(doc_id, g.epoch)
                    self.metrics.bump("writergroup", "self_fenced")
                    out["group_fenced"] += 1
                    continue
            if not renewed and self.clock() >= g.expires_at:
                self._group_fence_local(doc_id, g.epoch)
                self.metrics.bump("writergroup", "self_fenced")
                out["group_fenced"] += 1

    # ---- docs listing (for anti-entropy peers) ---------------------------

    def docs_json(self) -> dict:
        now = self.clock()
        doc_ids = self.store.doc_ids()
        # frontier advertisement per IN-MEMORY doc (not-yet-loaded .dt
        # files aren't worth a load just to advertise). Always included:
        # the follower-read tier folds them as staleness evidence, and
        # anti-entropy's frontier short-circuit skips the whole per-doc
        # summary round trip when the advertised frontier matches.
        # Computed under the store's oplog guard BEFORE the lease guard
        # below — the two are never nested.
        frontiers = {}
        with self.store.lock:
            for doc_id, ol in self.store.docs.items():
                frontiers[doc_id] = \
                    ol.cg.local_to_remote_frontier(ol.version)
        docs = {}
        with self.leases.lock:
            for doc_id in doc_ids:
                lease = self.leases.leases.get(doc_id)
                docs[doc_id] = {
                    "lease": lease.as_json(now) if lease is not None
                    and not lease.expired(now) else None}
                if doc_id in frontiers:
                    docs[doc_id]["frontier"] = frontiers[doc_id]
        return {"docs": docs, "self": self.self_id}

    # ---- wire-tier snapshot fetch (hydrator hook) ------------------------

    def fetch_remote_snapshot(self, doc_id: str) -> Optional[bytes]:
        """One GET of the doc owner's compacted snapshot frame, for a
        cold hydration miss whose durable home is empty. Best-effort:
        any transport error, a 404 (old peer or unknown doc) or a
        non-frame body returns None and the miss stays a fresh doc."""
        from ..wire.frames import WIRE_HEADER, is_frame
        target = self.route_mutation(doc_id)
        if target == self.self_id or not self.wire.enabled:
            return None
        try:
            st, body = self.table.call(
                target, f"/doc/{doc_id}/snapshot",
                headers={WIRE_HEADER: self.wire.header_value()})
        except (OSError, urllib.error.HTTPError, KeyError):
            return None
        if st != 200 or not is_frame(body):
            return None
        return body

    # ---- metrics ---------------------------------------------------------

    def metrics_json(self) -> dict:
        return self.metrics.snapshot(
            leases_held=self.leases.held_count(),
            per_peer=self.table.states(),
            faults=self.faults.snapshot()
            if self.faults is not None else None,
            membership_view=self.membership.as_json(),
            quorum_view={"voters": self.membership.voters(),
                         "quorum": self.membership.quorum_size(),
                         "rejoining": self.rejoining},
            override_table_size=self.overrides.size(),
            writergroup_sizes=self.writergroups.sizes())

    # ---- lifecycle -------------------------------------------------------

    def start(self) -> None:
        """Probe + maintain loop and the anti-entropy loop."""
        self.antientropy.start()
        if self._thread is not None:
            return

        def loop():
            while not self._stop.wait(self.probe_interval_s):
                try:
                    self.table.probe_once()
                    self.maintain()
                    rb = self.rebalancer
                    if rb is not None:
                        rb.tick()
                except Exception:   # pragma: no cover - keep running
                    pass

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self.antientropy.stop()
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None
        self._stop = threading.Event()
        self.table.stop_probe_loop()
        if self.journal is not None:
            self.journal.close()


def attach_replication(httpd, self_id: str, peer_addrs: List[str],
                       **opts) -> ReplicaNode:
    """Wire a ReplicaNode onto a running server (tools/server.serve):
    the store gains `.replica`, and the merge scheduler (when present)
    gets the ownership admit gate plus the epoch fencing callback.
    Split from serve() because tests bind port 0 first and only then
    know their own `host:port` identity."""
    store = httpd.store
    if "obs" not in opts:
        opts["obs"] = getattr(store, "obs", None)
    node = ReplicaNode(store, self_id, peer_addrs, **opts)
    store.replica = node
    if getattr(store, "scheduler", None) is not None:
        store.scheduler.admit = node.owns
        store.scheduler.epoch_of = node.active_epoch
        # wire tier: a cold hydration miss with an EMPTY durable home
        # asks the doc's owner for one compacted snapshot frame (the
        # `/doc/{id}/snapshot` endpoint is wire-v1-only, so a node
        # pinned to JSON never fetches — old-peer semantics preserved)
        hyd = getattr(store.scheduler, "hydrator", None)
        if hyd is not None and node.wire.enabled:
            hyd.remote_fetch = node.fetch_remote_snapshot
    return node
