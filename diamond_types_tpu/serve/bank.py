"""Per-shard session bank: LRU-bounded device residency + host fallback.

One bank per shard owns every device-resident `FusedDocSession`
(`tpu.flush_fuse`) placed on that shard's chip. Residency is bounded
two ways, mirroring the
eviction/resync machinery the multichip dryrun proved out
(`__graft_entry__._dryrun_session_sharded`):

  * `max_sessions` — at most N documents resident at once;
  * `max_slots`    — total device-slot footprint (sum of each session's
                     `footprint_slots()`, dominated by the W_cap x
                     n_rows state matrix) stays under a VMEM-shaped
                     budget. A session whose tail outgrows its
                     capacity class GROWS on the device, and past the
                     budget it evicts its least-recently-used neighbors
                     first (`before_growth`, set in `_build`).

Eviction drops the device carry; the document itself lives in its host
OpLog, so an evicted doc costs one rebuild (resync) on its next merge —
graceful degradation, exactly like the session's internal row LRU.

Every DATA fault is parity-recoverable: a replay that comes back with a
poisoned (-1) or drifting length (`flush_fuse.FenceFailure`, or a false
row from `adopt_results`) evicts the session, serves the doc from the
host engine (`oplog.checkout_tip()` — always correct) and counts a
host fallback. A compiler or runtime failure is not a data fault: any
other exception out of a device rung or a session build is counted
(`device_errors`), recorded with its text, and propagates — a rung that
was asked for and cannot run is an error, not a slower run.
`engine="host"` takes the host path for every doc by choice: the
scheduler then still provides routing/batching/metrics, and no JAX
backend is touched.

The flush path of a device bank is three steps, one way:

  1. plan   — `_plan_fused`: build or find each doc's session, pack its
              pending tail (`FusedDocSession.plan_tail`, the native
              mirror's transform), grow on the device each session
              whose tail overflows its capacity class
              (`FusedDocSession.make_room`) and group the sessions by
              (cap, max_ins).
  2. replay — one `flush_fuse.fused_replay` call a group on the shard's
              own chip (`sync_docs`); where the scheduler runs mesh
              windows it replays the groups of every shard itself with
              `parallel.mesh.mesh_fused_replay` (`plan_window` and
              `adopt_window` are the two halves it calls).
  3. adopt  — `adopt_window`: a poisoned or mismatched length evicts
              the session to the host oracle; what could not be grouped
              (capacity eviction mid-batch, a bucket with fewer than
              two docs to group) goes through `sync_doc` one doc at a
              time.

Locking contract for `sync_docs`: `oplog_lock` (the scheduler's
narrowed sync lock — e.g. DocStore.lock) is held only around the
HOST-side phases (session build, tail planning, fallback bookkeeping);
`device_lock` (per physical device) is held only around the device
replay, so shards on distinct chips flush genuinely concurrently. The
one remaining process-global serialization point is
`tpu.runtime.first_touch`: the very first JAX backend touch
process-wide is not thread-safe, so it runs once under a module lock
(documented exception to the per-device rule) — and checks there that
the process really has the device it was asked to use.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional

from ..obs.devprof import PROFILER
from ..obs.phases import phase
from ..tpu.flush_fuse import FenceFailure, decode_row
from ..tpu.runtime import first_touch
from .metrics import ServeMetrics


class _HostDoc:
    """Host-engine stand-in for a device session: the oplog IS the
    state, so sync is a no-op and text is a tracker checkout."""

    resyncs = 0

    def __init__(self, oplog) -> None:
        self.oplog = oplog
        self.synced_to = len(oplog)

    def sync(self) -> int:
        new = len(self.oplog) - self.synced_to
        self.synced_to = len(self.oplog)
        return max(new, 0)

    def text(self) -> str:
        return self.oplog.checkout_tip().snapshot()

    def footprint_slots(self) -> int:
        return 0


class SessionBank:
    def __init__(self, shard_id: int, max_sessions: int = 8,
                 max_slots: int = 1 << 24, engine: str = "device",
                 device=None, metrics: Optional[ServeMetrics] = None,
                 fused_opts: Optional[dict] = None,
                 warmup: bool = False,
                 flush_docs: int = 8,
                 mesh_shards: int = 0) -> None:
        if engine not in ("device", "host"):
            raise ValueError(f"unknown engine {engine!r}")
        self.shard_id = shard_id
        self.max_sessions = max(int(max_sessions), 1)
        self.max_slots = int(max_slots)
        self.engine = engine
        self.device = device
        self.metrics = metrics
        # cap / max_ins / headroom of the FusedDocSessions a device
        # bank builds
        self.fused_opts = dict(fused_opts or {})
        self.flush_docs = int(flush_docs)
        # >0: the scheduler runs mesh flush windows over this many
        # shards — warmup then ALSO pre-compiles the mesh super-batch
        # shape classes (B padded to the mesh) so the first window
        # doesn't eat a cold compile
        self.mesh_shards = int(mesh_shards)
        self.sessions: "OrderedDict[str, object]" = OrderedDict()
        self._resyncs_seen: Dict[str, int] = {}
        # capacity classes this bank has built a session of: their
        # copy programs are compiled (`_warm_growth`)
        self._classes_built: set = set()
        # obs.recorder.FlightRecorder (MergeScheduler.attach_obs);
        # evictions and fallbacks are rare enough to record each one
        self.recorder = None
        # obs.journey.OpJourney (same attach path): planned /
        # device_replayed / adopted stamps for sampled-trace items
        self.journey = None
        # residency tier (MergeScheduler.attach_hydrator): called as
        # snapshot_hook(doc_id, pending_ops) at every eviction site so
        # pending device state is persisted instead of silently
        # dropped. Enqueue-only by contract — eviction runs under
        # shard/oplog locks and must never wait on disk.
        self.snapshot_hook = None
        self._warmup_thread: Optional[threading.Thread] = None
        self.warmup_error: Optional[BaseException] = None
        if warmup and engine == "device":
            self._warmup_thread = threading.Thread(
                target=self._warmup, daemon=True)
            self._warmup_thread.start()

    def _warmup(self) -> None:
        """Background jit pre-compilation for the bucket shape classes
        this bank can flush (satellite: the first real flush should hit
        a warm cache, not eat a compile on the request path). Compile
        hits/misses surface through devprof's "fused" jit_cache rows.
        A failure — a kernel the compiler refuses, no device — is kept
        for `join_warmup` to raise."""
        try:
            first_touch()
            from ..tpu.flush_fuse import (DEFAULT_CAP, DEFAULT_MAX_INS,
                                          warmup_fused_cache)
            warmup_fused_cache(
                flush_docs=self.flush_docs,
                cap=self.fused_opts.get("cap", DEFAULT_CAP),
                max_ins=self.fused_opts.get("max_ins", DEFAULT_MAX_INS),
                mesh_shards=self.mesh_shards)
        except Exception as e:
            self.warmup_error = e
            self._bump("warmup_errors")
            self._record_error("warmup_error", e)

    def join_warmup(self, timeout: Optional[float] = None) -> None:
        """Block until background warmup finishes (serve() start-up,
        tests, benches) and raise if it failed: a rung that was asked
        for and cannot compile is an error here, not a slower run."""
        t = self._warmup_thread
        if t is not None:
            t.join(timeout=timeout)
            if t.is_alive():
                raise TimeoutError(
                    f"shard {self.shard_id} warm-up still compiling "
                    f"after {timeout}s")
        if self.warmup_error is not None:
            raise RuntimeError(
                f"shard {self.shard_id} warm-up failed: "
                f"{self.warmup_error.__class__.__name__}: "
                f"{self.warmup_error}") from self.warmup_error

    # ---- accounting ------------------------------------------------------

    def _bump(self, key: str, n: int = 1) -> None:
        if self.metrics is not None:
            self.metrics.bump(self.shard_id, key, n)

    def _record_error(self, kind: str, exc: BaseException,
                      **fields) -> None:
        if self.recorder is not None:
            self.recorder.record(
                kind, shard=self.shard_id,
                error=f"{exc.__class__.__name__}: {exc}"[:400], **fields)

    def device_error(self, rung: str, exc: BaseException,
                     **fields) -> None:
        """Count and record an exception out of a device rung (`rung`:
        mesh / fused / per_doc / build). The caller re-raises:
        a compiler or runtime failure is never answered by a quieter
        rung. The exception is tagged with this shard so a loop that
        survives it (`scheduler._loop_error`) files it where it
        happened."""
        exc.dt_shard = self.shard_id
        self._bump("device_errors")
        self._record_error("device_error", exc, rung=rung, **fields)

    def footprint_slots(self) -> int:
        return sum(s.footprint_slots() for s in self.sessions.values())

    @staticmethod
    def _pending_ops(sess) -> int:
        """Ops the session's oplog holds beyond its synced frontier —
        what a lossy eviction WOULD have dropped (device carry ahead of
        the durable home). Both session kinds expose oplog/synced_to;
        anything else reads as 0."""
        ol = getattr(sess, "oplog", None)
        if ol is None:
            return 0
        return max(len(ol) - getattr(sess, "synced_to", 0), 0)

    def _drop(self, doc_id: str, sess, why: str) -> None:
        """Shared eviction tail: count it, route the doc through the
        snapshot path (when a residency tier is attached), and record
        the flight-recorder event WITH the pending-op count — the
        event is informational, not a data-loss marker, precisely
        because the snapshot path persists that pending state."""
        self._resyncs_seen.pop(doc_id, None)
        self._bump("evictions")
        pending = self._pending_ops(sess)
        snapshotted = False
        if self.snapshot_hook is not None:
            try:
                snapshotted = bool(self.snapshot_hook(doc_id, pending))
            except Exception:   # pragma: no cover - hook must not wedge
                pass
        if self.recorder is not None:
            self.recorder.record("session_evicted",
                                 shard=self.shard_id, doc=doc_id,
                                 why=why, pending_ops=pending,
                                 snapshotted=snapshotted)

    def _evict_until_fits(self, incoming_slots: int = 0,
                          keep: Optional[str] = None) -> None:
        def over() -> bool:
            return (len(self.sessions) > self.max_sessions or
                    self.footprint_slots() + incoming_slots
                    > self.max_slots)
        while self.sessions and over():
            victim = next((k for k in self.sessions if k != keep), None)
            if victim is None:
                break      # only `keep` is resident; nothing to evict
            sess = self.sessions.pop(victim)
            self._drop(victim, sess, why="capacity")

    def evict(self, doc_id: str) -> bool:
        sess = self.sessions.pop(doc_id, None)
        if sess is not None:
            self._drop(doc_id, sess, why="explicit")
            return True
        return False

    # ---- residency -------------------------------------------------------

    def _on_device(self):
        """`jax.default_device` of this bank's chip: what a session
        builds and a replay stacks lands there. Nothing for a host
        bank (no JAX touched) or an unplaced one."""
        if self.device is None or self.engine == "host":
            return contextlib.nullcontext()
        import jax
        return jax.default_device(self.device)

    def _build(self, doc_id: str, oplog):
        if self.engine == "host":
            return _HostDoc(oplog)
        first_touch()
        from ..tpu.flush_fuse import FusedDocSession
        with self._on_device():
            sess = FusedDocSession(oplog, **self.fused_opts)
            first = self._warm_growth(sess.cap)
        if first and self.mesh_shards and self.device is not None:
            # under mesh flush windows the class's blocks are warmed
            # too: the two row programs of a mesh dispatch, on this
            # chip, for every block size a window can give it. OUTSIDE
            # `_on_device()`: `jax.default_device` is part of a jitted
            # program's cache key, and a window runs under none
            from ..parallel.mesh import (block_classes, serve_mesh,
                                         warm_block_programs)
            ndev = int(serve_mesh(self.mesh_shards).devices.size)
            warm_block_programs(
                [self.device], sess.cap,
                block_classes(ndev, self.mesh_shards * self.flush_docs))
        # the slots a growth adds are found BEFORE the copy, at the cost
        # of other documents and never of this one, so the budget holds
        # while both rows live: by `_plan_fused` itself on the fused
        # path, through this on the per-doc ladder (`sess.sync()`)
        sess.before_growth = functools.partial(self._evict_until_fits,
                                               keep=doc_id)
        # the initial build counts as this doc's baseline, not a resync
        self._resyncs_seen[doc_id] = sess.resyncs
        return sess

    def _warm_growth(self, cap: int) -> bool:
        """At the first session this bank builds in capacity class
        `cap` (the return says it was): compile the copy programs a
        growth out of or into that class can need, to the next class
        up and between it and every class built before, so that a
        session which outgrows its class under traffic compiles
        nothing on the flush path. Runs on the bank's chip, as the
        growth will."""
        if cap in self._classes_built:
            return False
        from ..tpu.flush_fuse import warm_grow
        pairs = {(cap, 2 * cap)} | {(min(cap, c), max(cap, c))
                                    for c in self._classes_built}
        self._classes_built.add(cap)
        for lo, hi in sorted(pairs):
            warm_grow(lo, hi)
        return True

    def session(self, doc_id: str, oplog):
        """Get-or-build the doc's resident session, updating LRU order
        and enforcing both residency bounds."""
        sess = self.sessions.get(doc_id)
        if sess is not None and getattr(sess, "oplog", None) is not None \
                and sess.oplog is not oplog:
            # residency churn: the doc was evicted from the WARM tier
            # and re-hydrated into a NEW OpLog object — a session bound
            # to the old oplog would serve a frozen view forever.
            # Rebuild against the live oplog (counted as an eviction,
            # snapshot-routed like any other).
            self.sessions.pop(doc_id)
            self._drop(doc_id, sess, why="stale-oplog")
            sess = None
        if sess is not None:
            self.sessions.move_to_end(doc_id)
            return sess
        # make room BEFORE the expensive build (the new session's exact
        # footprint is unknown until built; re-check after)
        self._evict_until_fits()
        try:
            sess = self._build(doc_id, oplog)
        except Exception as e:
            if self.engine == "device":
                self.device_error("build", e, doc=doc_id)
            raise
        self._bump("builds")
        self.sessions[doc_id] = sess
        self._evict_until_fits(keep=doc_id)
        if self.metrics is not None:
            self.metrics.observe_footprint(self.shard_id,
                                           self.footprint_slots())
        return sess

    # ---- merge path ------------------------------------------------------

    def sync_doc(self, doc_id: str, oplog) -> dict:
        """Fold the doc's appended ops into its shard-resident state.
        A data fault (`FenceFailure`) falls back to the host engine and
        records the fallback; any other device exception is counted,
        recorded and raised."""
        self._bump("syncs")
        t0 = time.perf_counter()
        sess = self.session(doc_id, oplog)
        try:
            with self._on_device():
                steps = sess.sync()
            # wall vs device attribution: the sync above returns once
            # dispatch is queued; block_until_ready isolates the device
            # wait. Only measured when the profiler is on — forcing a
            # sync point perturbs the async dispatch pipeline.
            device_s = 0.0
            if self.engine == "device" and PROFILER.enabled:
                import jax
                td = time.perf_counter()
                jax.block_until_ready(sess.lens)
                device_s = time.perf_counter() - td
        except FenceFailure as e:
            self.evict(doc_id)
            self._bump("host_fallbacks")
            self._record_error("host_fallback", e, doc=doc_id)
            return {"engine": "host", "steps": _HostDoc(oplog).sync(),
                    "error": f"{e.__class__.__name__}: {e}"[:200]}
        except Exception as e:
            if self.engine == "device":
                self.device_error("per_doc", e, doc=doc_id)
            raise       # host checkouts failing is a real bug too
        seen = self._resyncs_seen.get(doc_id)
        now_resyncs = getattr(sess, "resyncs", 0)
        if seen is not None and now_resyncs > seen:
            self._bump("resyncs", now_resyncs - seen)
            self._resyncs_seen[doc_id] = now_resyncs
        if self.metrics is not None:
            self.metrics.observe_footprint(self.shard_id,
                                           self.footprint_slots())
            self.metrics.observe_device_time(
                self.shard_id, time.perf_counter() - t0, device_s)
        PROFILER.observe_flush(self.shard_id,
                               time.perf_counter() - t0, device_s)
        return {"engine": self.engine, "steps": int(steps)}

    def plan_window(self, items, resolve, oplog_lock=None,
                    min_fuse: int = 2) -> dict:
        """Plan-only entry point — the host-side half of `sync_docs`,
        with NO device call issued. The mesh flush-window coordinator
        (`scheduler._flush_window`) calls this on every shard's bucket,
        concatenates the fusable rows into one mesh super-batch, issues
        a single `shard_map` program, and hands each shard its results
        back through `adopt_window`. `min_fuse=1` because even one
        fusable doc joins the shared super-batch (the amortization
        argument that demotes lone docs on the per-shard path doesn't
        apply when the dispatch is shared).

        Returns {"items", "ols", "serial", "groups"} where `groups` is
        [(sessions, plans, doc_ids)] keyed by (cap, max_ins) class."""
        olock = oplog_lock if oplog_lock is not None \
            else contextlib.nullcontext()
        # resolve first, outside every lock (non-reentrant store lock)
        with phase("bank.resolve"):
            ols = {it.doc_id: resolve(it.doc_id) for it in items}
        serial = list(items)
        groups: List[tuple] = []     # (sessions, plans, doc_ids)
        if self.engine == "device":
            # session builds and tail plans, under the oplog guard
            with phase("bank.plan"):
                serial, groups = self._plan_fused(items, ols, olock,
                                                  min_fuse=min_fuse)
        self._journey_stamp(items, "planned")
        return {"items": items, "ols": ols, "serial": serial,
                "groups": groups}

    def _journey_stamp(self, items, stage: str, docs=None) -> None:
        """Journey stamps for sampled-trace items; `docs` narrows to a
        doc-id subset. No-op until attach_obs wires `self.journey`."""
        j = self.journey
        if j is None or not j.enabled:
            return
        for it in items:
            tr = getattr(it, "trace", None)
            if tr is None or not tr.sampled:
                continue
            if docs is not None and it.doc_id not in docs:
                continue
            j.stamp(tr.trace_id, stage)

    def adopt_window(self, win: dict, failed: List[str],
                     oplog_lock=None, device_lock=None) -> dict:
        """Result adoption for one shard's slice of a flush window:
        bump per-doc sync counters for the fused rows (commits already
        happened at the device fence), evict `failed` docs — poisoned
        (-1) or length-drift rows whose device state is untrusted — to
        the host oracle, and run the serial fallback ladder for
        everything that couldn't fuse. Shared tail of `sync_docs` and
        the mesh window path, so the fallback ladder is one code path
        regardless of which program replayed the batch."""
        olock = oplog_lock if oplog_lock is not None \
            else contextlib.nullcontext()
        dlock = device_lock if device_lock is not None \
            else contextlib.nullcontext()
        out = {"docs": len(win["items"]), "fused_calls": 0,
               "fused_docs": 0, "fallback_docs": 0}
        for _sessions, _plans, doc_ids in win["groups"]:
            for _d in doc_ids:
                self._bump("syncs")
        with phase("adopt"), olock:
            for d in failed:
                # poisoned (-1) or length-drift result: the session's
                # device state is untrusted — evict it and serve the
                # doc from the host oracle until its next rebuild
                self.evict(d)
                self._bump("host_fallbacks")
                if self.recorder is not None:
                    self.recorder.record(
                        "host_fallback", shard=self.shard_id, doc=d,
                        error="fused_poisoned_or_len_mismatch")
            for it in win["serial"]:
                with dlock:
                    # The serial fallback rung interleaves oplog reads
                    # (span walk, agent keys, host checkouts) with its
                    # device continuation inside one sess.sync(), so it
                    # cannot drop the oplog guard the way the fused
                    # phases do. It is the rare rung — unfusable,
                    # overflowing or poisoned docs — and stalling
                    # oplog readers here is the documented cost of
                    # falling off the fused path.
                    self.sync_doc(it.doc_id, win["ols"][it.doc_id])  # dt-lint: ignore[device-under-lock]
            out["fallback_docs"] = len(win["serial"]) + len(failed)
            if self.metrics is not None:
                self.metrics.observe_footprint(self.shard_id,
                                               self.footprint_slots())
        # journey: every surviving item is merged once adoption ends —
        # fused rows committed at the device fence, serial/failed rows
        # through the fallback ladder just now
        self._journey_stamp(win["items"], "adopted")
        return out

    def sync_docs(self, items, resolve,
                  oplog_lock=None, device_lock=None,
                  min_fuse: int = 2) -> dict:
        """Flush one taken bucket: plan, replay, adopt (module
        docstring). `items` are admission
        PendingMerge rows; `resolve(doc_id) -> OpLog` is called OUTSIDE
        `oplog_lock` (DocStore.get takes that same non-reentrant lock).

        Lock discipline: `oplog_lock` around host-side phases (build,
        plan, fallback bookkeeping), `device_lock` around the fused
        device replay only — see the module docstring. `min_fuse=1`
        (a read's own flush) replays a lone document as a group of
        one, so that it does not take the per-doc ladder, which waits
        for the device under `oplog_lock`.

        Returns {"docs", "fused_calls", "fused_docs", "fallback_docs",
        "device_s"}: the last the seconds the fused calls waited for
        the device.
        """
        dlock = device_lock if device_lock is not None \
            else contextlib.nullcontext()
        win = self.plan_window(items, resolve, oplog_lock=oplog_lock,
                               min_fuse=min_fuse)
        fused_calls = fused_docs = 0
        device_total = 0.0
        # ---- device phase: one jitted call per fused group, under the
        # device lock ONLY — host threads keep mutating other oplogs
        failed: List[str] = []
        for sessions, plans, doc_ids in win["groups"]:
            # looked up when the group is replayed, not when the module
            # is imported: bench/instrument.py puts its clock in
            # `flush_fuse.fused_replay`'s place after the server started
            from ..tpu.flush_fuse import fused_replay
            t0 = time.perf_counter()
            try:
                with dlock, self._on_device():
                    ok, device_s = fused_replay(sessions, plans)
            except Exception as e:
                # counted, recorded and raised: a replay that cannot
                # run is never answered by a quieter path
                self.device_error("fused", e, docs=len(sessions))
                raise
            wall = time.perf_counter() - t0
            n = len(sessions)
            fused_calls += 1
            fused_docs += n
            device_total += device_s
            if self.metrics is not None:
                self.metrics.record_fused(self.shard_id, n)
                self.metrics.observe_device_time(self.shard_id, wall,
                                                 device_s)
            PROFILER.observe_fused(self.shard_id, wall, device_s, n)
            failed.extend(d for good, d in zip(ok, doc_ids)
                          if not good)
        if fused_docs:
            fused = {d for _s, _p, ds in win["groups"] for d in ds}
            self._journey_stamp(items, "device_replayed",
                                docs=fused.difference(failed))
        # ---- host phase: per-doc fallbacks + poisoned-result cleanup
        out = self.adopt_window(win, failed, oplog_lock=oplog_lock,
                                device_lock=device_lock)
        out["fused_calls"] = fused_calls
        out["fused_docs"] = fused_docs
        out["device_s"] = device_total
        return out

    def _plan_fused(self, items, ols, olock, min_fuse: int = 2):
        """Host-side phase of the flush, one pass under one hold of
        `olock`: get/build each doc's session, plan its tail, and group
        the sessions to replay by (cap, max_ins). A session whose tail
        overflows its capacity class grows on the device first and is
        grouped by its new class: its slots are found under that hold,
        the copy runs once the guard is released (no device work under
        it), and the grouping then takes a second hold. Anything that
        can't be grouped — LRU-evicted mid-batch, a growth the device
        refused, a bucket with fewer than `min_fuse` docs to replay —
        lands in the serial list."""
        serial = []
        fusable: List[tuple] = []    # (sess, plan, doc_id)

        def sort_planned() -> None:
            for it, sess, plan in planned:
                # building or growing session N can LRU-evict
                # already-planned M: only still-resident sessions may
                # commit device state. A tail that still does not fit
                # takes the per-doc path, whose growth may rebuild
                if self.sessions.get(it.doc_id) is not sess \
                        or not plan.fits(sess.cap):
                    serial.append(it)
                elif plan.n_ops == 0:
                    # frontier advance with no visible ops (e.g. a
                    # delete of an already-deleted span): no device work
                    sess.commit_host(plan)
                    self._bump("syncs")
                else:
                    fusable.append((sess, plan, it.doc_id))

        with olock:
            planned, growing, short = [], [], 0
            for it in items:
                # a build failure is counted in session() and raises
                sess = self.session(it.doc_id, ols[it.doc_id])
                plan = sess.plan_tail()
                if not plan.fits(sess.cap):
                    # with the slots of this batch's earlier growers,
                    # whose copies are still to come
                    short += sess.slots_short(plan)
                    self._evict_until_fits(incoming_slots=short,
                                           keep=it.doc_id)
                    growing.append((it.doc_id, sess, plan))
                planned.append((it, sess, plan))
            if not growing:
                sort_planned()
        if growing:
            with self._on_device():
                for doc_id, sess, plan in growing:
                    if self.sessions.get(doc_id) is sess:
                        sess.make_room(plan, rebuild=False)
            with olock:
                sort_planned()
        if len(fusable) < min_fuse:
            # below min_fuse the per-doc path amortizes nothing on the
            # per-shard path (the mesh coordinator passes min_fuse=1:
            # its dispatch is shared, so lone docs still join)
            serial.extend(
                next(it for it in items if it.doc_id == d)
                for _s, _p, d in fusable)
            return serial, []
        by_shape: Dict[tuple, list] = {}
        for sess, plan, d in fusable:
            by_shape.setdefault((sess.cap, sess.max_ins), []).append(
                (sess, plan, d))
        groups = [(
            [s for s, _p, _d in grp],
            [p for _s, p, _d in grp],
            [d for _s, _p, d in grp],
        ) for grp in by_shape.values()]
        return serial, groups

    def read_row(self, sess, device_lock=None):
        """A resident session's text and frontier (local versions), as
        ONE commit left them: the fetch of `MergeScheduler.read_tip`,
        for a session the scheduler has brought to the tip. Every
        commit that moves the row runs under `device_lock` (the
        replay's adoption, the per-doc ladder), so the row, its length
        and its frontier are read under it and belong together; the
        transfer of the whole row is waited for there too, and no
        other lock is held. Counted `reads_from_device`."""
        dlock = device_lock if device_lock is not None \
            else contextlib.nullcontext()
        with dlock:
            frontier = sess.frontier
            text = decode_row(sess.docs, sess.doc_len)
        self._bump("reads_from_device")
        return text, frontier
