"""The multi-document merge scheduler: router x admission x banks.

Sits between the sync server's DocStore and the device tier. A document
edit lands as `submit(doc_id, n_ops)`; the scheduler routes it to its
shard, coalesces it into a shape bucket, and `pump()` flushes due
buckets into the shard's session bank — one flush drives every doc in
the bucket back-to-back on that shard's chip, so they share the padded
micro-tape shape (and therefore the jit cache entry) instead of each
paying its own compile.

Threading: the global `lock` guards router + queue mutation only; each
shard's BANK has its own lock, so flushes (the device work) run with
the global lock RELEASED and different shards flush concurrently —
submits and reads for other shards never stall behind one shard's
device call. With `flush_workers=True` (default) `pump()` only TAKES
due buckets under the global lock and hands them to per-shard worker
threads, so the pump caller returns immediately and shards genuinely
overlap their flush windows; a shard whose worker still has a batch is
left in the queue, where its documents go on coalescing, so a worker
never holds a backlog of batches that an earlier one made stale, and a
worker paces its host work (`FLUSH_HOST_SHARE`); `drain()` waits for
workers to go idle
and `stop_workers()`/`stop_pump()` join them deterministically. The
fencing recheck runs INSIDE the worker (see `_flush_items`), so lease
epochs are validated at actual merge time, not dispatch time.

The old process-global `_sync_lock` over-serialized device syncs
across ALL shards. It is now narrowed to its real job — an OPLOG guard
(`sync_lock`, e.g. DocStore.lock, held around host-side oplog reads so
bank planning never races handler threads mutating the oplog) — while
device execution is guarded by a PER-DEVICE lock (shards placed on the
same chip share one; distinct chips flush concurrently). The one
remaining process-global serialization point is first-touch JAX
backend init (`tpu.runtime.first_touch`), which is not thread-safe and
runs exactly once. Lock order is always
global → shard → sync(oplog) → device, never reversed. Intended
callers: (a) HTTP handler threads submitting and reading, (b) pump
threads flushing (`start_pump`), and (c) bench drivers doing both
inline.

Ownership gate: when `admit` is set (cross-host replication — a
`replicate.ReplicaNode.owns` bound method), `submit` consults it first
and refuses merge work for docs whose lease this host does not hold;
the edit stays durable in the oplog, the device work runs on the
lease-holding host instead. When `epoch_of` is also set
(`ReplicaNode.active_epoch`), each accepted submit is stamped with the
lease epoch it was admitted under and RE-CHECKED at flush time: if the
lease moved (or was fenced off) between admit and flush, the queued
work is dropped — counted as `fenced` — instead of merged under a
stale lease. The ops themselves stay durable in the oplog; the new
owner merges them.
"""

from __future__ import annotations

import contextlib
import queue as _queue
import threading
import time
from typing import Callable, Dict, List, Optional

from ..analysis.witness import make_lock
from ..obs.phases import NOOP_PHASE
from ..obs.trace import NOOP_SPAN
from ..qos.classes import QOS_PRIORITY
from .admission import AdmissionQueue, Backpressure
from .bank import SessionBank
from .metrics import ServeMetrics
from .router import ShardRouter

# Merging is background work: no acknowledgement waits for it, and its
# worker shares the store's lock and the interpreter with the handlers
# that acknowledge. A shard's flush worker keeps its host part (what a
# flush takes besides the wait for the device: resolve, plan, the
# dispatches, adopt, and its own waits for the lock) to one part in
# this many of its time, and sits out the rest (`_worker_loop`); the
# pump thread does the same after a mesh flush window, which runs on
# it (`start_pump`; the arithmetic is `_pause_s`). A flush that mostly
# waits for the device is never held back, and a forced one (drain,
# shutdown: somebody waits for it) is not either.
FLUSH_HOST_SHARE = 8

# The longest a read waits for a flush in flight that carries its
# document's items before it plans and replays the tail itself
# (`_sync_for_read`): that flush takes the shard's lock too, so it is
# a wait all the same, and one that ends where the other flush died.
READ_WAIT_S = 1.0


class MergeScheduler:
    def __init__(self, n_shards: int,
                 resolve: Callable[[str], object],
                 engine: str = "device",
                 max_sessions_per_shard: int = 8,
                 max_slots_per_shard: int = 1 << 24,
                 max_pending: int = 256,
                 flush_docs: int = 8,
                 flush_deadline_s: float = 0.05,
                 place_on_devices: bool = False,
                 sync_lock=None,
                 admit: Optional[Callable[[str], bool]] = None,
                 fused_opts: Optional[dict] = None,
                 flush_workers: bool = True,
                 warmup: bool = False,
                 mesh_window: bool = False,
                 mesh_window_rows: Optional[int] = None,
                 reads: str = "host") -> None:
        """`resolve(doc_id) -> OpLog` is the document authority —
        DocStore.get fits directly. `sync_lock` (e.g. DocStore.lock) is
        the OPLOG guard: held around host-side oplog reads (session
        build / tail planning / host syncs) so bank reads never race
        handler threads mutating the oplog; `resolve` is always called
        OUTSIDE it (DocStore.get takes that same non-reentrant lock).
        Device execution is guarded by per-device locks instead — see
        the module docstring. A device engine holds every document as
        a `FusedDocSession` (built with `fused_opts`: cap / max_ins /
        headroom) and replays a whole bucket in one vmapped device
        call; `flush_workers=True` flushes through per-shard
        worker threads; `warmup=True` pre-compiles the replay kernels on
        a background thread at construction. `mesh_window=True`
        (device engine only) inverts the flush concurrency model:
        instead of handing each shard's bucket to its own worker (N
        device dispatches per window), `pump()` assembles EVERY due
        shard's fusable tails into one mesh-sharded super-batch and
        issues a single `shard_map` program over the `docs` axis —
        see `_flush_window`, which the pump thread's loop paces as a
        flush worker paces its flushes (`FLUSH_HOST_SHARE`);
        `mesh_window_rows` is the most rows of one class that go out
        in one such program, an even share of it a chip (default
        `n_shards x flush_docs`, one bucket a shard: a deployment
        states it when its warm-up is built around it). `reads` says where the served
        `GET /doc/{id}` at the tip is answered, and is part of what a
        deployment states: `"host"` (the default) checks the oplog out
        under the store lock, so the body shares nothing with the
        device row it is compared with; `"device"` answers from the
        document's session, brought to the oplog's tip first
        (`read_tip`), and leaves the host what has no session. It
        changes nothing else: `text()` is `read_tip` under either,
        the flush path and the guarantees are the same, and any other
        value raises `ValueError`. What follows a replay
        (per-doc → host) answers DATA faults only — an overflowing
        tail, a poisoned or drifting length. A replay that raises is
        counted (`device_errors`), recorded and re-raised: see
        serve/bank.py."""
        if reads not in ("host", "device"):
            raise ValueError(
                f"reads={reads!r}: \"host\" or \"device\"")
        self.reads = reads
        self.resolve = resolve
        self._sync_lock = sync_lock if sync_lock is not None \
            else contextlib.nullcontext()
        self.router = ShardRouter(n_shards)
        self.queue = AdmissionQueue(n_shards, max_pending=max_pending,
                                    flush_docs=flush_docs,
                                    flush_deadline_s=flush_deadline_s)
        self.metrics = ServeMetrics(n_shards, flush_docs, max_pending)
        devices: List = [None] * n_shards
        if engine == "device":
            # the process's first JAX touch: raises here, at
            # construction, when there is no device to be an engine of
            from ..tpu.runtime import first_touch
            first_touch()
            if place_on_devices:
                from ..parallel.mesh import serve_shard_devices
                devices = serve_shard_devices(n_shards)
        # mesh flush windows are assembled from FusedDocSession plan
        # rows: a host engine has none
        self.mesh_window = bool(mesh_window) and engine == "device"
        if mesh_window_rows is None:
            mesh_window_rows = n_shards * flush_docs
        if int(mesh_window_rows) < 1:
            raise ValueError(
                f"mesh_window_rows={mesh_window_rows!r}: at least 1")
        self.mesh_window_rows = int(mesh_window_rows)
        self._mesh = None          # lazy: first window / warmup builds
        self.banks = [
            SessionBank(i, max_sessions=max_sessions_per_shard,
                        max_slots=max_slots_per_shard, engine=engine,
                        device=devices[i], metrics=self.metrics,
                        fused_opts=fused_opts,
                        # the jit cache is process-global: one warmer
                        # covers every shard's shape classes
                        warmup=(warmup and i == 0),
                        flush_docs=flush_docs,
                        mesh_shards=(n_shards if self.mesh_window
                                     else 0))
            for i in range(n_shards)]
        # per-DEVICE locks: shards placed on the same chip share one;
        # unplaced shards (device=None) get their own (the default
        # device is thread-safe — contention there is a perf matter,
        # not a correctness one)
        by_dev: Dict[int, object] = {}
        self._device_locks: List = []
        for i, dev in enumerate(devices):
            key = id(dev) if dev is not None else ("shard", i)
            lock = by_dev.get(key)
            if lock is None:
                # witness rank = the first shard index mapped to the
                # device, so rank order == the sorted-shard-list
                # acquisition order _flush_window uses
                lock = by_dev[key] = make_lock(
                    f"device[{i}]", "device", rank=i)
            self._device_locks.append(lock)
        # `admit(doc_id) -> bool` — the cross-host ownership gate
        # (replicate.ReplicaNode.owns); None = single-host, admit all
        self.admit = admit
        # `epoch_of(doc_id) -> int` — the ACTIVE lease epoch this host
        # holds (replicate.ReplicaNode.active_epoch); None = unfenced
        self.epoch_of: Optional[Callable[[str], int]] = None
        # obs.Observability bundle (attach_obs); None = zero overhead:
        # every obs touchpoint below is guarded by this one attribute
        self.obs = None
        # serve.hydrate.Hydrator (attach_hydrator); None = the classic
        # everything-resident scheduler — no prefetch, no flush gate
        self.hydrator = None
        # qos.QosController (attach_qos); None = the static size-or-
        # deadline trigger, byte-identical to the pre-QoS scheduler
        self.qos = None
        # read.attach_follower_reads wires this to ReadPath.on_flush:
        # a completed flush moved the doc's merged tip, so the
        # follower-read checkout cache drops the doc's entries. Called
        # OUTSIDE shard/bank locks, right after record_flush.
        self.read_invalidate: Optional[Callable[[str], None]] = None
        self.lock = make_lock("scheduler.global", "global")
        self._shard_locks = [make_lock(f"shard[{i}]", "shard", rank=i)
                             for i in range(n_shards)]
        self._pump_stop = threading.Event()
        self._pump_thread: Optional[threading.Thread] = None
        # per-shard flush workers (lazy-spawned daemons): pump() hands
        # taken batches to these so distinct shards' flush windows
        # genuinely overlap; _inflight + the condvar make drain()
        # deterministic
        self._flush_workers = bool(flush_workers)
        self._work_qs: List[_queue.Queue] = [
            _queue.Queue() for _ in range(n_shards)]
        self._workers: List[Optional[threading.Thread]] = \
            [None] * n_shards
        self._inflight = 0
        # batches handed to each shard's worker and not yet flushed
        self._busy: List[int] = [0] * n_shards
        self._idle_cv = threading.Condition()
        # doc_id -> (frontier, its agents' names) of the last served
        # read (`read_tip`): a frontier is named once a commit
        self._read_frontiers: Dict[str, tuple] = {}

    def attach_obs(self, obs) -> None:
        """Wire an obs.Observability bundle into the admit→flush path:
        spans on submit/flush/device-sync, flush latencies into the
        metrics histogram, rare events (evictions, queue-bound
        violations, fenced flushes) into the flight recorder."""
        self.obs = obs
        self.metrics.recorder = obs.recorder
        # the queue-wait histogram is exported under a phase's name
        obs.phases.adopt("sched.queue_wait",
                         self.metrics.queue_wait_latency)
        # live-telemetry tier: counters/latencies double-write into the
        # windowed TimeSeries (rate()/quantile() "now" queries + SLO
        # burn rates); per-doc/agent usage feeds the top-K sketch
        self.metrics.ts = getattr(obs, "ts", None)
        if self.qos is not None:
            self.qos.attach_obs(obs)
        for bank in self.banks:
            bank.recorder = obs.recorder
            bank.journey = getattr(obs, "journey", None)
        if self.hydrator is not None:
            self.hydrator.recorder = obs.recorder
            self.hydrator.attrib = getattr(obs, "attrib", None)

    def attach_hydrator(self, hydrator) -> None:
        """Wire the residency tier in: `submit` prefetches on a doc's
        first admit (budgeted by the bucket flush deadline), the flush
        paths gate on warmth right after the lease fence (cold docs
        requeue — a delayed flush; quarantined docs drop before they
        can join a batch), and every bank eviction routes through the
        hydrator's snapshot queue instead of silently dropping pending
        state. The scheduler's `resolve` should be `hydrator.resolve`
        (the CLI soak wires it that way); `attach_hydrator` does not
        rebind it so store-backed resolves stay possible."""
        self.hydrator = hydrator
        if hydrator.metrics is None:
            hydrator.metrics = self.metrics
        if hydrator.oplog_lock is None and not isinstance(
                self._sync_lock, contextlib.nullcontext):
            hydrator.oplog_lock = self._sync_lock
        if self.obs is not None:
            hydrator.recorder = self.obs.recorder
            hydrator.attrib = getattr(self.obs, "attrib", None)
        for bank in self.banks:
            bank.snapshot_hook = hydrator.request_snapshot

    def attach_qos(self, controller) -> None:
        """Wire a qos.QosController into the admission path: the queue
        consults its published per-(shard, class) effective deadlines
        in place of the static trigger, submits bump its per-class
        counters, and start_pump/stop_pump own its control-loop thread.
        The controller takes its `qos` witness lock BEFORE this
        scheduler's global lock (qos(8) -> global(10) in the canonical
        order) when it reads queue fill each step."""
        controller.bind(self.queue, queue_lock=self.lock,
                        n_shards=self.queue.n_shards)
        if self.obs is not None:
            controller.attach_obs(self.obs)
        self.qos = controller
        self.queue.qos = controller

    # ---- intake ----------------------------------------------------------

    def submit(self, doc_id: str, n_ops: int = 1,
               now: Optional[float] = None, trace=None,
               qos: Optional[str] = None) -> dict:
        """Queue pending merge work. Returns {"accepted": True, "shard",
        "bucket"}, {"accepted": False, "retry_after"} on backpressure,
        or {"accepted": False, "reason": "not_owner"} when the
        ownership gate denies (never raises — rejects and denials are
        normal operation under load / during handoff). `trace` is an
        optional obs SpanContext (the originating HTTP edit); when its
        trace is sampled the admit, the ownership gate, and later the
        flush + device sync all join it. `qos` is the ingress-
        classified class (qos/classes.py; default interactive) — the
        shed gate itself runs at HTTP ingress, BEFORE the edit is
        durable, not here. Unknown classes normalize to interactive
        (mirroring classify_headers' typo-safe fallback) so a direct
        library caller can't poison per-class depth accounting or trip
        QosMetrics on an undeclared class."""
        now = time.monotonic() if now is None else now
        qos_cls = qos if qos in QOS_PRIORITY else "interactive"
        obs = self.obs
        span = NOOP_SPAN
        if obs is not None:
            span = obs.tracer.start("serve.admit", parent=trace,
                                    attrs={"doc": doc_id,
                                           "n_ops": n_ops})
        if self.admit is not None:
            gate = NOOP_SPAN if not span.sampled else obs.tracer.start(
                "serve.ownership_gate", parent=span.context(),
                attrs={"doc": doc_id})
            admitted = self.admit(doc_id)
            gate.end(admitted=admitted)
            if not admitted:
                # shard_of (not assign): a denied doc must not register
                # a live assignment this host will never flush
                shard = self.router.shard_of(doc_id)
                self.metrics.bump(shard, "denied")
                span.end(outcome="denied")
                return {"accepted": False, "shard": shard,
                        "reason": "not_owner"}
        hyd = self.hydrator
        if hyd is not None:
            if hyd.store.is_quarantined(doc_id) is not None:
                shard = self.router.shard_of(doc_id)
                span.end(outcome="quarantined")
                return {"accepted": False, "shard": shard,
                        "reason": "quarantined"}
            # async prefetch on FIRST admit, budgeted by the bucket
            # flush deadline — by the time the bucket is due the doc
            # is usually warm. The unlocked assignments peek is a
            # benign race: a doc already warm/pending is a no-op
            # prefetch, and prefetch itself re-checks under its lock.
            if doc_id not in self.router.assignments:
                hyd.prefetch(doc_id,
                             budget_s=self.queue.flush_deadline_s)
        # stamp the admit-time lease epoch; the flush rechecks it
        epoch = self.epoch_of(doc_id) if self.epoch_of is not None \
            else -1
        with self.lock:
            shard = self.router.assign(doc_id)
            self.metrics.bump(shard, "submits")
            already = self.queue.pending_bucket(shard, doc_id) is not None
            try:
                bucket = self.queue.submit(shard, doc_id, n_ops, now,
                                           epoch=epoch,
                                           trace=span.context(),
                                           qos=qos_cls)
            except Backpressure as bp:
                self.metrics.bump(shard, "rejects")
                span.end(outcome="backpressure")
                return {"accepted": False, "shard": shard,
                        "retry_after": bp.retry_after,
                        "qos": qos_cls}
            if already:
                self.metrics.bump(shard, "coalesced")
            self.metrics.observe_queue(shard, self.queue.depth(shard))
        if self.qos is not None:
            # per-class admitted counter — also the controller's
            # arrival-rate estimator input (qos.admitted.<cls> series)
            self.qos.metrics.bump_class(qos_cls, "admitted")
        span.end(outcome="queued", shard=shard, bucket=bucket)
        if obs is not None and span.sampled:
            # journey: open at the scheduler when the HTTP handler did
            # not (driver-driven submits) — begin() is first-wins, so
            # an ingress-admitted journey keeps its (agent, seq)
            j = obs.journey
            j.begin(None, None, doc=doc_id, trace=span.trace_id)
            j.stamp(span.trace_id, "queued")
        return {"accepted": True, "shard": shard, "bucket": bucket}

    # ---- flush -----------------------------------------------------------

    def pump(self, now: Optional[float] = None,
             force: bool = False) -> int:
        """Flush every due bucket. Returns the number of docs
        dispatched (synced inline, or handed to a shard worker).

        Queue mutation (due/take) happens under the global lock only;
        the flush work runs on per-shard worker threads (or inline
        without workers) under each shard's OWN lock, so shards flush
        concurrently and submits never wait on device calls (ROADMAP
        item (a)). Queue depths are re-recorded in a single pass after
        dispatch — one lock acquisition, each touched shard once."""
        return self._pump(now, force, False)[0]

    def _pump(self, now: Optional[float], force: bool, paced: bool):
        """`pump()`, and what the pump thread's loop paces itself by:
        (docs dispatched, the seconds to sit out). `paced` says the
        caller will sit them out (`start_pump`'s loop alone, which
        never forces): a mesh window then counts `paced` and its pause
        is reckoned from the window's own clock, one flush deadline of
        host time a bucket taken (`_pause_s`). 0.0 for everything
        else: batches handed to workers pace themselves."""
        now = time.monotonic() if now is None else now
        taken = []      # (shard, reason, items)
        with self.lock:
            for shard, bucket, reason in self.queue.due(now, force=force):
                if self._busy[shard] and not force:
                    # its worker still has a batch: the bucket stays
                    # queued and coalesces until the worker is free
                    continue
                items = self.queue.take(shard, bucket)
                if items:
                    taken.append((shard, reason, items))
            window = bool(taken) and self.mesh_window
            if window:
                # the window runs on THIS thread and its items have
                # left the queue: count it in flight before the lock
                # is let go, or a drain() on another thread finds the
                # queue empty, nothing in flight, and returns while
                # the window's sessions are still being built
                with self._idle_cv:
                    self._inflight += 1
        synced = 0
        pause_s = 0.0
        if window:
            # window coordinator: every due shard's bucket folds into
            # ONE mesh-sharded program instead of N worker dispatches
            try:
                synced, wall_s, device_s = self._flush_window(taken, paced)
            finally:
                with self._idle_cv:
                    self._inflight -= 1
                    self._idle_cv.notify_all()
            if paced:
                pause_s = self._pause_s(wall_s, device_s, len(taken))
        else:
            for shard, reason, items in taken:
                if self._flush_workers:
                    self._dispatch(shard, reason, items)
                else:
                    self._flush_items(shard, reason, items)
                synced += len(items)
            if taken:
                # the PR-5 control's dispatch accounting: one handoff
                # (>= one device call) per taken bucket per window
                self.metrics.record_window(
                    len(taken), synced,
                    len({s for s, _r, _i in taken}))
        if taken:
            with self.lock:
                for shard in {s for s, _r, _i in taken}:
                    self.metrics.observe_queue(
                        shard, self.queue.depth(shard))
        return synced, pause_s

    def _pause_s(self, wall_s: float, device_s: float,
                 buckets: int) -> float:
        """FLUSH_HOST_SHARE: the seconds a merge thread sits out after
        a flush of `wall_s` seconds that waited `device_s` of them for
        the device. The wait for the device counts towards the rest;
        the host part counts up to one flush deadline a bucket
        flushed, so that a slow flush (a session built, a class
        compiled) is not sat out for seconds. A flush that mostly
        waited for the device comes out at or below 0: no pause."""
        host_s = min(wall_s - device_s,
                     buckets * self.queue.flush_deadline_s)
        return (FLUSH_HOST_SHARE - 1) * host_s - device_s

    def _sit_out(self, pause_s: float) -> None:
        """The pause itself: a root of its own (`sched.flush` has
        closed), so a row, and under a profiler session a span on the
        device trace's clock; a stop ends it. A pause at or below 0
        writes none."""
        if pause_s > 0:
            with self.obs.phases.phase("sched.pause") \
                    if self.obs is not None else NOOP_PHASE:
                self._pump_stop.wait(pause_s)

    # ---- worker pool -----------------------------------------------------

    def _dispatch(self, shard: int, reason: str, items) -> None:
        """Hand one taken batch to its shard's worker (spawned lazily:
        a host-engine scheduler that never pumps never pays for
        threads)."""
        with self._idle_cv:
            self._inflight += 1
            self._busy[shard] += 1
        if self._workers[shard] is None:
            t = threading.Thread(target=self._worker_loop, args=(shard,),
                                 name=f"flush-worker-{shard}",
                                 daemon=True)
            self._workers[shard] = t
            t.start()
        self._work_qs[shard].put((reason, items))

    def _worker_loop(self, shard: int) -> None:
        q = self._work_qs[shard]
        while True:
            job = q.get()
            if job is None:
                return
            reason, items = job
            try:
                wall_s, device_s = self._flush_items(shard, reason, items)
                if reason != "force":
                    self._sit_out(self._pause_s(wall_s, device_s, 1))
            except Exception as e:      # keep the shard alive, loudly
                self._loop_error("flush_worker", shard, e)
            finally:
                with self._idle_cv:
                    self._inflight -= 1
                    self._busy[shard] -= 1
                    self._idle_cv.notify_all()

    def _loop_error(self, where: str, shard: int,
                    exc: BaseException) -> None:
        """A pump or flush-worker thread survived an exception: the
        thread must keep serving, so the failure is counted
        (`pump_errors`), recorded with its text and printed — the
        taken work is lost to this flush (its ops stay durable; reads
        answer from the host checkout, counted `reads_from_host`).
        Filed under the shard the failing bank tagged the exception
        with (`bank.device_error`), else under the caller's `shard`."""
        shard = getattr(exc, "dt_shard", shard)
        self.metrics.bump(shard, "pump_errors")
        if self.obs is not None:
            self.obs.recorder.record(
                "pump_error", where=where, shard=shard,
                error=f"{exc.__class__.__name__}: {exc}"[:400])
        import sys
        import traceback
        print(f"[dt] {where} (shard {shard}) raised:", file=sys.stderr)
        traceback.print_exception(exc, file=sys.stderr)

    def join_warmup(self, timeout: Optional[float] = None) -> None:
        """Wait for the banks' background warm-up and raise if a
        kernel it was asked for failed to compile."""
        for bank in self.banks:
            bank.join_warmup(timeout=timeout)

    def _wait_idle(self, timeout: float = 30.0) -> None:
        """Block until every dispatched batch has been flushed."""
        deadline = time.monotonic() + timeout
        with self._idle_cv:
            while self._inflight > 0:
                left = deadline - time.monotonic()
                if left <= 0:   # pragma: no cover - defensive
                    return
                self._idle_cv.wait(timeout=left)

    def stop_workers(self) -> None:
        """Join the flush workers deterministically (after a drain()).
        Safe to call repeatedly; workers respawn on the next pump."""
        self._wait_idle()
        for i, w in enumerate(self._workers):
            if w is not None:
                self._work_qs[i].put(None)
        for i, w in enumerate(self._workers):
            if w is not None:
                w.join(timeout=5)
                self._workers[i] = None

    def _fence(self, shard: int, items) -> list:
        """Lease-epoch recheck: drop work admitted under an epoch this
        host no longer holds (`fenced`) — its ops stay durable in the
        oplog for the new owner. Shared by the per-shard flush (recheck
        at merge time inside the worker) and the mesh window coordinator
        (recheck at WINDOW ASSEMBLY — the last host-side moment before a
        doc's rows join the shared super-batch)."""
        if self.epoch_of is None:
            return items
        kept = []
        for item in items:
            if item.epoch != -1 \
                    and self.epoch_of(item.doc_id) != item.epoch:
                self.metrics.bump(shard, "fenced")
                if self.obs is not None:
                    self.obs.recorder.record("flush_fenced",
                                             doc=item.doc_id,
                                             shard=shard,
                                             admit_epoch=item.epoch)
            else:
                kept.append(item)
        return kept

    def _flush_resolve(self, doc_id: str):
        """The flush paths' resolve: identical to `self.resolve` except
        that an exception INSIDE a batch is counted as a flush leak —
        the hydration gate should have filtered the doc first, so the
        soak asserts this stays zero."""
        try:
            return self.resolve(doc_id)
        except Exception as e:
            if self.hydrator is not None:
                self.hydrator.note_flush_leak(doc_id, e)
            raise

    def _hydration_gate(self, shard: int, items) -> list:
        """Residency recheck right after the lease fence: keep warm
        docs, DROP quarantined ones (they must never join a batch),
        and REQUEUE still-cold ones — a delayed flush on the next pump
        once hydration lands, never a stalled batch waiting on disk."""
        hyd = self.hydrator
        if hyd is None:
            return items
        keep, defer, dropped = hyd.flush_gate(shard, items)
        if defer:
            now = time.monotonic()
            with self.lock:
                for it in defer:
                    try:
                        self.queue.submit(shard, it.doc_id, it.n_ops,
                                          now, epoch=it.epoch,
                                          trace=it.trace)
                    except Backpressure:
                        # the queue refilled while this batch was in
                        # flight; the doc's ops are durable, drop the
                        # merge work like a fenced item
                        hyd._bump("deferred_drops")
        if dropped and self.obs is not None:
            self.obs.recorder.record(
                "flush_gate_dropped", shard=shard, docs=len(dropped))
        return keep

    def _flush_items(self, shard: int, reason: str, items,
                     min_fuse: int = 2) -> tuple:
        """Sync one taken batch into its shard's bank, under that
        shard's lock only (items are already off the queue, so a
        concurrent submit for the same doc simply queues fresh work).
        The fencing recheck runs first: work admitted under a lease
        epoch this host no longer holds is dropped (`fenced`), never
        merged — its ops are still in the oplog for the new owner.
        The hydration gate runs second (residency recheck: see
        `_hydration_gate`).

        A device error out of the bank propagates (to the flush worker
        or pump loop, which count it, or to an inline caller). It ends
        the spans marked `error=` and takes this batch's accounting
        with it — `record_flush`, queue waits, read invalidation (cache
        hygiene only: the read cache is frontier-keyed). Groups of the
        batch that committed before the raise keep their device state;
        the rest stay behind their oplogs until their next flush.

        Returns the flush's seconds and, of them, those its fused calls
        waited for the device (what the worker's pacing reads). The
        `sched.flush` root counts the flush by kind: `paced` (a flush
        worker's, held to FLUSH_HOST_SHARE), `forced` (drain, shutdown,
        the warm rounds) or `inline` (the caller's own thread: a read's
        sync, a scheduler without workers)."""
        obs = self.obs
        items = self._fence(shard, items)
        items = self._hydration_gate(shard, items)
        if not items:
            return 0.0, 0.0
        fspan = NOOP_SPAN
        if obs is not None:
            parent = next(
                (i.trace for i in items if i.trace is not None), None)
            if parent is not None:
                fspan = obs.tracer.start(
                    "serve.flush", parent=parent,
                    attrs={"shard": shard, "reason": reason,
                           "docs": len(items)})
        bank = self.banks[shard]
        t0 = time.perf_counter()
        # the root the bank's and the replay's phases hang under
        # (obs/phases.py: they find it on this thread)
        ph = obs.phases.phase("sched.flush", span=fspan) \
            if obs is not None else NOOP_PHASE
        ph.count("forced" if reason == "force" else "paced"
                 if threading.current_thread() is self._workers[shard]
                 else "inline")
        # the spans are context managers so a raise out of the bank
        # (a device error) ends them with `error=<type>`, not never
        with fspan, ph:
            with self._shard_locks[shard]:
                # one device_sync span per taken batch — the whole
                # bucket is (at best) ONE device call now, so per-doc
                # spans would misrepresent the execution shape
                dspan = NOOP_SPAN if not fspan.sampled else \
                    obs.tracer.start("serve.device_sync",
                                     parent=fspan.context(),
                                     attrs={"docs": len(items)})
                with dspan:
                    res = bank.sync_docs(
                        items, self._flush_resolve,
                        oplog_lock=self._sync_lock,
                        device_lock=self._device_locks[shard],
                        min_fuse=min_fuse)
                    dspan.end(fused_calls=res["fused_calls"],
                              fused_docs=res["fused_docs"])
            dur = time.perf_counter() - t0
            fspan.end(dur_s=round(dur, 6))
        self.metrics.record_flush(
            shard, len(items), sum(i.n_ops for i in items), reason,
            dur_s=dur)
        # live telemetry: admit->flush queue wait per merged item (the
        # admission SLO), a flush-latency exemplar when this flush rode
        # a sampled trace, and per-doc ops/device-time attribution
        now_m = time.monotonic()
        for it in items:
            self.metrics.observe_queue_wait(
                max(0.0, now_m - it.enqueued_at))
        if obs is not None:
            if fspan.sampled:
                obs.exemplars.note("serve.flush", dur,
                                   fspan.context().trace_id)
            dev_share = dur / len(items)
            for it in items:
                obs.attrib.note("ops", doc=it.doc_id, n=it.n_ops)
                obs.attrib.note("device_s", doc=it.doc_id, n=dev_share)
        if self.read_invalidate is not None:
            for it in items:
                self.read_invalidate(it.doc_id)
        return dur, res["device_s"]

    # ---- mesh flush window -----------------------------------------------

    def _get_mesh(self):
        """Lazy serve mesh over the shard devices (also built by bank
        0's background warmup indirectly, via the shared jit cache).
        Called BEFORE any shard lock is taken — it briefly needs the
        global lock, and lock order is global → shard, never back."""
        m = self._mesh
        if m is None:
            from ..parallel.mesh import serve_mesh
            with self.lock:
                if self._mesh is None:
                    self._mesh = serve_mesh(len(self.banks))
                m = self._mesh
        return m

    def _flush_window(self, taken, paced: bool):
        """The mesh flush-window coordinator: ONE device program per
        window instead of one per shard.

        Every due bucket in `taken` — across ALL shards — goes through:

          1. fencing recheck (window assembly is merge time here);
          2. host-side planning per shard (`bank.plan_window`,
             min_fuse=1: lone docs join the shared dispatch);
          3. fusable rows concatenated ACROSS shards by (cap, max_ins)
             shape class, laid out by the chip each row lives on
             (`parallel.mesh.home_blocks`) and replayed by
             `mesh_fused_replay` — one `shard_map` program over the
             serve mesh's `docs` axis per class (uniform-shape window
             ⇒ exactly one dispatch), and one more whenever a chip's
             block would pass `mesh_window_rows / ndev` rows (several
             buckets of a shard due at once);
          4. per-shard adoption (`bank.adopt_window`): poisoned /
             length-drift rows evict to the host oracle, serial
             leftovers run the per-doc ladder — the SAME data-fault
             ladder as the per-shard path.

        A replay that RAISES (compiler, runtime) is counted on the
        shard of the class's first row, recorded, and re-raised once
        the window is wound up: its class and the classes after it are
        not replayed (plans are pure, so those sessions simply stay
        behind their oplogs until their next flush), while the classes
        that committed before it are adopted and accounted as usual.

        The `sched.flush` root's steps are `window.plan` (2),
        `window.replay` (3, device locks included) and `window.adopt`
        (4); its counts say where the window's documents went, and its
        kind: `forced` (drain, shutdown, the warm rounds), `paced` (the
        pump thread's own loop, which sits out `FLUSH_HOST_SHARE - 1`
        parts of the window's host time afterwards: `paced` says so)
        or `inline` (another caller's `pump()`).

        Lock order: shard locks (sorted) → oplog lock (inside
        plan/adopt) → device locks (sorted, deduped); the mesh device
        phase holds ONLY the device locks of the shards in the window.
        Returns the number of docs flushed (post-fencing), the
        window's seconds and, of them, those its dispatches waited for
        the device."""
        from ..obs.devprof import PROFILER
        from ..parallel.mesh import home_blocks, mesh_fused_replay
        obs = self.obs
        entries = []        # (shard, reason, items) — post-fencing
        for shard, reason, items in taken:
            items = self._fence(shard, items)
            items = self._hydration_gate(shard, items)
            if items:
                entries.append((shard, reason, items))
        if not entries:
            # an all-fenced window still counts (dispatches=0 keeps it
            # out of the device_calls_per_window denominator)
            self.metrics.record_window(
                0, 0, len({s for s, _r, _i in taken}))
            return 0, 0.0, 0.0
        mesh = self._get_mesh()     # needs self.lock: before shard locks
        shards = sorted({s for s, _r, _i in entries})
        n_docs = sum(len(i) for _s, _r, i in entries)
        fspan = NOOP_SPAN
        if obs is not None:
            parent = next((i.trace for _s, _r, its in entries
                           for i in its if i.trace is not None), None)
            if parent is not None:
                fspan = obs.tracer.start(
                    "serve.mesh_window", parent=parent,
                    attrs={"shards": len(shards), "docs": n_docs})
        t0 = time.perf_counter()
        with contextlib.ExitStack() as sstack:
            # the root the window's steps, the banks' phases and
            # `mesh.replay` hang under (obs/phases.py)
            root = NOOP_PHASE if obs is None else sstack.enter_context(
                obs.phases.phase("sched.flush", span=fspan))
            for s in shards:
                sstack.enter_context(self._shard_locks[s])
            root.step("window.plan")
            wins = [self.banks[s].plan_window(
                        items, self._flush_resolve,
                        oplog_lock=self._sync_lock, min_fuse=1)
                    for s, _r, items in entries]
            # concatenate fusable rows across shards by shape class —
            # rows sharing (cap, max_ins) share one mesh program
            classes: Dict[tuple, list] = {}
            for ei, (s, _r, _items) in enumerate(entries):
                for sessions, plans, doc_ids in wins[ei]["groups"]:
                    for sess, plan, d in zip(sessions, plans, doc_ids):
                        classes.setdefault(
                            (sess.cap, sess.max_ins), []).append(
                                (ei, s, sess, plan, d))
            # device locks of the window's shards, deduped in shard
            # order (co-located shards share a lock object). The
            # comprehension runs directly over the sorted shard list so
            # the acquisition order is lexically evident (dt-lint
            # unsorted-locks) and matches the witness's rank order.
            root.step("window.replay")
            seen: set = set()
            dlocks = [lk for s in shards
                      if id(lk := self._device_locks[s]) not in seen
                      and not seen.add(id(lk))]
            dispatches = mesh_docs = padded_rows = staged_bytes = 0
            homes_off_bank = 0
            failed: List[List[str]] = [[] for _ in entries]
            replayed: List[set] = [set() for _ in entries]
            err: Optional[BaseException] = None
            # a class's rows go out in dispatches in which no chip's
            # block passes `mesh_window_rows / ndev` rows (default
            # `shards x flush_docs` over the mesh: the largest batch
            # class the boot warm-up and a flush of one bucket a shard
            # can have compiled). Several buckets of a shard due at
            # once would otherwise make a batch class of their own
            # and compile it on the pump
            most = max(self.mesh_window_rows // int(mesh.devices.size), 1)
            chunks = []
            for key, rows in sorted(classes.items()):
                blocks, _astray = home_blocks(mesh, [r[2] for r in rows])
                chunks += [(key, [rows[i] for blk in blocks
                                  for i in blk[lo:lo + most]])
                           for lo in range(0, max(map(len, blocks)), most)]
            waited_s = 0.0
            for (cap, mi), rows in chunks:
                sessions = [r[2] for r in rows]
                plans = [r[3] for r in rows]
                t_cls = time.perf_counter()
                with contextlib.ExitStack() as dstack:
                    for lk in dlocks:
                        dstack.enter_context(lk)
                    dspan = NOOP_SPAN if not fspan.sampled else \
                        obs.tracer.start(
                            "serve.mesh_dispatch",
                            parent=fspan.context(),
                            attrs={"docs": len(rows), "cap": cap,
                                   "max_ins": mi})
                    try:
                        ok, device_s, bp, staged = \
                            mesh_fused_replay(mesh, sessions, plans)
                    except Exception as e:
                        dspan.end(error=e.__class__.__name__)
                        self.banks[rows[0][1]].device_error(
                            "mesh", e, docs=len(rows), cap=cap)
                        err = e
                        break
                    mesh_docs += len(rows)
                    padded_rows += bp
                    staged_bytes += staged
                    waited_s += device_s
                    dspan.end(padded_b=bp, staged_bytes=staged)
                    dispatches += 1
                wall = time.perf_counter() - t_cls
                PROFILER.observe_window(wall, device_s, len(rows),
                                        len(shards),
                                        staged_bytes=staged)
                for good, (ei, s, sess, _plan, d) in zip(ok, rows):
                    if good:
                        replayed[ei].add(d)
                        # a committed row left on another chip than
                        # its bank's (the fault PR 21 found and
                        # repaired): counted, and must stay 0
                        dev = self.banks[s].device
                        if dev is not None and (
                                sess.docs.devices() != {dev}
                                or sess.lens.devices() != {dev}):
                            homes_off_bank += 1
                    else:
                        failed[ei].append(d)
            # journey: the window path orchestrates the device phase
            # itself, so the device_replayed stamp lives here (the
            # per-shard path stamps inside bank.sync_docs); planned /
            # adopted ride plan_window / adopt_window for both paths
            if obs is not None:
                j = obs.journey
                for ei, (_s, _r, its) in enumerate(entries):
                    for it in its:
                        if (it.trace is not None and it.trace.sampled
                                and it.doc_id in replayed[ei]):
                            j.stamp(it.trace.trace_id,
                                    "device_replayed")
            # adoption + per-bucket flush accounting, per shard
            root.step("window.adopt")
            # by kind, as `_flush_items`
            root.count("forced" if all(r == "force" for _s, r, _i in entries)
                       else "paced" if paced else "inline")
            # where the window's documents went: the mesh rung, no
            # device work (an empty plan), or the per-doc ladder
            root.count("window_docs", n_docs)
            root.count("window_mesh_docs", mesh_docs)
            root.count("window_serial_docs",
                       sum(len(w["serial"]) for w in wins))
            root.count("homes_off_bank", homes_off_bank)
            for ei, (s, reason, items) in enumerate(entries):
                self.banks[s].adopt_window(
                    wins[ei], failed[ei], oplog_lock=self._sync_lock,
                    device_lock=self._device_locks[s])
                self.metrics.record_flush(
                    s, len(items), sum(i.n_ops for i in items), reason,
                    dur_s=time.perf_counter() - t0)
                if self.read_invalidate is not None:
                    for it in items:
                        self.read_invalidate(it.doc_id)
        dur = time.perf_counter() - t0
        if err is not None:
            fspan.annotate(error=err.__class__.__name__)
        fspan.end(dur_s=round(dur, 6), dispatches=dispatches)
        self.metrics.record_window(dispatches, n_docs, len(shards),
                                   mesh_docs=mesh_docs,
                                   padded_rows=padded_rows,
                                   staged_bytes=staged_bytes,
                                   shape_classes=len(classes))
        # live telemetry (mirrors _flush_items): queue waits, a flush
        # exemplar off the window span, per-doc attribution
        now_m = time.monotonic()
        dev_share = dur / max(n_docs, 1)
        for _s, _r, its in entries:
            for it in its:
                self.metrics.observe_queue_wait(
                    max(0.0, now_m - it.enqueued_at))
                if obs is not None:
                    obs.attrib.note("ops", doc=it.doc_id, n=it.n_ops)
                    obs.attrib.note("device_s", doc=it.doc_id,
                                    n=dev_share)
        if obs is not None and fspan.sampled:
            obs.exemplars.note("serve.flush", dur,
                               fspan.context().trace_id)
        if err is not None:
            raise err
        return n_docs, dur, waited_s

    def drain(self) -> int:
        """Flush everything regardless of triggers (shutdown, rebalance,
        parity checks), then wait for the shard workers to go idle —
        the return means every dispatched doc has actually merged. A
        hydration gate deferral requeues from INSIDE a flush worker, so
        after the workers go idle the depth is re-checked: deferred
        docs get further rounds until they hydrate (bounded by the
        hydrator's defer budget) or the queue is genuinely empty."""
        total = 0
        while True:
            progressed = False
            while self.queue.total_depth():
                n = self.pump(force=True)
                if n == 0:
                    break     # defensive: a take() returning nothing
                progressed = True
                total += n
            self._wait_idle()
            if not self.queue.total_depth() or not progressed:
                return total

    # ---- reads / control -------------------------------------------------

    def text(self, doc_id: str) -> str:
        """Merged text at the oplog's tip: `read_tip`'s answer, the
        document's device session brought to the tip first, and the
        host checkout (counted `reads_from_host` there) only where
        that gives none."""
        got = self.read_tip(doc_id)
        if got is not None:
            return got[0]
        ol = self.resolve(doc_id)       # outside the oplog guard
        with self._sync_lock:
            return ol.checkout_tip().snapshot()

    def read_tip(self, doc_id: str, ph=NOOP_PHASE):
        """The document at its oplog's tip, from its device session:
        `text()`, and the served `GET /doc/{id}` of a scheduler built
        with `reads="device"`. Returns (text, the
        session's frontier as [(agent, seq)]), or None where the host
        has to answer: no resident device session (never built,
        evicted, a poisoned row), a host engine, an owner that is not
        admitted. That is counted `reads_from_host` here; the caller
        checks the oplog out.

        The oplog's length is noted first, so every edit acknowledged
        before the read came is below it. A session behind that length
        is brought to it on this thread (step `get.sync` of `ph`, the
        request's root): the document's pending bucket is taken and
        flushed inline with reason "read", a lone document as a group
        of one (`min_fuse=1`: not the per-doc ladder, which waits for
        the device under the oplog guard); where the shard's worker or
        a mesh window already carries the items, that flush is waited
        for; and where nothing is queued or in flight (the edit's own
        submit is still to come, or was refused) the tail is planned
        and replayed here all the same. A flush that raises raises out
        of the read: a device that fails is never answered by a stale
        row, nor quietly by the host. The fetch (step `get.fetch`) is
        `SessionBank.read_row`, under the shard's device lock alone.
        The oplog guard is held only to name the frontier's agents,
        once a commit of the session. `ph` counts `at_tip` where no
        sync was needed."""
        ol = self.resolve(doc_id)
        shard = self.router.assignments.get(doc_id)
        if shard is None:
            with self.lock:
                shard = self.router.assign(doc_id)
        bank = self.banks[shard]
        n = len(ol)

        def at_tip():
            s = bank.sessions.get(doc_id)
            return s if s is not None and s.oplog is ol \
                and s.synced_to >= n else None

        if bank.engine == "host" or doc_id not in bank.sessions \
                or (self.admit is not None and not self.admit(doc_id)):
            self.metrics.bump(shard, "reads_from_host")
            return None
        sess = at_tip()
        if sess is not None:
            ph.count("at_tip")
        else:
            ph.step("get.sync")
            self._sync_for_read(shard, doc_id, at_tip)
            sess = at_tip()
            if sess is None:
                # evicted by the flush (its row came back poisoned, the
                # bank ran out of room) or dropped from it (a fenced
                # lease, a cold document): the oplog answers
                self.metrics.bump(shard, "reads_from_host")
                return None
        ph.step("get.fetch")
        text, frontier = bank.read_row(sess, self._device_locks[shard])
        named = self._read_frontiers.get(doc_id)
        if named is None or named[0] != frontier:
            with self._sync_lock:
                named = self._read_frontiers[doc_id] = (
                    frontier, ol.cg.local_to_remote_frontier(frontier))
        return text, named[1]

    def _sync_for_read(self, shard: int, doc_id: str, at_tip) -> None:
        """Bring `doc_id`'s session to the length `at_tip` checks, on
        the reading thread (`read_tip`)."""
        def take():
            with self.lock:
                bucket = self.queue.pending_bucket(shard, doc_id)
                return [] if bucket is None else self.queue.take(
                    shard, bucket, limit=self.queue.max_pending)

        items = take()
        if not items:
            # taken already: by the shard's worker, or by the pump for
            # a mesh window. Its flush is waited for, not raced
            deadline = time.monotonic() + READ_WAIT_S
            with self._idle_cv:
                while at_tip() is None and time.monotonic() < deadline \
                        and (self._inflight if self.mesh_window
                             else self._busy[shard]):
                    self._idle_cv.wait(timeout=0.05)
            if at_tip() is not None:
                return
            # nothing queued and nothing in flight (or the wait was
            # long): whatever has been queued since, or the tail alone
            from .admission import PendingMerge
            items = take() or [PendingMerge(
                doc_id, 0, time.monotonic(),
                epoch=self.epoch_of(doc_id)
                if self.epoch_of is not None else -1)]
        step = self.queue.flush_docs
        for lo in range(0, len(items), step):
            # a bucket's neighbours share the shape; no batch larger
            # than a paced flush's, so no class the warm-up did not see
            self._flush_items(shard, "read", items[lo:lo + step],
                              min_fuse=1)
        with self.lock:
            self.metrics.observe_queue(shard, self.queue.depth(shard))

    def rebalance(self, n_shards: int) -> Dict[str, tuple]:
        """Shrink (or restore) the live shard count: drain pending work,
        re-route, and evict moved docs' sessions from their OLD shards
        (they rebuild on the new shard at next merge). Growing past the
        constructed bank count needs a new scheduler — banks hold device
        placement decided at construction."""
        if n_shards > len(self.banks):
            raise ValueError(
                f"cannot grow past the constructed {len(self.banks)} "
                "shards; build a new MergeScheduler")
        self.drain()
        with self.lock:
            moved = self.router.rebalance(n_shards)
        for doc_id, (old, _new) in moved.items():
            with self._shard_locks[old]:
                self.banks[old].evict(doc_id)
        return moved

    def metrics_json(self) -> dict:
        snap = self.metrics.snapshot()
        snap["router_counts"] = self.router.counts()
        if self.obs is not None:
            # beside, not inside, the ServeMetrics schema
            snap["phases"] = self.obs.phases.snapshot()
        return snap

    # ---- background pump -------------------------------------------------

    def start_pump(self, interval_s: Optional[float] = None) -> None:
        if self._pump_thread is not None:
            return
        interval = interval_s if interval_s is not None else \
            max(self.queue.flush_deadline_s / 2, 0.01)

        def loop():
            while not self._pump_stop.wait(interval):
                try:
                    # a mesh window ran on this thread: it keeps the
                    # flush workers' rule (FLUSH_HOST_SHARE)
                    self._sit_out(self._pump(None, False, True)[1])
                except Exception as e:      # keep pumping, loudly
                    self._loop_error("pump", 0, e)

        self._pump_thread = threading.Thread(target=loop, name="merge-pump",
                                             daemon=True)
        self._pump_thread.start()
        if self.qos is not None:
            # the controller's loop lives and dies with the pump: no
            # pump, no flushes, nothing for the deadlines to steer
            self.qos.start()

    def stop_pump(self, drain: bool = True) -> None:
        if self.qos is not None:
            self.qos.stop()
        self._pump_stop.set()
        if self._pump_thread is not None:
            self._pump_thread.join(timeout=2)
            self._pump_thread = None
        self._pump_stop = threading.Event()
        try:
            if drain:
                self.drain()    # inline: a device error raises here
        finally:
            self.stop_workers()
