"""JSON-exportable scheduler metrics.

One plain-counter surface shared by serve-bench, the soak tools,
chip_smoke.py and the sync server's /metrics endpoint. Everything here is host-side Python
ints/floats — recording a sample never touches the device, so the
metrics path can run inside flush loops without perturbing timings.

Schema (snapshot()):

  {"version": 15,                  # counter-set schema; bump on change
   "uptime_s": s,                  # monotonic since construction
   "shards": N, "flush_docs": B,
   "totals": {"submits", "coalesced", "rejects", "denied", "fenced",
              "flushes", "flushed_docs", "flushed_ops", "builds",
              "evictions", "resyncs", "syncs", "host_fallbacks",
              "fused_calls", "fused_docs", "device_errors",
              "warmup_errors", "pump_errors", "reads_from_device",
              "reads_from_host"},
   "batch_occupancy": mean(flush size) / flush_docs,   # 0..1
   "host_fallback_ratio": host_fallbacks / max(syncs, 1),
   "flush_reasons": {"size": n, "deadline": n, "force": n},
   "flush_size_hist": {"1": n, "2": n, ...},
   "fused": {"device_calls", "docs",          # fused bucket replays
             "occupancy",                     # docs per device call
             "occupancy_hist": {"2": n, ...}},
   "window": {"windows", "device_windows", "dispatches",
              "device_calls_per_window",      # N->1 dispatch signal
              "docs", "mesh_docs", "mesh_padded_rows",
              "shape_classes",                # one program per class
              "mesh_occupancy",               # docs / padded rows
              "shards_hist": {"2": n, ...}},  # shards per window
   "hydration": {"prefetches", "warm_hits", "hydrations", ...},
                                    # the residency tier's counter set
                                    # (HYDRATION_KEYS; all zero until a
                                    # Hydrator is attached)
   "max_depth_seen": d,
   "queue_bound_violations": 0,     # depth observed above max_pending
   "latencies": {"flush": hist,     # obs.hist snapshot w/ p50/p90/p99
                 "hydration_cold_start": hist,   # prefetch/miss -> warm
                 "queue_wait": hist},            # admit -> flush start
   "per_shard": [{"shard", "queue_depth", "submits", "rejects",
                  "flushes", "flushed_docs", "builds", "evictions",
                  "resyncs", "host_fallbacks", "footprint_slots",
                  "flush_wall_s", "device_sync_s"}, ...]}
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List

from ..obs.hist import Histogram


_SHARD_KEYS = ("submits", "coalesced", "rejects", "denied", "fenced",
               "flushes", "flushed_docs", "flushed_ops", "builds",
               "evictions", "resyncs", "syncs", "host_fallbacks",
               "fused_calls", "fused_docs",
               # failures that are NOT data faults (host_fallbacks counts
               # those): an exception out of a device rung or a session
               # build, a failed warm-up compile, an exception a pump or
               # flush-worker loop had to survive. Each comes with a
               # flight-recorder event carrying the error text; all three
               # are 0 on a healthy server.
               "device_errors", "warmup_errors", "pump_errors",
               # a read at the tip (`MergeScheduler.read_tip`) by source:
               # the resident device session, or the host checkout (host
               # engine, no session, not the doc's owner)
               "reads_from_device", "reads_from_host")

# the residency tier's counter set (serve.hydrate.Hydrator feeds these
# through record_hydration; hydrate.py imports the tuple so the two
# surfaces can never drift)
HYDRATION_KEYS = (
    "prefetches",           # async hydrations queued on first admit
    "warm_hits",            # resolve served from the warm map
    "hydrations",           # cold -> warm installs (async + sync)
    "sync_hydrations",      # resolve cold misses hydrated inline
    "attempts", "retries",  # load attempts / attempts after the first
    "timeouts",             # per-attempt HydrationTimeouts
    "load_errors",          # unexpected load exceptions (transient)
    "hydrate_gave_up",      # async ladder exhausted; doc left cold
    "quarantined",          # docs the HYDRATOR quarantined
    "quarantined_drops",    # flush-gate drops of quarantined docs
    "deferrals",            # cold docs requeued for a delayed flush
    "defer_escalations",    # 2nd gate visit: hydrated sync in-flush
    "defer_gave_up",        # defer budget exhausted -> quarantined
    "deferred_drops",       # deferral requeue hit backpressure
    "prefetch_queue_full",  # prefetch rejected, bounded queue full
    "flush_leaks",          # resolve raised INSIDE a batch (must be 0)
    "snapshot_requests",    # bank eviction hook enqueues
    "snapshots",            # successful doc-file persists
    "snapshot_queue_full",  # hook enqueue rejected
    "snapshot_errors",      # persist failed (doc stays warm)
    "evictions_to_snapshot",  # warm evictions that saved first
    "eviction_aborts",      # eviction raced a resolve; doc kept warm
    "spills_to_snapshot",   # device-tier spills: warm state persisted
                            # to the snapshot home under bank/warm-map
                            # pressure (eviction + bank-evict persists)
    "spill_bytes",          # on-disk bytes those spills wrote (home
                            # file growth, clamped at 0 per spill —
                            # compaction can shrink the home)
    "remote_fills",         # cold misses whose empty home was filled
                            # from a peer's snapshot frame (wire tier)
    "remote_fill_errors",   # remote snapshot fetch/apply failures
                            # (doc stays a legitimate fresh-empty doc)
)


class ServeMetrics:
    # bump whenever the counter set changes so bench/soak tooling can
    # detect schema drift across PRs (v2 = uptime_s + version + the
    # `denied` ownership-gate counter; v3 = `fenced`, queued work
    # skipped at flush because its admit-time lease epoch is no longer
    # the one this host holds; v4 = `latencies.flush` histogram and
    # per-shard `flush_wall_s`/`device_sync_s` device-time attribution;
    # v5 = fused-flush counters (`fused_calls`/`fused_docs`) and the
    # `fused` occupancy block — docs folded per vmapped device call;
    # v6 = the `window` block — flush-window dispatch accounting
    # (`device_calls_per_window` is the N-dispatches-to-1 signal the
    # mesh flush window exists to move) + mesh super-batch occupancy;
    # v7 = the `hydration` block (HYDRATION_KEYS — the cold->warm
    # residency tier's counters) + `latencies.hydration_cold_start`;
    # v8 = the `read` block — the follower-read tier's ReadMetrics
    # snapshot (read/metrics.py READ_KEYS + staleness/read_wait
    # histograms) when a ReadPath is attached, null otherwise;
    # v9 = `latencies.queue_wait` (admit -> flush-start wait per merged
    # item, the admission-SLO signal) + the live-telemetry double-write
    # (`ts` TimeSeries, wired by attach_obs: every counter/latency also
    # lands in the windowed ring so rate()/quantile() answer "now");
    # v10 = the `transform` block (device-resident tail planning:
    # docs planned on device vs. the host tracker walk,
    # per-doc cross-check fallbacks, batched dispatches) + the
    # `pallas_fallbacks` shard counter (Pallas replay rung failures
    # that fell to the XLA fused rung);
    # v11 = device-tier spill accounting (`spills_to_snapshot` /
    # `spill_bytes` in the hydration block — scenario scorecards stamp
    # these; prom exports them as dt_serve_hydration_spill*_total);
    # v12 = wire-tier remote hydration (`remote_fills` /
    # `remote_fill_errors` in the hydration block — cold misses
    # hydrated from a peer's compacted snapshot frame);
    # v13 = shape-steered device-resident staging (`staged_bytes` /
    # `staged_bytes_per_window` in the window block — host->device
    # bytes the mesh windows' state staging paid; near-zero when the
    # arena / device-side gather keeps rows resident);
    # v14 = no fallback that hides the device: `pallas_fallbacks` left
    # with the behaviour it counted (a rung that raises now propagates),
    # `device_errors` / `warmup_errors` / `pump_errors` count what used
    # to be swallowed, `reads_from_device` / `reads_from_host` say
    # where a read at the tip was answered from, and `window.shape_classes`
    # counts the (cap, max_ins) classes the mesh windows held;
    # v15 = the `transform` block left with the device-plan transform
    # it counted: every tail is planned by
    # `FusedDocSession.plan_tail`, whose `plan.tail` phase row counts
    # its walks (`xf_native` / `xf_python`)
    SCHEMA_VERSION = 15

    def __init__(self, n_shards: int, flush_docs: int,
                 max_pending: int) -> None:
        self.n_shards = n_shards
        self.flush_docs = flush_docs
        self.max_pending = max_pending
        self.started_at = time.monotonic()
        # flush recording now happens OUTSIDE the scheduler's global
        # lock (per-shard flush locks); counters get their own lock
        self._lock = threading.Lock()
        self.shard: List[Dict[str, int]] = [
            {k: 0 for k in _SHARD_KEYS} for _ in range(n_shards)]
        self.flush_reasons: Dict[str, int] = {}
        self.flush_size_hist: Dict[int, int] = {}
        self.fused_occupancy_hist: Dict[int, int] = {}
        # flush-window dispatch accounting (scheduler-level, not
        # per-shard: a mesh window spans shards by construction)
        self.windows = 0             # pump rounds that took >= 1 bucket
        self.device_windows = 0      # windows issuing >= 1 device prog
        self.window_dispatches = 0   # device programs / worker handoffs
        self.window_docs = 0
        self.mesh_docs = 0           # docs replayed via the mesh prog
        self.mesh_padded_rows = 0    # super-batch rows incl. padding
        self.window_staged_bytes = 0  # host->device staging paid
        self.window_shape_classes = 0  # (cap, max_ins) classes held
        self.window_shards_hist: Dict[int, int] = {}
        self.max_depth_seen = 0
        self.queue_bound_violations = 0
        self.queue_depth: List[int] = [0] * n_shards
        self.footprint_slots: List[int] = [0] * n_shards
        self.flush_latency = Histogram()
        self.queue_wait_latency = Histogram()
        # residency-tier counters: all zero until a Hydrator is
        # attached (the block is always exported so dashboards don't
        # need schema forks)
        self.hydration: Dict[str, int] = {k: 0 for k in HYDRATION_KEYS}
        self.cold_start_latency = Histogram()
        self.flush_wall_s: List[float] = [0.0] * n_shards
        self.device_sync_s: List[float] = [0.0] * n_shards
        # obs.recorder.FlightRecorder, wired by
        # MergeScheduler.attach_obs; only rare events touch it
        self.recorder = None
        # follower-read tier (read/metrics.py ReadMetrics), wired by
        # read.attach_follower_reads; the v8 `read` block is its
        # snapshot, null until a ReadPath is attached
        self.read = None
        # live-telemetry tier (obs/timeseries.py TimeSeries), wired by
        # MergeScheduler.attach_obs; None (or disabled) => every
        # double-write below is a single branch, no allocation
        self.ts = None

    # ---- recording -------------------------------------------------------

    def bump(self, shard: int, key: str, n: int = 1) -> None:
        with self._lock:
            self.shard[shard][key] += n
        if self.ts is not None:
            self.ts.inc(f"serve.{key}", n)

    def record_flush(self, shard: int, n_docs: int, n_ops: int,
                     reason: str, dur_s: float = 0.0) -> None:
        with self._lock:
            c = self.shard[shard]
            c["flushes"] += 1
            c["flushed_docs"] += n_docs
            c["flushed_ops"] += n_ops
            self.flush_reasons[reason] = \
                self.flush_reasons.get(reason, 0) + 1
            self.flush_size_hist[n_docs] = \
                self.flush_size_hist.get(n_docs, 0) + 1
        # histogram carries its own lock; record outside ours
        self.flush_latency.record(dur_s)
        if self.ts is not None:
            self.ts.observe("serve.flush", dur_s)
            self.ts.inc("serve.flushed_ops", n_ops)

    def record_fused(self, shard: int, n_docs: int) -> None:
        """One fused bucket replay: `n_docs` documents folded into a
        single vmapped device call (the occupancy histogram is the
        arithmetic-intensity signal the fused flush exists to raise)."""
        with self._lock:
            c = self.shard[shard]
            c["fused_calls"] += 1
            c["fused_docs"] += n_docs
            self.fused_occupancy_hist[n_docs] = \
                self.fused_occupancy_hist.get(n_docs, 0) + 1

    def record_window(self, dispatches: int, n_docs: int,
                      n_shards: int, mesh_docs: int = 0,
                      padded_rows: int = 0,
                      staged_bytes: int = 0,
                      shape_classes: int = 0) -> None:
        """One flush window: `dispatches` device programs (mesh path:
        the number of shard_map calls, 1 for a uniform-shape window) or
        per-shard worker handoffs (the PR-5 control, >= n_shards when
        several shards' buckets are due) covering `n_docs` docs across
        `n_shards` shards. `device_calls_per_window` in the snapshot is
        dispatches / windows-with-device-work — the N-to-1 dispatch
        claim, directly. `staged_bytes` is the host->device staging
        the window's mesh dispatches paid (v13). `shape_classes` is the
        number of (cap, max_ins) classes a mesh window held: a mixed
        window takes one program per class (v14)."""
        with self._lock:
            self.windows += 1
            if dispatches > 0:
                self.device_windows += 1
            self.window_dispatches += dispatches
            self.window_docs += n_docs
            self.mesh_docs += mesh_docs
            self.mesh_padded_rows += padded_rows
            self.window_staged_bytes += staged_bytes
            self.window_shape_classes += shape_classes
            self.window_shards_hist[n_shards] = \
                self.window_shards_hist.get(n_shards, 0) + 1

    def observe_device_time(self, shard: int, wall_s: float,
                            device_s: float) -> None:
        """Per-shard wall vs. block_until_ready device seconds for one
        doc sync (obs/devprof feeds the process-wide view; this keeps
        the attribution in the /metrics per_shard rows)."""
        with self._lock:
            self.flush_wall_s[shard] += wall_s
            self.device_sync_s[shard] += device_s

    def observe_queue(self, shard: int, depth: int) -> None:
        with self._lock:
            self.queue_depth[shard] = depth
            if depth > self.max_depth_seen:
                self.max_depth_seen = depth
            violated = depth > self.max_pending
            if violated:
                # must stay 0: the bounded-queue contract (admission
                # raises Backpressure before this point); nonzero = a
                # real bug
                self.queue_bound_violations += 1
        if violated and self.recorder is not None:
            self.recorder.record("queue_bound_violation", shard=shard,
                                 depth=depth,
                                 max_pending=self.max_pending)

    def observe_footprint(self, shard: int, slots: int) -> None:
        with self._lock:
            self.footprint_slots[shard] = int(slots)

    def record_hydration(self, event: str, n: int = 1) -> None:
        """One residency-tier event (a HYDRATION_KEYS key). Unknown
        keys are created rather than dropped — a newer Hydrator against
        an older metrics build degrades to extra counters, not lost
        ones."""
        with self._lock:
            self.hydration[event] = self.hydration.get(event, 0) + n
        if self.ts is not None:
            self.ts.inc(f"serve.hydration.{event}", n)

    def observe_cold_start(self, dur_s: float) -> None:
        """Cold-start latency: prefetch enqueue (or resolve miss) to
        warm install. The histogram has its own lock."""
        self.cold_start_latency.record(dur_s)
        if self.ts is not None:
            self.ts.observe("serve.hydration_cold_start", dur_s)

    def observe_queue_wait(self, dur_s: float) -> None:
        """Admit (or coalesce origin) -> flush-start wait for one
        queued merge — the admission-deadline SLO signal."""
        self.queue_wait_latency.record(dur_s)
        if self.ts is not None:
            self.ts.observe("serve.queue_wait", dur_s)

    # ---- export ----------------------------------------------------------

    def snapshot(self) -> dict:
        # the histograms have their own locks: snapshot them before
        # taking ours (never nest)
        flush_hist = self.flush_latency.snapshot()
        cold_hist = self.cold_start_latency.snapshot()
        queue_wait_hist = self.queue_wait_latency.snapshot()
        read_snap = self.read.snapshot() if self.read is not None else None
        with self._lock:
            totals = {k: sum(s[k] for s in self.shard)
                      for k in _SHARD_KEYS}
            flushes = max(totals["flushes"], 1)
            occupancy = (totals["flushed_docs"] / flushes) \
                / self.flush_docs
            return self._snapshot_locked(totals, occupancy, flush_hist,
                                         cold_hist, queue_wait_hist,
                                         read_snap)

    def _snapshot_locked(self, totals, occupancy, flush_hist,
                         cold_hist, queue_wait_hist, read_snap) -> dict:
        return {
            "version": self.SCHEMA_VERSION,
            "uptime_s": round(time.monotonic() - self.started_at, 3),
            "shards": self.n_shards,
            "flush_docs": self.flush_docs,
            "max_pending": self.max_pending,
            "totals": totals,
            "batch_occupancy": round(occupancy, 4),
            "host_fallback_ratio": round(
                totals["host_fallbacks"] / max(totals["syncs"], 1), 4),
            "flush_reasons": dict(self.flush_reasons),
            "flush_size_hist": {str(k): v for k, v in
                                sorted(self.flush_size_hist.items())},
            "fused": {
                "device_calls": totals["fused_calls"],
                "docs": totals["fused_docs"],
                "occupancy": round(
                    totals["fused_docs"]
                    / max(totals["fused_calls"], 1), 4),
                "occupancy_hist": {
                    str(k): v for k, v in
                    sorted(self.fused_occupancy_hist.items())},
            },
            "window": {
                "windows": self.windows,
                "device_windows": self.device_windows,
                "dispatches": self.window_dispatches,
                "device_calls_per_window": round(
                    self.window_dispatches
                    / max(self.device_windows, 1), 4),
                "docs": self.window_docs,
                "mesh_docs": self.mesh_docs,
                "mesh_padded_rows": self.mesh_padded_rows,
                "shape_classes": self.window_shape_classes,
                "mesh_occupancy": round(
                    self.mesh_docs
                    / max(self.mesh_padded_rows, 1), 4),
                "staged_bytes": self.window_staged_bytes,
                "staged_bytes_per_window": round(
                    self.window_staged_bytes
                    / max(self.device_windows, 1), 2),
                "shards_hist": {
                    str(k): v for k, v in
                    sorted(self.window_shards_hist.items())},
            },
            "hydration": dict(self.hydration),
            "read": read_snap,
            "max_depth_seen": self.max_depth_seen,
            "queue_bound_violations": self.queue_bound_violations,
            "latencies": {"flush": flush_hist,
                          "hydration_cold_start": cold_hist,
                          "queue_wait": queue_wait_hist},
            "per_shard": [
                {"shard": i, "queue_depth": self.queue_depth[i],
                 "footprint_slots": self.footprint_slots[i],
                 "flush_wall_s": round(self.flush_wall_s[i], 6),
                 "device_sync_s": round(self.device_sync_s[i], 6),
                 **self.shard[i]}
                for i in range(self.n_shards)],
        }
