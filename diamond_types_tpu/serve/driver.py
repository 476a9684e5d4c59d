"""Workload driver for the merge scheduler (cli `serve-bench`).

Replays a trace corpus through a MergeScheduler on N simulated shards
and byte-parity-gates every document against the single-engine merge —
this is what makes the multi-chip path WORKLOAD-DRIVEN instead of
dryrun-only. Two workload shapes:

  * trace      — every doc replays the same editing trace (the
                 reference's crdt-testdata JSON format, text/trace.py),
                 linear single-agent history. All docs share padded
                 shapes, so the whole fleet shares one jit cache entry
                 per micro-tape length — the shape-bucketing payoff in
                 its purest form.
  * concurrent — per doc, two agents keep typing from their OWN heads
                 (the realtime shape device_soak drives). The
                 (agent, length) schedule is shared across docs — same
                 shapes again — while positions derive from a per-doc
                 rng, so content and merge order genuinely differ.

Parity: for engine="device" the scheduler's answer comes from the replay
kernel's device state (FusedDocSession.text()) while the single-engine
result is the host tracker checkout — two independent engines, compared
byte-for-byte per document. Runs on CPU (JAX_PLATFORMS=cpu + virtual
devices); a real mesh only changes placement, not the code path.
"""

from __future__ import annotations

import random
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..obs import Observability
from ..obs.devprof import PROFILER
from ..text.oplog import OpLog
from ..text.trace import TestData, load_trace
from .scheduler import MergeScheduler


def synth_trace(n_txns: int = 40, ops_per_txn: int = 3,
                seed: int = 7) -> TestData:
    """Deterministic typing-shaped trace (inserts with occasional
    deletes) in the crdt-testdata format — the fallback corpus when no
    trace file is given."""
    rng = random.Random(seed)
    doc: List[str] = []
    txns: List[List[Tuple[int, int, str]]] = []
    for _ in range(n_txns):
        txn: List[Tuple[int, int, str]] = []
        for _ in range(ops_per_txn):
            if doc and rng.random() < 0.25:
                pos = rng.randrange(len(doc))
                n = min(rng.randint(1, 3), len(doc) - pos)
                txn.append((pos, n, ""))
                del doc[pos:pos + n]
            else:
                pos = rng.randint(0, len(doc))
                s = "".join(rng.choice("abcdefgh ")
                            for _ in range(rng.randint(1, 4)))
                txn.append((pos, 0, s))
                doc[pos:pos] = list(s)
        txns.append(txn)
    return TestData(start_content="", end_content="".join(doc),
                    txns=txns)


def _trace_feeders(data: TestData, doc_ids: List[str]):
    """Per-doc generators: each yield applies one txn to the doc's oplog
    (linear append, like replay_into_oplog) and reports its op count."""
    def feeder(ol: OpLog):
        agent = ol.get_or_create_agent_id("trace")
        for txn in data.txns:
            n = 0
            for (pos, num_del, ins) in txn:
                if num_del:
                    ol.add_delete_without_content(agent, pos,
                                                  pos + num_del)
                    n += 1
                if ins:
                    ol.add_insert(agent, pos, ins)
                    n += 1
            yield n
    return {d: feeder for d in doc_ids}


def _concurrent_schedule(rounds: int, edits_per_round: int,
                         seed: int) -> List[List[Tuple[int, int]]]:
    """(agent_idx, insert_len) per edit, SHARED across docs so their
    session shapes coincide (positions stay per-doc)."""
    rng = random.Random(seed)
    return [[(e % 2, rng.randint(1, 4))
             for e in range(edits_per_round)]
            for _ in range(rounds)]


def _concurrent_feeders(schedule, doc_ids: List[str], seed: int):
    def make_feeder(doc_idx: int):
        def feeder(ol: OpLog):
            rng = random.Random(seed * 7919 + doc_idx)
            agents = [ol.get_or_create_agent_id(n)
                      for n in ("ca", "cb")]
            heads: Dict[int, list] = {0: [], 1: []}
            lens = {0: 0, 1: 0}
            for round_edits in schedule:
                for (ai, n) in round_edits:
                    pos = rng.randrange(max(lens[ai], 1)) \
                        if lens[ai] else 0
                    ch = chr(ord("a") + (doc_idx % 26))
                    heads[ai] = [ol.add_insert_at(
                        agents[ai], heads[ai], pos, ch * n)]
                    lens[ai] += n
                yield len(round_edits)
        return feeder
    return {d: make_feeder(i) for i, d in enumerate(doc_ids)}


def _flash_feeders(doc_ids: List[str], rounds: int, seed: int):
    """Flash-crowd tape: a migrating hot doc takes op BURSTS while the
    cold tail trickles, so each window's max-op count — and with it
    the pow2 `n` jit shape class — thrashes from round to round. This
    is the shape-steering stress tape: unsteered, nearly every window
    lands on a cold `(b, n)` class; steered, windows pad onto the
    warmed classes and the jit caches stay hot."""
    ndocs = len(doc_ids)

    def make_feeder(doc_idx: int):
        def feeder(ol: OpLog):
            agent = ol.get_or_create_agent_id("flash")
            rng = random.Random(seed * 104729 + doc_idx)
            ln = 0
            for r in range(rounds):
                hot = (r // 2) % max(ndocs, 1)
                if doc_idx == hot:
                    burst = 6 + rng.randrange(10)
                elif (doc_idx + r) % 7 == 0:
                    burst = 3 + rng.randrange(4)
                else:
                    burst = 1 + rng.randrange(2)
                n = 0
                for _ in range(burst):
                    pos = rng.randint(0, ln)
                    s = "".join(rng.choice("abcdefgh ")
                                for _ in range(rng.randint(1, 3)))
                    ol.add_insert(agent, pos, s)
                    ln += len(s)
                    n += 1
                yield n
        return feeder
    return {d: make_feeder(i) for i, d in enumerate(doc_ids)}


def run_serve_bench(shards: int = 4, docs: int = 8,
                    txns: Optional[int] = None, engine: str = "device",
                    mode: str = "trace", corpus: Optional[str] = None,
                    flush_docs: int = 4, flush_deadline_s: float = 0.02,
                    max_pending: int = 64, max_sessions: int = 4,
                    seed: int = 7, place_on_devices: bool = True,
                    obs_sample_rate: float = 0.01,
                    flush_workers: bool = True,
                    warmup: bool = False,
                    steady_rounds: int = 0,
                    mesh_window: bool = False,
                    telemetry: bool = True,
                    journey: bool = True,
                    steer: bool = True,
                    device_stage: bool = True) -> dict:
    """Replay the workload through a fresh scheduler; returns a JSON-able
    report with throughput, the metrics snapshot, the parity gate, and
    the device-profiler snapshot (wall vs. device time per flush, jit
    cache hit/miss — obs/devprof). The bench runs with the production
    observability defaults (1% trace sampling) so its throughput
    numbers ARE the instrumented numbers. `mesh_window=True` routes
    flushes through the scheduler's mesh flush-window coordinator (one
    shard_map dispatch per window instead of one device call per
    shard) — the report's `device_calls_per_window` is the direct
    A/B signal against the per-shard default.

    `steer=False` / `device_stage=False` are the PR-20 A/B control
    arms: no batch-shape steering (every window dispatches its raw
    pow2 shape class) and host-numpy mesh staging (every resident byte
    round-trips per window). `mode="flash"` replays the flash-crowd
    tape whose per-window op counts thrash the jit shape classes — the
    steering stress shape; with `steady_rounds` the report's
    `steady_jit_hit_rate` measures the post-warm phase alone."""
    doc_ids = [f"doc{i:03d}" for i in range(docs)]
    ols: Dict[str, OpLog] = {}
    for d in doc_ids:
        ol = OpLog()
        ol.doc_id = d
        ols[d] = ol

    if mode == "trace":
        data = load_trace(corpus) if corpus else \
            synth_trace(n_txns=txns or 40, seed=seed)
        if txns:
            data = TestData(start_content=data.start_content,
                            end_content=data.end_content,
                            txns=data.txns[:txns])
        feeders = {d: f(ols[d])
                   for d, f in _trace_feeders(data, doc_ids).items()}
        n_rounds = len(data.txns)
    elif mode == "concurrent":
        n_rounds = txns or 24
        schedule = _concurrent_schedule(n_rounds, 2, seed)
        feeders = {d: f(ols[d]) for d, f in
                   _concurrent_feeders(schedule, doc_ids, seed).items()}
    elif mode == "flash":
        n_rounds = txns or 24
        feeders = {d: f(ols[d]) for d, f in
                   _flash_feeders(doc_ids, n_rounds, seed).items()}
    else:
        raise ValueError(f"unknown mode {mode!r}")

    # enable the profiler BEFORE scheduler construction so background
    # warmup compiles land in the "fused" jit_cache rows
    PROFILER.reset()
    PROFILER.enabled = True
    # steering + staging arms: process-global switches, fresh state per
    # bench run so A/B subprocesses and in-process repeats start equal
    from ..parallel.arena import DEVICE_STAGE, reset_arenas
    from ..tpu.steer import STEER
    STEER.reset(table=True)
    STEER.enabled = steer
    DEVICE_STAGE.enabled = device_stage
    reset_arenas()
    # with flush workers on, worker threads READ oplogs (tail planning)
    # while this loop APPENDS to them — the oplog lock makes that safe,
    # exactly the way the sync server passes DocStore.lock
    oplog_lock = threading.Lock()
    sched = MergeScheduler(
        shards, resolve=ols.__getitem__, engine=engine,
        max_sessions_per_shard=max_sessions,
        max_pending=max_pending, flush_docs=flush_docs,
        flush_deadline_s=flush_deadline_s,
        place_on_devices=place_on_devices,
        sync_lock=oplog_lock,
        flush_workers=flush_workers, warmup=warmup,
        mesh_window=mesh_window)
    obs = Observability(sample_rate=obs_sample_rate, seed=seed,
                        telemetry=telemetry, journey=journey)
    sched.attach_obs(obs)
    if warmup:
        # the bench should measure warm-cache flushes, not count the
        # background compile into the first flush window
        sched.banks[0].join_warmup()

    t0 = time.perf_counter()
    total_ops = 0
    retries = 0
    live = dict(feeders)
    while live:
        done = []
        for d, gen in live.items():
            try:
                with oplog_lock:
                    n = next(gen)
            except StopIteration:
                done.append(d)
                continue
            total_ops += n
            r = sched.submit(d, n_ops=n)
            attempts = 0
            while not r["accepted"]:
                # reject-with-retry-after: flush due work and retry; a
                # couple of polite retries, then force a flush so the
                # feed loop always terminates
                retries += 1
                attempts += 1
                sched.pump(force=attempts > 2)
                r = sched.submit(d, n_ops=n)
        for d in done:
            del live[d]
        sched.pump()
    sched.drain()

    # steady-state phase (lockstep): the continuous feed above runs
    # orders of magnitude faster than the flush cadence, so workers
    # mostly see a backlog whose ops an earlier tip-sync already
    # consumed — realistic for a burst, but it never measures the
    # fused path's steady-state shape. Here every doc is RESIDENT:
    # each round appends one more txn per doc and drains, so each
    # flush carries its whole bucket with fresh tails — the docs-per-
    # device-call occupancy the fused flush exists to raise.
    jit_steady0 = PROFILER.snapshot()["jit_cache"]
    if steady_rounds:
        if mode == "trace":
            sdata = synth_trace(n_txns=steady_rounds, seed=seed + 1)
            sfeeders = {d: f(ols[d]) for d, f in
                        _trace_feeders(sdata, doc_ids).items()}
        elif mode == "flash":
            sfeeders = {d: f(ols[d]) for d, f in _flash_feeders(
                doc_ids, steady_rounds, seed + 1).items()}
        else:
            ssched = _concurrent_schedule(steady_rounds, 2, seed + 1)
            sfeeders = {d: f(ols[d]) for d, f in _concurrent_feeders(
                ssched, doc_ids, seed + 1).items()}
        for _ in range(steady_rounds):
            for d, gen in sfeeders.items():
                try:
                    with oplog_lock:
                        n = next(gen)
                except StopIteration:
                    continue
                total_ops += n
                r = sched.submit(d, n_ops=n)
                while not r["accepted"]:
                    retries += 1
                    sched.pump(force=True)
                    r = sched.submit(d, n_ops=n)
            sched.drain()
    feed_wall = time.perf_counter() - t0
    sched.stop_workers()

    mismatches = []
    for d in doc_ids:
        want = ols[d].checkout_tip().snapshot()
        got = sched.text(d)
        if got != want:
            mismatches.append(d)
    wall = time.perf_counter() - t0

    m = sched.metrics_json()
    # evaluate SLO burn rates over the run's telemetry before building
    # the verdict: a bench that passes parity but burned its latency
    # budget should fail loudly, not average the burn away
    obs.slo.evaluate()
    slo_verdict = obs.slo.verdict()

    # jit hit rates over the REPLAY caches (fused/mesh — the
    # classes steering snaps); steady rate from the post-burst deltas,
    # the ">= 90% steady-state hits" number ISSUE 20 gates on
    devprof = PROFILER.snapshot()
    _replay = ("fused", "mesh")

    def _rate(now, base):
        hits = lookups = 0
        for c in _replay:
            h1 = now.get(c, {}).get("hits", 0)
            m1 = now.get(c, {}).get("misses", 0)
            h0 = base.get(c, {}).get("hits", 0) if base else 0
            m0 = base.get(c, {}).get("misses", 0) if base else 0
            hits += h1 - h0
            lookups += (h1 + m1) - (h0 + m0)
        return (round(hits / lookups, 4) if lookups else None), lookups

    jit_hit_rate, _ = _rate(devprof["jit_cache"], None)
    steady_jit_hit_rate, steady_lookups = _rate(devprof["jit_cache"],
                                                jit_steady0)
    staged_per_window = m["window"]["staged_bytes_per_window"]
    report = {
        "config": {"shards": shards, "docs": docs, "engine": engine,
                   "mode": mode, "corpus": corpus,
                   "rounds": n_rounds, "flush_docs": flush_docs,
                   "flush_deadline_s": flush_deadline_s,
                   "max_pending": max_pending,
                   "max_sessions": max_sessions, "seed": seed,
                   "flush_workers": flush_workers, "warmup": warmup,
                   "steady_rounds": steady_rounds,
                   "mesh_window": sched.mesh_window,
                   "steer": steer, "device_stage": device_stage,
                   "telemetry": telemetry, "journey": journey},
        "total_ops": total_ops,
        "submit_retries": retries,
        "feed_wall_s": round(feed_wall, 3),
        "wall_s": round(wall, 3),
        "ops_per_sec": round(total_ops / max(feed_wall, 1e-9)),
        "parity_ok": not mismatches,
        "parity_mismatches": mismatches,
        "slo": slo_verdict,
        "slo_ok": slo_verdict["slo_ok"],
        "fused_device_calls": m["fused"]["device_calls"],
        "fused_occupancy": m["fused"]["occupancy"],
        # the N-dispatches-to-1 signal: device programs per flush
        # window (mesh mode targets 1.0; the per-shard control pays one
        # per due bucket)
        "device_calls_per_window":
            m["window"]["device_calls_per_window"],
        # shape steering + device-resident staging (PR 20): replay-
        # cache hit rates (overall and steady-phase), host->device
        # staging per mesh window, and the steer policy's own counters
        "jit_hit_rate": jit_hit_rate,
        "steady_jit_hit_rate": steady_jit_hit_rate,
        "steady_jit_lookups": steady_lookups,
        "staged_bytes_per_window": staged_per_window,
        "steer": STEER.snapshot(),
        "metrics": m,
        "devprof": devprof,
        "obs": {"trace": obs.tracer.stats(),
                "ts_recorded": obs.ts.recorded,
                "journey": obs.journey.snapshot()},
    }
    # a banded scorecard so serve-bench A/B arms gate through the SAME
    # engine as scenario runs (`diff_scorecards` / scorecard-diff)
    from ..obs.scorecard import build_scorecard
    steady_or_overall = steady_jit_hit_rate if steady_jit_hit_rate \
        is not None else jit_hit_rate
    report["scorecard"] = build_scorecard(
        scenario={"name": f"serve-bench-{mode}", "seed": seed,
                  "steer": steer, "device_stage": device_stage},
        wall_s=wall, virtual_s=0.0,
        totals={"ops": total_ops, "writes": total_ops, "reads": 0,
                "errors": len(mismatches)},
        latency_p99_s={"flush": m["latencies"]["flush"]["p99"]},
        slo={"slo_ok": slo_verdict["slo_ok"],
             "burning": slo_verdict["burning"],
             "warning": slo_verdict["warning"]},
        ok=bool(not mismatches and slo_verdict["slo_ok"]),
        serve={
            "jit_cache_hit_rate": steady_or_overall,
            "staged_bytes_per_window": staged_per_window,
            "device_calls_per_window":
                m["window"]["device_calls_per_window"],
            "steer_compiles": report["steer"]["compiles"],
        },
    )
    PROFILER.enabled = False
    if mismatches:
        # a parity failure report should be diagnosable standalone:
        # attach the flight-recorder tail (evictions, fallbacks,
        # fencing — the usual suspects for a stale device text)
        report["events_tail"] = obs.recorder.tail(50)
    return report
