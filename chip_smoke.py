#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path starts on the chip.

One process (server, pump, flush workers and clients are its threads)
drives the system's main path once, through the entry points a user
calls — `serve()`, `SyncClient`, `POST /doc/{id}/push`,
`POST /doc/{id}/edit`, `GET /doc/{id}`, `GET /metrics` — at the document
lengths of the upstream project's own benchmark corpora, with the merge
scheduler's device engine flushing to the chip(s) this process owns:

  kernels   the Pallas step kernel (parked outside the flush path),
            compiled for the device and compared with the served XLA
            replay on the same inputs
  load      each document is built in a client replica and pushed as a
            v1 patch; its session is materialised on the device
  rounds    every document takes an edit burst (paper-length documents
            from two agents typing concurrently from their own heads,
            note-length ones from one agent, a handful take a 2-4 KB
            paste); after each round: drain, then read every document
            three ways and require byte equality
  restart   the server is closed and started again on the same
            --data-dir with mesh flush windows; every acknowledged edit
            must read back, and the rounds repeat through the mesh rung
            — one length class per window first (one shape class
            each), then the mixed fleet (a dispatch a shape class, one
            more whenever a chip's block of a class would pass
            `mesh_window_rows / chips` rows)
  kernels   again, at the largest batch shapes the traffic dispatched

Every check is fatal: a non-zero exit with the reason on the last lines
and no result line. On success the last line of stdout is
`{"ok": true, "device": {"platform", "kind", "count"}, ...}`.

Without a TPU this script fails. The one exception is a pre-flight of
the script itself on the CPU, which needs BOTH `JAX_PLATFORMS=cpu` in
the environment and `--tiny` on the command line, says `on_chip: false`
and proves nothing about the chip.

Every time printed here is set-up accounting (compilation apart from
the rest), not a speed: speeds are the benchmark's to measure.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

MAX_INS = 16
ALPHABET = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz    etaoin\n",
                         dtype=np.uint8)

# Scale per chip. Document LENGTH is this system's width and is the
# source's own; fleet size is the depth and is what `reduced` lists.
# `note_max` keeps a note inside its capacity class across the restart
# (sessions re-materialise at 2x the text): pastes go only where the
# text stays under it.
FULL = dict(papers=32, notes=224, paper_ops=259_778,
            note_len=(4_200, 7_800), note_max=8_000, burst_ops=8,
            paste=(2_048, 4_096), pastes_per_round=4, rounds=3,
            kernel_caps=(1 << 14, 1 << 18))
TINY = dict(papers=2, notes=6, paper_ops=6_000,
            note_len=(150, 190), note_max=230, burst_ops=2,
            paste=(24, 40), pastes_per_round=2, rounds=2,
            kernel_caps=(512, 8_192))

DEPLOYMENT = {
    "name": "upstream-bench-lengths/mixed-fleet",
    "source": (
        "upstream diamond-types' own benchmark corpora, "
        "crates/bench/src/main.rs (BASELINE.md, 'Benchmark harness & "
        "datasets'): the sequential class at automerge-paper's size "
        "(259,778 single-keystroke ops, one agent, final text ~1e5 "
        "chars) and the concurrent class (friendsforever: two agents "
        "typing from their own heads)"),
    "assumed": [
        "the corpora are not in the repo: documents are generated from "
        "--seed (run-based typing: geometric runs of mean 14 keystrokes "
        "with 42.5% backspaced, so 259,778 ops leave ~104.9k chars as in "
        "the source) and are never reported under a corpus's name",
        "a note-length class of 4-8 K chars beside the paper-length "
        "class, so two jit capacity classes (2^14, 2^18) are exercised",
        "fleet mix per chip: 32 paper-length + 224 note-length documents",
        "edit bursts of 8 ops per agent per round, 25% small deletes; "
        "pastes of 2-4 KB",
        "one merge-scheduler shard per chip, flush_docs=8, max_ins=16, "
        "headroom=2.0; bank budgets sized from the chip's HBM",
    ],
    "guarantees": [
        "every read equals the CRDT merge of all acknowledged edits "
        "(device session == host engine == client replica, byte for "
        "byte; single-agent documents also == a plain bytearray replay)",
        "an edit acknowledged before a clean shutdown is on disk and "
        "reads back after a restart on the same --data-dir",
    ],
}


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - T0:7.1f}s] {msg}", flush=True)


def require(cond, why: str) -> None:
    if not cond:
        raise SmokeFailure(why)


# ---- seeded documents ------------------------------------------------------

def typing_trace(rng, n_ops: int, mean_run: int = 14,
                 p_back: float = 0.425):
    """`n_ops` single-keystroke ops of run-based typing: jump the cursor,
    type a run, backspace part of it. Returns the columnar patch arrays
    `apply_local_patch_columns` takes plus the run table the plain
    reference replays."""
    n_runs = int(n_ops / (mean_run * (1 + p_back)) * 1.25) + 64
    k = rng.geometric(1.0 / mean_run, size=n_runs).astype(np.int64)
    j = rng.binomial(k, p_back).astype(np.int64)
    cum = np.cumsum(k + j)
    last = int(np.searchsorted(cum, n_ops))
    k, j = k[:last + 1].copy(), j[:last + 1].copy()
    over = int(cum[last] - n_ops)
    dj = min(over, int(j[-1]))
    j[-1] -= dj
    k[-1] -= over - dj
    if k[-1] == 0:
        k, j = k[:-1], j[:-1]
    net = k - j
    cur = np.floor(rng.random(len(k)) * (np.cumsum(net) - net + 1)) \
        .astype(np.int64)
    per = k + j
    run = np.repeat(np.arange(len(k)), per)
    off = np.arange(len(run)) - np.repeat(np.cumsum(per) - per, per)
    is_ins = off < k[run]
    pos = np.where(is_ins, cur[run] + off, cur[run] + 2 * k[run] - 1 - off)
    chars = ALPHABET[rng.integers(0, len(ALPHABET), size=int(k.sum()))] \
        .tobytes()
    return (pos, (~is_ins).astype(np.int64), is_ins.astype(np.int64),
            chars, (cur, k, net))


def plain_replay(model: bytearray, chars: bytes, runs) -> None:
    """The plain reference: the same typing applied to a bytearray, one
    splice per run (what survives a run's backspaces is its prefix)."""
    cur, k, net = runs
    for c, o, keep in zip(cur.tolist(), (np.cumsum(k) - k).tolist(),
                          net.tolist()):
        model[c:c] = chars[o:o + keep]


class Doc:
    """One document of the fleet: its client replica, its writers' heads
    and, while it has a single writer, its plain-reference text."""

    def __init__(self, doc_id: str, kind: str, shard: int) -> None:
        self.id = doc_id
        self.kind = kind            # "paper" | "note"
        self.shard = shard
        self.client = None
        self.model = None           # bytearray | None once concurrent
        self.heads = {}             # agent -> [remote frontier, length]


def burst(rng, length: int, n_ops: int):
    """`n_ops` small edits against a text of `length` chars: inserts of
    1-4 chars, 25% deletes of 1-3 (synth_trace's mix). Returns the JSON
    ops and the new length."""
    ops = []
    for _ in range(n_ops):
        if length > 8 and rng.random() < 0.25:
            n = int(rng.integers(1, 4))
            start = int(rng.integers(0, length - n))
            ops.append({"kind": "del", "start": start, "end": start + n})
            length -= n
        else:
            n = int(rng.integers(1, 5))
            text = ALPHABET[rng.integers(0, 26, size=n)].tobytes().decode()
            ops.append({"kind": "ins", "pos": int(rng.integers(0, length + 1)),
                        "text": text})
            length += n
    return ops, length


def apply_plain(model: bytearray, ops) -> None:
    for op in ops:
        if op["kind"] == "ins":
            model[op["pos"]:op["pos"]] = op["text"].encode()
        else:
            del model[op["start"]:op["end"]]


# ---- HTTP ------------------------------------------------------------------

def http(url: str, data: bytes = None, timeout: float = 120.0) -> bytes:
    req = urllib.request.Request(url, data=data)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read()


# ---- phase: kernels (g) -----------------------------------------------------

def require_mosaic(lowered_text: str, what: str) -> None:
    """On the TPU the program under test must hold a Mosaic kernel: a
    `tpu_custom_call` in its lowering. (Off the TPU — the named-CPU
    pre-flight — Pallas runs interpreted and there is none.)"""
    import jax
    if jax.default_backend() == "tpu":
        require("tpu_custom_call" in lowered_text,
                f"{what}: no Mosaic custom call in the lowered program — "
                "the Pallas kernel would not run as one on the TPU")


def check_apply_op_block(rng, cap: int, b: int, n: int) -> dict:
    """A scan over `apply_op_block` (`replay_ops_pallas`: the hand
    kernel parked outside the flush path, ROADMAP D4) against the
    served XLA replay (`_fused_fn`) on the same seeded ops, at one
    shape."""
    import jax
    import jax.numpy as jnp

    from diamond_types_tpu.tpu import flush_fuse as ff
    from diamond_types_tpu.tpu.pallas_kernels import (_TILE,
                                                      replay_ops_pallas)

    lens = rng.integers(cap // 4, cap // 2, size=b).astype(np.int32)
    docs = np.zeros((b, cap), np.int32)
    for i in range(b):
        docs[i, :lens[i]] = rng.integers(32, 127, size=lens[i])
    # two inserts then a delete, each of 1..max_ins chars, at a position
    # that is legal for the row's length at that step
    # (sized so the text cannot outgrow the row even if every op inserts)
    kmax = max(1, min(MAX_INS, cap // (2 * n)))
    k = rng.integers(1, kmax + 1, size=(b, n)).astype(np.int32)
    is_del = (np.arange(n) % 3 == 2)[None, :]
    dlen, ilen = np.where(is_del, k, 0), np.where(is_del, 0, k)
    cur = lens[:, None] + np.cumsum(ilen - dlen, axis=1) - (ilen - dlen)
    pos = np.floor(rng.random((b, n)) * (cur - dlen + 1)).astype(np.int32)
    # an op at each edge of a lane tile, where the halo is read
    pos[0, 0], pos[1, 0] = min(_TILE, lens[0]), min(_TILE - 1, lens[1])
    chars = np.where(np.arange(MAX_INS)[None, None, :] < ilen[:, :, None],
                     rng.integers(32, 127, size=(b, n, MAX_INS)), 0)
    want_lens = (cur[:, -1] + (ilen - dlen)[:, -1]).astype(np.int32)
    args = [jnp.asarray(a.astype(np.int32))
            for a in (pos, dlen, ilen, chars)]
    got = {}
    for name, fn in (("xla", ff._fused_fn(b, n, MAX_INS, cap)),
                     ("pallas", jax.jit(replay_ops_pallas))):
        state = (jnp.asarray(docs), jnp.asarray(lens))
        if name == "pallas":
            require_mosaic(fn.lower(*state, *args).as_text(),
                           f"apply_op_block b={b} n={n} cap={cap}")
        d, ln = fn(*state, *args)
        got[name] = (np.asarray(d), np.asarray(ln))
    row = {"cap": cap, "b": b, "n": n,
           "lens_equal": np.array_equal(got["xla"][1], got["pallas"][1]),
           "docs_equal": np.array_equal(got["xla"][0], got["pallas"][0])}
    require(row["lens_equal"] and row["docs_equal"],
            f"apply_op_block (Pallas) != XLA replay at b={b} n={n} "
            f"cap={cap}")
    require(np.array_equal(got["xla"][1], want_lens),
            f"XLA replay lengths drifted from the host's at cap {cap}")
    return row


def check_kernels(cfg, rng) -> dict:
    """(g) The Pallas step kernel against the served XLA replay, on the
    same inputs, at both capacity classes. On the TPU the Pallas
    side is Mosaic-compiled — asserted on the lowered program itself,
    never interpreted; a kernel the compiler refuses raises here with
    the compiler's words."""
    from diamond_types_tpu.tpu.runtime import pallas_interpret

    return {"interpreted": pallas_interpret(),
            "apply_op_block": [check_apply_op_block(rng, cap, 8, 16)
                               for cap in cfg["kernel_caps"]]}


def check_kernels_at_traffic_shapes(rng) -> list:
    """(g), second half: the step kernel again at the largest `(b, n)`
    the server warmed or the fleet's own traffic dispatched in each
    capacity class (read off the steer table the jit lookups feed) —
    the pastes run `n` into the hundreds."""
    from diamond_types_tpu.tpu.steer import STEER
    seen = {}
    for cache in ("fused", "mesh"):
        for mi, cap, b, n in STEER.classes(cache):
            if mi == MAX_INS:
                big = seen.setdefault(cap, [1, 1])
                big[0], big[1] = max(big[0], b), max(big[1], n)
    return [check_apply_op_block(rng, cap, b, n)
            for cap, (b, n) in sorted(seen.items())]


# ---- the fleet --------------------------------------------------------------

def make_fleet(cfg, n_shards: int):
    """Doc ids chosen so the scheduler's own router puts exactly the
    per-chip quota of each length class on every shard."""
    from diamond_types_tpu.serve.router import ShardRouter
    router = ShardRouter(n_shards)
    fleet = []
    for kind, quota in (("paper", cfg["papers"]), ("note", cfg["notes"])):
        have = [0] * n_shards
        i = 0
        while min(have) < quota:
            doc_id = f"{kind[0]}{i:05d}"
            i += 1
            s = router.shard_of(doc_id)
            if have[s] < quota:
                have[s] += 1
                fleet.append(Doc(doc_id, kind, s))
    return fleet


def build_and_push(doc: Doc, cfg, base: str, seed: int) -> int:
    from diamond_types_tpu.tools.server import SyncClient
    rng = np.random.default_rng([seed, int(doc.id[1:]), ord(doc.id[0])])
    if doc.kind == "paper":
        n_ops = cfg["paper_ops"]
    else:
        lo, hi = cfg["note_len"]
        n_ops = int(int(rng.integers(lo, hi)) / 0.4035)
    pos, nd, ni, chars, runs = typing_trace(rng, n_ops)
    c = doc.client = SyncClient(base, doc.id, "author", timeout=300.0)
    c.oplog.apply_local_patch_columns(c.agent, pos, nd, ni,
                                      chars.decode("ascii"))
    c.branch.merge(c.oplog, c.oplog.version)
    doc.model = bytearray()
    plain_replay(doc.model, chars, runs)
    require(c.text().encode() == bytes(doc.model),
            f"{doc.id}: client replica != plain reference after typing")
    c.push()
    tip = c.oplog.cg.local_to_remote_frontier(c.oplog.version)
    writers = ("w0", "w1") if doc.kind == "paper" else ("w0",)
    doc.heads = {w: [tip, len(doc.model)] for w in writers}
    return n_ops


def edit(doc: Doc, agent: str, ops, new_len: int, base: str) -> None:
    head = doc.heads[agent]
    body = json.dumps({"agent": agent, "version": head[0],
                       "ops": ops}).encode()
    resp = json.loads(http(f"{base}/doc/{doc.id}/edit", body))
    head[0], head[1] = resp["version"], new_len


def round_edits(doc: Doc, cfg, base: str, rng, paste: bool) -> int:
    """One round's traffic for one document; returns the ops sent."""
    sent = 0
    for agent in doc.heads:
        ops, new_len = burst(rng, doc.heads[agent][1], cfg["burst_ops"])
        if paste and agent == "w0":
            n = int(rng.integers(*cfg["paste"]))
            if doc.kind == "note":
                n = min(n, cfg["note_max"] - new_len)
            text = ALPHABET[rng.integers(0, len(ALPHABET), size=n)] \
                .tobytes().decode()
            ops.append({"kind": "ins",
                        "pos": int(rng.integers(0, new_len + 1)),
                        "text": text})
            new_len += n
        edit(doc, agent, ops, new_len, base)
        if len(doc.heads) == 1:
            apply_plain(doc.model, ops)
        else:
            doc.model = None     # concurrent writers: the CRDT decides
        sent += len(ops)
    return sent


def read_three_ways(doc: Doc, base: str, sched) -> int:
    """(b): HTTP body (host engine) == device session == client replica
    (== the plain reference while the doc has one writer)."""
    via_http = http(f"{base}/doc/{doc.id}")
    via_device = sched.text(doc.id).encode()
    doc.client.pull()
    via_client = doc.client.text().encode()
    require(via_http == via_device,
            f"{doc.id}: device session text != host engine (HTTP) text")
    require(via_http == via_client,
            f"{doc.id}: server text != client replica's own merge")
    if doc.model is not None:
        require(via_http == bytes(doc.model),
                f"{doc.id}: server text != plain reference")
    return len(via_http)


def serve_metrics(base: str) -> dict:
    return json.loads(http(f"{base}/metrics"))["serve"]


ZERO_COUNTERS = ("host_fallbacks", "device_errors", "warmup_errors",
                 "pump_errors", "reads_from_host", "rejects")


def check_counters(m: dict, recorder, where: str) -> None:
    """(c) + (d): nothing fell off the device, nothing was swallowed."""
    bad = {k: m["totals"][k] for k in ZERO_COUNTERS if m["totals"][k]}
    if bad:
        tail = [e for e in recorder.dump() if e["kind"] in (
            "device_error", "pump_error", "warmup_error", "host_fallback",
            "session_evicted")][-5:]
        raise SmokeFailure(f"{where}: counters that must be 0: {bad}; "
                           f"recorder tail: {json.dumps(tail)[:1500]}")
    require(m["queue_bound_violations"] == 0,
            f"{where}: queue bound violated")
    require(m["totals"]["evictions"] == 0,
            f"{where}: {m['totals']['evictions']} sessions evicted — the "
            "fleet is supposed to be resident")


def check_phase_end(name: str, fleet, sched, m: dict, mesh: bool,
                    n_shards: int, ph: dict) -> None:
    """(d), (f) and placement, once the phase's rounds are over."""
    from diamond_types_tpu.tpu.steer import STEER
    ph["metrics"] = {"totals": m["totals"], "fused": m["fused"],
                     "window": m["window"],
                     "steer": STEER.snapshot()}
    merges = [s.merges for b in sched.banks for s in b.sessions.values()]
    require(len(merges) == len(fleet),
            f"{name}: {len(merges)} resident sessions for "
            f"{len(fleet)} documents")
    require(min(merges) >= 1,
            f"{name}: a document never took a device replay")
    by_kind = {k: sorted({sched.banks[d.shard].sessions[d.id].cap
                          for d in fleet if d.kind == k})
               for k in ("note", "paper")}
    ph["capacity_classes"] = by_kind
    require(all(len(c) == 1 for c in by_kind.values()),
            f"{name}: a length class spread over several capacity "
            f"classes ({by_kind}): the deployment drifted")
    if mesh:
        w = m["window"]
        require(w["mesh_docs"] >= len(fleet),
                f"{name}: mesh rung replayed {w['mesh_docs']} docs, "
                f"fleet is {len(fleet)}")
        require(m["fused"]["device_calls"] == 0,
                f"{name}: {m['fused']['device_calls']} per-shard fused "
                "calls in a mesh-window phase")
    else:
        require(m["fused"]["device_calls"] > 0
                and m["fused"]["docs"] >= len(fleet),
                f"{name}: fused replay covered {m['fused']['docs']} "
                f"docs in {m['fused']['device_calls']} calls, fleet "
                f"is {len(fleet)}")
    # placement, in BOTH phases: every session's state sits on its own
    # bank's chip and nowhere else, one shard per chip
    placed = {}
    for bank in sched.banks:
        devs = set()
        for s in bank.sessions.values():
            devs.update(s.docs.devices())
            devs.update(s.lens.devices())
        placed[bank.shard_id] = sorted(str(d) for d in devs)
        require(devs == {bank.device},
                f"{name}: shard {bank.shard_id}'s sessions are on "
                f"{placed[bank.shard_id]}, its bank is on {bank.device}")
    ph["session_devices"] = placed
    require(len({b.device for b in sched.banks}) == n_shards,
            f"{name}: {n_shards} shards are not on {n_shards} distinct "
            f"devices: {placed}")


def check_round_windows(name: str, r: int, cfg, m0: dict, m1: dict,
                        mixed: bool, max_rows: int, row: dict) -> None:
    """(f) the mesh rung's dispatch count, per round: a window takes one
    program a shape class it holds, and one more only when a chip's
    block of the class passes its share of `mesh_window_rows`
    (`max_rows`: the rows a chip at most in a dispatch; several
    buckets of a shard due at once: `_flush_window`). A uniform-shape
    window holds exactly one class; a mixed wave must produce windows
    that hold both."""
    d = {k: m1["window"][k] - m0["window"][k]
         for k in ("device_windows", "dispatches", "shape_classes",
                   "mesh_docs")}
    row["windows"] = d
    require(d["device_windows"] > 0,
            f"{name} round {r}: no mesh window did device work")
    require(d["shape_classes"] <= d["dispatches"]
            <= d["shape_classes"] + d["mesh_docs"] // max_rows,
            f"{name} round {r}: {d['dispatches']} dispatches for "
            f"{d['shape_classes']} shape classes and {d['mesh_docs']} "
            f"rows at {max_rows} rows a chip a dispatch")
    if mixed:
        # (the CPU pre-flight's 16 documents can fall one bucket to a
        # window; at full size a mixed wave cannot avoid mixed windows)
        require(d["shape_classes"] > d["device_windows"] or cfg is TINY,
                f"{name} round {r}: the mixed wave produced no window "
                f"holding both length classes ({d})")
    else:
        require(d["shape_classes"] == d["device_windows"],
                f"{name} round {r}: {d['shape_classes']} shape classes "
                f"in {d['device_windows']} uniform-shape windows, want "
                "exactly 1 each")


# ---- a server phase ----------------------------------------------------------

def sched_opts(cfg, n_docs_per_shard: int, mesh: bool):
    """The scheduler's settings for this deployment, bank budgets sized
    from the chip's memory; returns (opts, hbm bytes or None)."""
    from diamond_types_tpu.tpu.runtime import devices
    hbm = (devices()[0].memory_stats() or {}).get("bytes_limit")
    # half the chip's memory for resident sessions (int32 slots); the
    # rest is for replay temporaries and the stacked window batches
    slots = (hbm // 2) // 4 if hbm else 1 << 24
    return dict(max_sessions_per_shard=2 * n_docs_per_shard,
                max_slots_per_shard=int(slots),
                max_pending=4 * n_docs_per_shard, flush_docs=8,
                mesh_window=mesh), hbm


def run_phase(name: str, cfg, fleet, data_dir: str, device: dict,
              seed: int, mesh: bool, load: bool, report: dict) -> None:
    from diamond_types_tpu.tools.server import serve
    from diamond_types_tpu.tpu.runtime import COMPILE_STATS

    n_shards = device["count"]
    so, hbm = sched_opts(cfg, len(fleet) // n_shards, mesh)
    ph = report[name] = {"mesh_window": mesh, "shards": n_shards,
                         "budgets": {k: so[k] for k in (
                             "max_sessions_per_shard",
                             "max_slots_per_shard", "max_pending")},
                         "hbm_bytes_limit": hbm}
    c0 = COMPILE_STATS.snapshot()
    t0 = time.monotonic()
    httpd = serve(port=0, data_dir=data_dir, serve_shards=n_shards,
                  engine="device", sched_opts=so,
                  obs_opts={"sample_rate": 0.01})
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    pool = ThreadPoolExecutor(max_workers=8)
    try:
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        store, sched = httpd.store, httpd.store.scheduler
        ph["boot"] = {"wall_s": round(time.monotonic() - t0, 2),
                      **COMPILE_STATS.delta(COMPILE_STATS.snapshot(), c0)}
        say(f"{name}: server up on {n_shards} shard(s), mesh_window={mesh}, "
            f"warm-up compiled {ph['boot']['compiles']} programs in "
            f"{ph['boot']['compile_s']}s")
        rng = np.random.default_rng([seed, 77, int(mesh)])

        if load:
            c1, t1 = COMPILE_STATS.snapshot(), time.monotonic()
            ops = sum(pool.map(
                lambda d: build_and_push(d, cfg, base, seed), fleet))
            sched.drain()
            ph["load"] = {"docs": len(fleet), "ops": ops,
                          "wall_s": round(time.monotonic() - t1, 2),
                          **COMPILE_STATS.delta(COMPILE_STATS.snapshot(),
                                                c1)}
            say(f"{name}: {len(fleet)} documents ({ops} ops) built in "
                f"client replicas, pushed and made resident")
        else:
            # (h) every acknowledged edit reads back after the restart
            for d in fleet:
                d.client.base = base
                got = http(f"{base}/doc/{d.id}")
                require(got == d.client.text().encode(),
                        f"{d.id}: text after restart != acknowledged text")
            ph["restart_reads"] = len(fleet)
            say(f"{name}: all {len(fleet)} documents read back after the "
                "restart")

        # one extra round after a restart: its first round only rebuilds
        # the sessions (the new ops are inside the materialised text)
        rounds = cfg["rounds"] + (0 if load else 1)
        ph["rounds"] = []
        by_kind = [[d for d in fleet if d.kind == k]
                   for k in ("note", "paper")]
        for r in range(rounds):
            # the fleet edits at once, shuffled, as a real one would.
            # The mesh phase first sends one length class per wave, so
            # every window is uniform-shape and must take exactly one
            # dispatch (f); its LAST round is the mixed fleet again, so
            # windows hold both classes.
            mixed = not mesh or r == rounds - 1
            waves = [list(rng.permutation(fleet))] if mixed else by_kind
            c1, t1 = COMPILE_STATS.snapshot(), time.monotonic()
            m0 = serve_metrics(base)
            # the round's pastes: papers, and notes with room for one
            room = cfg["note_max"] - cfg["paste"][0] - 4 * cfg["burst_ops"]
            pasted = set()
            for ids in ([d.id for d in fleet if d.kind == "paper"],
                        [d.id for d in fleet if d.kind == "note"
                         and d.heads["w0"][1] <= room]):
                k = min(cfg["pastes_per_round"] // 2 * n_shards, len(ids))
                pasted.update(rng.choice(ids, size=k, replace=False)
                              if k else ())
            sent = 0
            for wave in waves:
                seeds = rng.integers(0, 2 ** 31, size=len(wave))
                sent += sum(pool.map(
                    lambda a: round_edits(
                        a[0], cfg, base, np.random.default_rng(int(a[1])),
                        a[0].id in pasted), zip(wave, seeds)))
                sched.drain()
            c2, t2 = COMPILE_STATS.snapshot(), time.monotonic()
            chars = sum(pool.map(
                lambda d: read_three_ways(d, base, sched), fleet))
            m1 = serve_metrics(base)
            row = {"round": r, "ops_sent": sent, "chars_read": chars,
                   "edit_and_flush": {
                       "wall_s": round(t2 - t1, 2),
                       **COMPILE_STATS.delta(c2, c1)},
                   "reads": {
                       "wall_s": round(time.monotonic() - t2, 2),
                       **COMPILE_STATS.delta(COMPILE_STATS.snapshot(),
                                             c2)},
                   "fused_docs": m1["fused"]["docs"] - m0["fused"]["docs"],
                   "mesh_docs": m1["window"]["mesh_docs"]
                   - m0["window"]["mesh_docs"],
                   "reads_from_device": m1["totals"]["reads_from_device"]
                   - m0["totals"]["reads_from_device"]}
            ph["rounds"].append(row)
            say(f"{name}: round {r}: {sent} ops over {len(fleet)} docs, "
                f"{row['edit_and_flush']['compiles']} compilations while "
                f"flushing, {row['reads']['compiles']} while reading; "
                "three-way equality holds")
            check_counters(m1, store.obs.recorder, f"{name} round {r}")
            if mesh and r > 0:
                check_round_windows(
                    name, r, cfg, m0, m1, mixed,
                    max(sched.mesh_window_rows // n_shards, 1), row)
            require(row["reads_from_device"] == len(fleet),
                    f"{name} round {r}: {row['reads_from_device']} of "
                    f"{len(fleet)} reads came from the device")

        check_phase_end(name, fleet, sched, serve_metrics(base), mesh,
                        n_shards, ph)
    finally:
        pool.shutdown(wait=False, cancel_futures=True)
        httpd.shutdown()
        httpd.server_close()
        thread.join(timeout=30)
    ph["wall_s"] = round(time.monotonic() - t0, 2)
    ph["compile"] = COMPILE_STATS.delta(COMPILE_STATS.snapshot(), c0)


# ---- main ---------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU pre-flight of the script itself; needs "
                    "JAX_PLATFORMS=cpu too, proves nothing about the chip")
    ap.add_argument("--data-dir", default=None,
                    help="server data dir (default: a temp dir, removed)")
    ap.add_argument("--report", default=None,
                    help="also write the full report JSON here (default: "
                    "chiprun_out/chip_smoke.json when on the chip)")
    args = ap.parse_args()

    try:
        from diamond_types_tpu.native import require_native
        from diamond_types_tpu.tpu.runtime import (COMPILE_STATS,
                                                   first_touch)
    except ImportError as e:
        print(f"chip_smoke: needs the diamond_types_tpu checkout it "
              f"belongs to ({e})", file=sys.stderr)
        return 2

    # (a) the device, through the served path's own first touch: raises
    # without a TPU unless the environment named cpu
    device = first_touch()
    on_chip = device["platform"] == "tpu"
    if not on_chip and not args.tiny:
        raise SmokeFailure(
            f"platform is {device['platform']!r}: the full smoke runs on "
            "a TPU only (the CPU pre-flight is JAX_PLATFORMS=cpu "
            "chip_smoke.py --tiny)")
    cfg = TINY if args.tiny else FULL
    report = {"on_chip": on_chip, "tiny": args.tiny, "seed": args.seed,
              "device": {"platform": device["platform"],
                         "kind": device["device_kind"],
                         "count": device["count"]},
              "compile_cache_dir": device["cache_dir"]}
    say(f"device: platform={device['platform']} "
        f"device_kind={device['device_kind']!r} count={device['count']} "
        f"on_chip={on_chip} compile_cache={device['cache_dir']}")

    # (e) the native host core is loaded, not its Python stand-in
    require(require_native(), "DT_TPU_NO_NATIVE is set: the smoke runs "
            "the native host core")
    report["native"] = True

    n_shards = device["count"]
    fleet = make_fleet(cfg, n_shards)
    dep = dict(DEPLOYMENT)
    dep["scale"] = {"chips": n_shards, "shards": n_shards,
                    "paper_docs": cfg["papers"] * n_shards,
                    "note_docs": cfg["notes"] * n_shards,
                    "paper_ops": cfg["paper_ops"],
                    "rounds": cfg["rounds"]}
    dep["reduced"] = [
        "fleet: 256 documents per chip (~48 MiB of resident sessions) "
        "where the chip's HBM would hold thousands — cut for the run's "
        "1200 s limit; document lengths are not cut",
        "traffic: 3 rounds per phase, one burst per writer per round",
    ] + (["--tiny: document lengths AND fleet cut to a CPU pre-flight "
          "size; not a deployment"] if args.tiny else [])
    report["deployment"] = dep
    print(json.dumps({"deployment": dep}), flush=True)

    rng = np.random.default_rng([args.seed, 1])
    c0 = COMPILE_STATS.snapshot()
    report["kernels"] = check_kernels(cfg, rng)
    report["kernels"]["compile"] = COMPILE_STATS.delta(
        COMPILE_STATS.snapshot(), c0)
    say("kernels: apply_op_block "
        + ("(INTERPRETED: cpu)" if report["kernels"]["interpreted"]
           else "Mosaic-compiled") + " matches the XLA replay at "
        f"caps {list(cfg['kernel_caps'])}")

    data_dir = args.data_dir or tempfile.mkdtemp(prefix="dt-chip-smoke-")
    try:
        run_phase("per_shard", cfg, fleet, data_dir, device, args.seed,
                  mesh=False, load=True, report=report)
        run_phase("mesh_window", cfg, fleet, data_dir, device, args.seed,
                  mesh=True, load=False, report=report)
    finally:
        if args.data_dir is None:
            shutil.rmtree(data_dir, ignore_errors=True)

    big = check_kernels_at_traffic_shapes(rng)
    report["kernels"]["apply_op_block"] += big
    say("kernels: apply_op_block matches its XLA twin at the traffic's "
        "largest shapes too: "
        + ", ".join(f"b={k['b']} n={k['n']} cap={k['cap']}" for k in big))

    # (i) set-up accounting, not speed
    total = COMPILE_STATS.snapshot()
    in_rounds = {k: sum(r[k]["compiles"] for p in ("per_shard", "mesh_window")
                        for r in report[p]["rounds"])
                 for k in ("edit_and_flush", "reads")}
    report["setup"] = {
        "note": "set-up accounting, not a speed; every speed is "
                "'not measured'",
        "wall_s": round(time.monotonic() - T0, 1),
        "compile_s": round(total["compile_s"] + total["trace_s"], 1),
        "compilations": total["compiles"],
        "compilations_inside_rounds": in_rounds,
        "persistent_cache": {k: total[k] for k in (
            "cache_requests", "cache_hits", "cache_misses")}}
    print(json.dumps({"setup": report["setup"]}), flush=True)

    out = args.report or (os.path.join("chiprun_out", "chip_smoke.json")
                          if on_chip else None)
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w", encoding="utf8") as f:
            json.dump(report, f, indent=1)
    result = {"ok": True, "device": report["device"]}
    if not on_chip:
        result["on_chip"] = False       # a CPU pre-flight, by request
    print(json.dumps(result), flush=True)
    return 0


T0 = time.monotonic()

if __name__ == "__main__":
    try:
        rc = main()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        print(f"chip_smoke: FAILED: {e}", flush=True)
        rc = 1
    except Exception as e:   # no TPU, a kernel the compiler refuses, ...
        import traceback
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {e.__class__.__name__}: "
              f"{str(e)[:2000]}", flush=True)
        rc = 1
    sys.exit(rc)
