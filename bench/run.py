#!/usr/bin/env python3
"""bench/run.py — one cell of BENCHMARK.json, once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Load, warm, measure `--seconds`, verify, print one JSON line (`correct`,
`attempted`, `failed`, `metrics`, `device`, and `breakdown` when
traced), exit. Everything else goes on earlier lines or into
`bench/out/`.

This process is the server's: it calls `serve(engine="device", ...)`,
the entry point users call, holds the chip and is the only process that
imports JAX. The load generator (`bench/gen.py`) is a child started
with `spawn` BEFORE that import; it speaks HTTP over loopback, holds
the plain reference and never imports `jax` or `diamond_types_tpu`.

Everything that belongs to one cell is data, found by the names in
BENCHMARK.json: the `file` of the configuration
(`bench/configs/<config>.json`: fleet classes, scheduler settings,
guarantees), the traffic mix in `mixes/` beside the configuration's
directory (`bench/mixes/<traffic>.json`: loop, rate, what a push is,
popularity, the flush shapes to warm), and one reader for each metric,
`bench/metrics/<metric>.json` (a path into the run's context) or
`bench/metrics/<metric>.py` (`read(ctx)`).

Without a TPU this fails and prints no result. `JAX_PLATFORMS=cpu` plus
`--tiny` is the rehearsal of the harness itself: it says so in `device`
and proves nothing about the chip.
"""

from __future__ import annotations

import time

T_PROC = time.monotonic()

import argparse
import importlib.util
import json
import multiprocessing
import os
import random
import shutil
import sys
import tempfile
import threading
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "bench", "out")

ZERO_COUNTERS = ("reads_from_host", "host_fallbacks", "device_errors",
                 "warmup_errors", "pump_errors")
UNTOUCHED_SAMPLE = 8     # documents the traffic did not touch, also compared
DRAIN_S = 240.0          # the longest the backlog may take to merge


class BenchFailure(Exception):
    """The run cannot produce a result: no result line, exit code 1."""


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_PROC:7.2f}s] {msg}", flush=True)


def load_json(path: str):
    with open(os.path.join(ROOT, path), encoding="utf8") as f:
        return json.load(f)


def ask(conn, msg: dict, timeout: float = 300.0) -> dict:
    conn.send(msg)
    return answer(conn, msg["cmd"], timeout)


def answer(conn, what: str, timeout: float) -> dict:
    if not conn.poll(timeout):
        raise BenchFailure(f"the generator did not answer {what!r} "
                           f"within {timeout:.0f}s")
    out = conn.recv()
    if "error" in out:
        raise BenchFailure(f"generator: {out['error']}")
    return out


def sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(left)


def find_cell(bench: dict, name: str):
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise BenchFailure(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    # a traffic mix lies in `mixes/` beside the directory of the
    # configuration's file: bench/configs/x.json, bench/mixes/y.json
    mixes = os.path.join(os.path.dirname(os.path.dirname(cfg["file"])),
                         "mixes")
    return cell, load_json(cfg["file"]), \
        load_json(os.path.join(mixes, cell["traffic"] + ".json"))


def dig(ctx, path):
    for key in path:
        if not isinstance(ctx, dict) or ctx.get(key) is None:
            return None
        ctx = ctx[key]
    return ctx


def metric_reader(name: str):
    """A metric is a small reader of its own, found by name: a `.json`
    that names a path into the context, or a `.py` with `read(ctx)`.
    A reader that finds nothing to read returns None."""
    base = os.path.join(ROOT, "bench", "metrics", name)
    if os.path.exists(base + ".json"):
        spec = load_json(f"bench/metrics/{name}.json")

        def read(ctx):
            v = dig(ctx, spec["path"])
            if v is not None and "over" in spec:
                over = dig(ctx, spec["over"])
                v = v / over if over else None
            return None if v is None else v * spec.get("scale", 1)
        return read
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"),
        base + ".py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bench: dict, cell: dict, ctx: dict, trace: bool) -> dict:
    """`--trace 0`: the cell's end-to-end metrics. `--trace 1`: its
    per-layer metrics. A metric with a `workloads` key is read only in
    the cells it lists."""
    out = {}
    for m in bench["per_layer" if trace else "end_to_end"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ---- the server ---------------------------------------------------------------

def check_device(tiny: bool, chips: int) -> dict:
    """The device as the served path's own first touch reports it; no
    chip (or fewer than the cell asks for), no run."""
    from diamond_types_tpu.native import require_native
    from diamond_types_tpu.tpu.runtime import first_touch
    dev = first_touch()
    if dev["platform"] != "tpu" and not tiny:
        raise BenchFailure(
            f"platform is {dev['platform']!r}: a cell runs on a TPU only "
            "(the rehearsal is JAX_PLATFORMS=cpu with --tiny)")
    if dev["platform"] == "tpu" and tiny:
        raise BenchFailure("--tiny is the CPU rehearsal's size")
    if dev["count"] < chips:
        raise BenchFailure(f"the cell asks for {chips} chip(s), JAX "
                           f"reports {dev['count']}")
    if not require_native():
        raise BenchFailure("DT_TPU_NO_NATIVE is set: a cell runs the "
                           "native host core")
    return dev


def sched_opts(config: dict, classes: list, chips: int) -> dict:
    """The configuration's scheduler settings, bank budgets from the
    chip's `bytes_limit` (half of it for resident sessions, int32
    slots; the rest is for replay temporaries and stacked batches).
    The sessions' floor capacity is the smallest class's: a longer
    document materialises at its own."""
    from diamond_types_tpu.tpu.runtime import devices
    hbm = (devices()[0].memory_stats() or {}).get("bytes_limit")
    per_shard = -(-sum(c["docs"] for c in classes) // chips)
    so = dict(config["sched_opts"])
    so["fused_opts"] = dict(so["fused_opts"],
                            cap=min(c["cap"] for c in classes))
    so.update(max_sessions_per_shard=2 * per_shard,
              max_slots_per_shard=(hbm // 2) // 4 if hbm else 1 << 24,
              max_pending=4 * per_shard)
    return so


def start_server(data_dir: str, chips: int, engine: str, so):
    from diamond_types_tpu.tools.server import serve
    httpd = serve(port=0, data_dir=data_dir,
                  serve_shards=chips if engine == "device" else 0,
                  engine=engine, sched_opts=so,
                  obs_opts={"sample_rate": 0.01})
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    return httpd, thread, ("127.0.0.1", httpd.server_address[1])


def stop_server(httpd, thread) -> None:
    httpd.shutdown()
    httpd.server_close()        # drain, final durable flush
    thread.join(timeout=30)


def fleet_docs(classes: list, chips: int, seed: int) -> list:
    """The fleet, class by class: for each document its id, its number
    in the fleet, its length in operations, its writers and its class.
    Ids are chosen so that the scheduler's own router spreads each
    class evenly over the shards (copied from `chip_smoke.py`'s
    `make_fleet`)."""
    from bench import corpus
    from diamond_types_tpu.serve.router import ShardRouter
    router = ShardRouter(chips)
    docs = []
    for k, cls in enumerate(classes):
        have, i = [0] * chips, 0
        for n_ops in corpus.class_ops(cls, seed, k):
            while True:
                doc_id = f"{cls['prefix']}{i:05d}"
                i += 1
                s = router.shard_of(doc_id)
                if have[s] < -(-cls["docs"] // chips):
                    have[s] += 1
                    break
            docs.append({"id": doc_id, "index": len(docs), "ops": n_ops,
                         "writers": cls["writers"], "class": k})
    return docs


def push_doc(base: str, seed: int, doc: dict):
    """Build the document once in a client replica and push it as one
    encoded patch; returns the tip it was acknowledged at."""
    from bench import corpus
    from diamond_types_tpu.tools.server import SyncClient
    pos, nd, ni, chars = corpus.doc_columns(seed, doc["index"], doc["ops"])
    c = SyncClient(base, doc["id"], "author", timeout=300.0)
    c.oplog.apply_local_patch_columns(c.agent, pos, nd, ni,
                                      chars.decode("ascii"))
    c.push()
    return c.oplog.cg.local_to_remote_frontier(c.oplog.version)


def load_fleet(conn, seed: int, classes: list, mix: dict, chips: int,
               addr, sched, setup: dict) -> list:
    """Generate the fleet from the seed (here as patch columns, in the
    generator as the reference's text), push it and make its sessions
    resident, each at its class's capacity."""
    t0 = time.monotonic()
    docs = fleet_docs(classes, chips, seed)
    conn.send({"cmd": "build", "seed": seed, "docs": docs,
               "burst": mix["burst"], "addr": addr})
    base = f"http://{addr[0]}:{addr[1]}"
    with ThreadPoolExecutor(max_workers=8) as pool:
        tips = list(pool.map(lambda d: push_doc(base, seed, d), docs))
    sched.drain()
    built = answer(conn, "build", 300.0)
    ask(conn, {"cmd": "heads", "tips": {d["id"]: t
                                        for d, t in zip(docs, tips)}})
    setup["load_s"] = time.monotonic() - t0
    sessions = {}
    for bank in sched.banks:
        sessions.update(bank.sessions)
    off = [d["id"] for d in docs if d["id"] not in sessions
           or sessions[d["id"]].cap != classes[d["class"]]["cap"]]
    say(f"{len(sessions)} of {len(docs)} documents resident "
        f"({built['chars']} characters), capacity class(es) "
        f"{sorted({s.cap for s in sessions.values()})}")
    if off:
        raise BenchFailure(
            f"after the load {len(off)} documents are not resident at "
            f"their class's capacity: {off[:5]}")
    return docs


def warm_shapes(conn, sched, seed: int, docs: list, n_classes: int,
                shapes) -> int:
    """Walk the flush shapes this cell's traffic can meet, as set-up:
    with the pump stopped, `k` documents of one fleet class take one
    push of `rows` inserts that the oplog cannot merge (a plan has a
    row for each run) and one drain flushes them as one batch, for
    every [k, rows] of the mix's `warm_shapes` and every class. The
    stack, slice and replay programs of each shape class are then
    compiled (or read from the persistent cache) before the window
    opens."""
    took, r = [], 0
    for c in range(n_classes):
        ids = [d["id"] for d in docs if d["class"] == c]
        first = 0
        for k, rows in shapes:
            t = time.monotonic()
            sched.stop_pump(drain=True)
            out = ask(conn, {"cmd": "warm_round", "rows": rows, "round": r,
                             "seed": seed,
                             "ids": [ids[(first + i) % len(ids)]
                                     for i in range(min(k, len(ids)))]})
            if out["failed"]:
                raise BenchFailure(f"warm round failed: {out['failures']}")
            sched.drain()
            sched.start_pump()
            first += k
            r += 1
            took.append(round(time.monotonic() - t, 2))
    say(f"warm rounds took {took}")
    return r


def settle_autosave(store, timeout: float = 30.0) -> None:
    """Wait until the server's own autosave has saved what the load and
    the warm rounds left dirty. The window's autosave passes are then
    its own traffic's, and the traffic starts just after a pass has
    ended: at the same phase of the autosave timer in every run, where
    a pass that holds `DocStore.lock` for seconds would otherwise fall
    inside the window once more or once less from run to run."""
    deadline = time.monotonic() + timeout
    while store.dirty and time.monotonic() < deadline:
        time.sleep(0.01)
    if store.dirty:
        store.flush(force=True)


# ---- the window -----------------------------------------------------------------

def fused_classes() -> list:
    """The replay program's shape classes compiled so far, as
    (batch, rows, max_ins, capacity): the difference over the window
    names what `compile.in_window` counted."""
    from diamond_types_tpu.tpu import flush_fuse
    return sorted(getattr(flush_fuse, "_fused_jit_cache", {}))


def scrape(httpd) -> dict:
    """The program's counters, with the time they were taken."""
    store = httpd.store
    sessions = [(d, s) for b in store.scheduler.banks
                for d, s in list(b.sessions.items())]
    return {"serve": store.scheduler.metrics_json(),
            "merges": sum(s.merges for _d, s in sessions),
            # oplog items acknowledged and not yet on the device
            "unmerged_ops": sum(max(len(store.docs[d]) - s.synced_to, 0)
                                for d, s in sessions if d in store.docs),
            "_at": time.monotonic()}


def trace_window(t0: float, t1: float, tag: str) -> dict:
    """Profile the window (but for its first and last second, so that
    starting and stopping the profiler do not fall on its ends)."""
    import jax

    from bench import reduce
    tdir = os.path.join(OUT, tag + ".trace")
    shutil.rmtree(tdir, ignore_errors=True)
    edge = min(1.0, (t1 - t0) / 10)
    sleep_until(t0 + edge)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(reduce.WINDOW_SPAN):
            sleep_until(t1 - edge)
    finally:
        jax.profiler.stop_trace()
    say("profiler stopped")
    return {"dir": tdir}


def reduce_window(tr: dict, tag: str, tiny: bool) -> dict:
    """The trace's reduction: busy and idle, top device operations,
    idle gaps by host span."""
    from bench import reduce
    path = reduce.find_xplane(tr["dir"])
    if path is None:
        raise BenchFailure(f"the profiler wrote no trace under {tr['dir']}")
    planes = reduce.load_xplane(path, rehearsal=tiny)
    if not planes["devices"]:
        raise BenchFailure("the trace holds no device plane: "
                           f"{[p['name'] for p in planes['planes']]}")
    red = reduce.reduce_trace(planes)
    red["planes"] = planes["planes"]
    with open(os.path.join(OUT, tag + ".trace.json"), "w",
              encoding="utf8") as f:
        json.dump(red, f, indent=1)
    shutil.rmtree(tr["dir"], ignore_errors=True)
    if "error" in red:
        raise BenchFailure(f"{red['error']}; planes: {red['planes']}")
    return red


def run_window(conn, httpd, mix: dict, seed: int, seconds: float,
               trace: bool, tag: str) -> dict:
    """Warm-up traffic, then the measured window. Returns the
    generator's rows with the counters and compile counts taken at the
    window's two ends, and the trace's reduction."""
    from diamond_types_tpu.tpu.runtime import COMPILE_STATS
    t0 = time.monotonic() + float(mix["warm_s"]) + 0.5
    t1 = t0 + seconds
    conn.send({"cmd": "run", "mix": mix, "seed": seed, "t_open": t0,
               "seconds": seconds,
               "failures_path": os.path.join(OUT, tag + ".failures.jsonl")})
    sleep_until(t0)
    out = {"t0": t0, "t1": t1, "m0": scrape(httpd),
           "c0": COMPILE_STATS.snapshot(), "f0": fused_classes()}
    say("window opens")
    if trace:
        out["trace_dir"] = trace_window(t0, t1, tag)
    sleep_until(t1)
    out["m1"] = scrape(httpd)
    out["c1"] = COMPILE_STATS.snapshot()
    out["new_classes"] = [list(k) for k in fused_classes()
                          if k not in out["f0"]]
    say("window closes")
    out["gen"] = answer(conn, "run", float(mix["timeout_s"]) + 60.0)
    return out


# ---- correct ----------------------------------------------------------------------

def device_text(sess) -> str:
    """The session's text, read without `FusedDocSession.text()`: the
    whole row is fetched and cut on the host, which compiles nothing
    (`text()` compiles one program for each document length)."""
    import numpy as np
    row = np.asarray(sess.docs)
    n = int(np.asarray(sess.lens))
    if n != sess.doc_len or not 0 <= n <= len(row):
        raise ValueError(f"device length {n}, host projection "
                         f"{sess.doc_len}, capacity {len(row)}")
    return row[:n].astype(np.int32).tobytes().decode("utf-32-le")


def drain(sched) -> float:
    """Merge the backlog: until the queue is empty and no flush is in
    flight. Returns the seconds it took."""
    t0 = time.monotonic()
    while True:
        sched.drain()
        with sched._idle_cv:
            idle = sched._inflight == 0
        if idle and not sched.queue.total_depth():
            return time.monotonic() - t0
        if time.monotonic() - t0 > DRAIN_S:
            raise BenchFailure(f"the backlog did not merge in {DRAIN_S}s")
        time.sleep(0.05)


def verify(conn, httpd, doc_ids, checks: list) -> None:
    """Three-way byte equality after the drain: (a) the HTTP body,
    (b) the device session's text, (c) the reference. Appends
    (name, value, limit) rows to `checks`."""
    store, sched = httpd.store, httpd.store.scheduler
    drain(sched)
    got = ask(conn, {"cmd": "verify", "ids": doc_ids, "texts": True})
    for line in got["mismatch"][:5]:
        say(f"verify: {line}")
    checks.append(("http_vs_reference_mismatches", len(got["mismatch"]), 0))
    sessions = {}
    for bank in sched.banks:
        sessions.update(bank.sessions)
    bad = behind = 0
    for doc_id, want in sorted(got["texts"].items()):
        sess = sessions.get(doc_id)
        if sess is None:
            bad += 1
            say(f"verify: {doc_id}: no device session")
            continue
        if sess.synced_to < len(store.get(doc_id)):
            behind += 1
        try:
            same = device_text(sess) == want
        except ValueError as e:
            same = False
            say(f"verify: {doc_id}: {e}")
        if not same:
            bad += 1
            say(f"verify: {doc_id}: device session text != reference")
    checks.append(("device_vs_reference_mismatches", bad, 0))
    checks.append(("sessions_behind_after_drain", behind, 0))


def verify_restart(conn, data_dir: str, doc_ids, checks: list) -> None:
    """A second server on the same --data-dir, host engine, no shards:
    an edit acknowledged before the clean shutdown reads back."""
    httpd, thread, addr = start_server(data_dir, 1, "host", None)
    try:
        got = ask(conn, {"cmd": "verify", "ids": doc_ids, "addr": addr})
    finally:
        stop_server(httpd, thread)
    for line in got["mismatch"][:5]:
        say(f"restart: {line}")
    checks.append(("restart_vs_reference_mismatches",
                   len(got["mismatch"]), 0))


def decide_correct(conn, httpd, all_ids, w: dict, seed: int,
                   checks: list) -> list:
    """The checks that need the first server: three-way equality on
    every touched document and a seeded sample of the rest, the
    counters that must be 0, and a replay adopted by some session.
    Returns the document ids compared."""
    st = ask(conn, {"cmd": "state"})
    touched = set(st["touched"])
    rest = [d for d in all_ids if d not in touched]
    sample = random.Random(seed).sample(rest, min(UNTOUCHED_SAMPLE,
                                                  len(rest)))
    ids = sorted(touched) + sorted(sample)
    verify(conn, httpd, ids, checks)
    end = scrape(httpd)
    w["m_end"] = end
    for key in ZERO_COUNTERS:
        checks.append((key, end["serve"]["totals"][key], 0))
    adopted = end["merges"] - w["m0"]["merges"]
    say(f"fused.device_calls {end['serve']['fused']['device_calls']}, "
        f"replays adopted by sessions since the window opened {adopted}; "
        f"{len(touched)} documents touched, {len(sample)} more sampled")
    checks.append(("device_replays_adopted", adopted, ">0"))
    checks.append(("tainted_documents", len(st["tainted"]), 0))
    return ids


def device_block(tiny: bool) -> dict:
    import jax
    devs = jax.devices()
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devs), default=0)
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": int(peak)}
    if tiny:
        out["rehearsal"] = "cpu --tiny: not a measurement"
    return out


# ---- one run -------------------------------------------------------------------------

def run(args, broken=None) -> dict:
    """One run. `broken` is for the controls and tests under
    `bench/tests/` only: an object whose hooks break the timed path
    underneath the harness, which must then report `correct` false."""
    bench = load_json(args.manifest)
    cell, config, mix = find_cell(bench, args.workload)
    classes = config["tiny" if args.tiny else "fleet"]
    if args.tiny:
        mix = dict(mix, **mix.get("tiny", {}))
    os.makedirs(OUT, exist_ok=True)
    tag = f"{cell['name']}.{args.seed}"
    if "jax" in sys.modules:
        raise BenchFailure("jax was imported before the generator started")
    from bench import gen
    ctx_mp = multiprocessing.get_context("spawn")
    conn, child_conn = ctx_mp.Pipe()
    child = ctx_mp.Process(target=gen.serve, args=(child_conn,),
                           daemon=True)
    child.start()
    child_conn.close()
    data_dir = tempfile.mkdtemp(prefix="dt-bench-")
    httpd = thread = spans = None
    setup, checks = {}, []
    try:
        dev = check_device(args.tiny, cell["chips"])
        from diamond_types_tpu.tpu.runtime import COMPILE_STATS
        say(f"{cell['name']} seed={args.seed} on {dev['platform']} "
            f"{dev['device_kind']!r} x{dev['count']}, compile cache "
            f"{dev['cache_dir']}")
        setup["import_s"] = time.monotonic() - T_PROC
        c_start = COMPILE_STATS.snapshot()
        t = time.monotonic()
        httpd, thread, addr = start_server(
            data_dir, cell["chips"], "device",
            sched_opts(config, classes, cell["chips"]))
        sched = httpd.store.scheduler
        setup["boot_s"] = time.monotonic() - t
        if broken is not None:
            broken.server_started(httpd)
        docs = load_fleet(conn, args.seed, classes, mix, cell["chips"],
                          addr, sched, setup)
        ids = [d["id"] for d in docs]
        t = time.monotonic()
        n = warm_shapes(conn, sched, args.seed, docs, len(classes),
                        mix["warm_shapes"])
        setup["warm_rounds_s"] = time.monotonic() - t
        t = time.monotonic()
        settle_autosave(httpd.store)
        setup["autosave_settle_s"] = time.monotonic() - t
        say(f"{n} flush shapes warmed, autosave settled in "
            f"{setup['autosave_settle_s']:.1f}s")
        if args.trace:
            from bench.instrument import Spans
            spans = Spans()
            spans.install(httpd)
        w = run_window(conn, httpd, mix, args.seed, args.seconds,
                       bool(args.trace), tag)
        setup["setup_s"] = w["t0"] - T_PROC
        setup["compile"] = COMPILE_STATS.delta(w["c0"], c_start)
        from bench import reduce
        g = reduce.reduce_gen(w["gen"])
        with open(os.path.join(OUT, tag + ".rows.json"), "w",
                  encoding="utf8") as f:     # every operation, for a post-mortem
            json.dump({k: w["gen"][k] for k in (
                "t_open", "seconds", "due", "sent", "done", "read", "ok",
                "ops")}, f)
        say(f"window: {g['attempted']} operations, {g['failed']} failed, "
            f"{g['warm_failed']} failed in the warm-up; p50/p95 ms: edit "
            f"{g['edit_ack_ms'].get('p50')}/{g['edit_ack_ms'].get('p95')}, "
            f"checkout {g['checkout_ms'].get('p50')}/"
            f"{g['checkout_ms'].get('p95')}; acked edits/s "
            f"{g['acked_edits_per_s']}")
        t_verify = time.monotonic()
        compared = decide_correct(conn, httpd, ids, w, args.seed, checks)
        checks.append(("reads_vs_reference_mismatches",
                       g["read_mismatches"], 0))
        checks.append(("failed_in_warm_up", g["warm_failed"], 0))
        device = device_block(args.tiny)
        span_sum = None
        if spans is not None:
            span_sum = spans.summary(w["t0"], w["t1"])
            spans.uninstall()
        if broken is not None:
            broken.before_shutdown(httpd)
        stop_server(httpd, thread)
        httpd = None
        verify_restart(conn, data_dir, compared, checks)
    finally:
        if httpd is not None:
            try:
                stop_server(httpd, thread)
            except Exception as e:      # the first error is the one to show
                say(f"server shutdown raised {e!r}")
        try:
            conn.send({"cmd": "quit"})
        except OSError:
            pass                        # the generator is already gone
        child.join(timeout=10)
        if child.is_alive():
            child.terminate()
            child.join(timeout=10)
        conn.close()
        shutil.rmtree(data_dir, ignore_errors=True)

    correct = True
    for name, value, limit in checks:
        ok = value > 0 if limit == ">0" else value <= limit
        correct = correct and ok
        say(f"check {name}: {value} (limit {limit}) "
            f"{'ok' if ok else 'NOT OK'}")
    say(f"verified {len(compared)} documents in "
        f"{time.monotonic() - t_verify:.1f}s")

    t0s, t1s = w["m0"]["serve"], w["m1"]["serve"]
    setup["compile_total_s"] = setup["compile"]["compile_s"] \
        + setup["compile"]["trace_s"]
    ctx = {"cell": cell, "config": config, "mix": mix, "gen": g,
           "setup": setup, "seconds": args.seconds,
           # the program's counters over the window
           "delta": {"flushed_ops": t1s["totals"]["flushed_ops"]
                     - t0s["totals"]["flushed_ops"],
                     "fused_docs": t1s["fused"]["docs"]
                     - t0s["fused"]["docs"],
                     "fused_calls": t1s["fused"]["device_calls"]
                     - t0s["fused"]["device_calls"]},
           "compile_in_window": COMPILE_STATS.delta(w["c1"], w["c0"]),
           "m0": w["m0"], "m1": w["m1"], "m_end": w["m_end"],
           "spans": span_sum, "trace": None, "device": device,
           "peaks": load_json("bench/peaks.json")}
    result = {"correct": bool(correct), "attempted": g["attempted"],
              "failed": g["failed"]}
    if args.trace:
        red = reduce_window(w["trace_dir"], tag, args.tiny)
        if not red["busy_s"] > 0:
            raise BenchFailure("no operation ran on the device inside "
                               f"the traced window: {red['planes']}")
        ctx["trace"] = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    print(json.dumps({"setup": setup,
                      "compile_in_window": ctx["compile_in_window"],
                      "replay_classes_compiled_in_window": w["new_classes"],
                      "late_ms": g["late_ms"],
                      "edit_ack_ms": g["edit_ack_ms"],
                      "checkout_ms": g["checkout_ms"],
                      "acked_edits_per_s": g["acked_edits_per_s"],
                      "spans": span_sum,
                      "failures": w["gen"]["failures"][:20]}), flush=True)
    result["metrics"] = metrics_for(bench, cell, ctx, bool(args.trace))
    result["device"] = device
    return result


def run_cli(argv=None, broken=None):
    """Parse the command line and run once. Returns (exit code, result
    or None); a run that cannot produce a result says why on stderr."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default="BENCHMARK.json",
                    help="another manifest, for the tests under bench/tests")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal of the harness; needs "
                    "JAX_PLATFORMS=cpu too, proves nothing about the chip")
    args = ap.parse_args(argv)
    try:
        return 0, run(args, broken)
    except BenchFailure as e:
        print(f"bench: FAILED: {e}", file=sys.stderr, flush=True)
    except Exception as e:    # no TPU, a program the compiler refuses, ...
        import traceback
        traceback.print_exc()
        print(f"bench: FAILED: {e.__class__.__name__}: {str(e)[:2000]}",
              file=sys.stderr, flush=True)
    return 1, None


def main() -> int:
    rc, result = run_cli()
    if result is not None:
        print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
