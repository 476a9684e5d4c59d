"""The load generator: a child process of `bench/run.py`.

Started with `spawn` before the parent imports JAX, so it never holds
the chip. It speaks HTTP over loopback to the server in the parent,
keeps the plain reference (`bench/corpus.py`) in step with every
acknowledged edit, and imports neither `jax` nor `diamond_types_tpu`.

One general generator reads a traffic mix (`bench/mixes/<mix>.json`):

  loop      "closed": `clients` clients (one a document unless the mix
            says fewer; a client then takes its documents in turn),
            each sends its next push when the last is acknowledged.
            "open": `rate_per_s` operations a second on a schedule made
            from the seed, whatever the server does; latency counts
            from when an operation was due.
  get_share share of operations that are `GET /doc/{id}` (open loop)
  burst     what a push is (`corpus.Typist`): `ops` keystrokes of
            run-based typing (`mean_run`, `p_back`), and optionally
            every `paste_every`th push one insert of `paste_chars`
            [lo, hi] characters
  popularity  which documents the open loop's operations go to:
            {"kind": "flat"} (every document as often as every other)
            or {"kind": "zipf", "s": 0.99} (the document of rank r
            gets a share proportional to r^-s; ranks dealt by the seed)
  threads   sender threads (open loop); a document belongs to one
            thread, so its operations never overtake each other and a
            read can be compared with the reference as it arrives
  warm_s    seconds of the same traffic before the window opens
  timeout_s client time-out; an operation that times out or is refused
            has failed

Every seed gets the same set of gaps, kinds and document turns in
another order (an exponential gap for each quantile, so arrivals are
Poisson in shape), so the seed does not change the amount of work.
"""

from __future__ import annotations

import http.client
import json
import os
import threading
import time
import traceback

import numpy as np

from bench import corpus

MAX_FAILURES_KEPT = 200


class Fleet:
    def __init__(self) -> None:
        self.docs = []          # PlainDoc, fleet order
        self.by_id = {}


def request(addr, method: str, path: str, body: bytes = None,
            timeout: float = 60.0):
    """One HTTP/1.0-style exchange on a connection of its own (the
    server closes after each response). Returns (status, body)."""
    conn = http.client.HTTPConnection(addr[0], addr[1], timeout=timeout)
    try:
        conn.request(method, path, body=body)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def push(addr, doc: corpus.PlainDoc, n_ops: int, timeout: float,
         scatter_rng=None):
    """One push by the document's next writer, from that writer's own
    head: its next `n_ops` keystrokes, or with `scatter_rng` a warm
    round's `n_ops` unmergeable inserts. The reference takes the edit
    once it is acknowledged. Returns (ok, operations, error text)."""
    w = doc.next_writer
    doc.next_writer = (w + 1) % len(doc.regions)
    ops = doc.next_push(w, n_ops) if scatter_rng is None \
        else doc.scatter(scatter_rng, w, n_ops)
    body = json.dumps({"agent": f"w{w}", "version": doc.heads[w],
                       "ops": ops}).encode()
    try:
        status, data = request(addr, "POST", f"/doc/{doc.id}/edit", body,
                               timeout)
    except (OSError, http.client.HTTPException) as e:
        # sent but not answered: the reference cannot know its fate
        doc.tainted = True
        doc.typists[w].reset()
        return False, len(ops), f"{e.__class__.__name__}: {e}"
    if status != 200:
        doc.typists[w].reset()      # refused: the run in hand is lost
        return False, len(ops), f"HTTP {status}: {data[:200]!r}"
    doc.acknowledge(w, ops, json.loads(data)["version"])
    return True, len(ops), None


def get(addr, doc: corpus.PlainDoc, timeout: float):
    """`GET /doc/{id}` at the tip. Returns (ok, matches reference,
    error text)."""
    try:
        status, data = request(addr, "GET", f"/doc/{doc.id}", None, timeout)
    except (OSError, http.client.HTTPException) as e:
        return False, True, f"{e.__class__.__name__}: {e}"
    if status != 200:
        return False, True, f"HTTP {status}: {data[:200]!r}"
    return True, data == doc.text(), None


def run_threads(target, n: int, gap_s: float = 0.0) -> None:
    """`target(k)` for k < n, each on a thread of its own, started
    `gap_s` apart; returns when all have ended. The gap keeps senders
    that start together from connecting in one instant: the server
    listens with a backlog of 5, and a burst of connections beyond it
    is answered a second late or, now and then, reset."""
    pool = [threading.Thread(target=target, args=(k,), daemon=True)
            for k in range(n)]
    for t in pool:
        t.start()
        if gap_s:
            time.sleep(gap_s)
    for t in pool:
        t.join()


def sleep_until(t: float) -> None:
    """Sleep to within 2 ms of `t`, then yield the processor (and the
    interpreter lock) in a loop: a plain sleep wakes a millisecond
    late here, and latency counts from when an operation was due."""
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(left - 0.002 if left > 0.002 else 0)


def schedule(mix: dict, n_docs: int, seed: int, seconds: float):
    """The open loop's operations: (due offset from the window's
    opening, is a read, document index), in due order. The same gaps,
    kinds and document turns for every seed, shuffled by the seed."""
    rate = float(mix["rate_per_s"])
    span = float(mix["warm_s"]) + seconds
    n = int(round(rate * span))
    rng = np.random.default_rng([seed, 3])
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps *= span / gaps.sum()
    due = np.cumsum(rng.permutation(gaps)) - gaps.mean() / 2 \
        - float(mix["warm_s"])
    reads = np.zeros(n, bool)
    reads[:int(round(n * float(mix.get("get_share", 0.0))))] = True
    reads = rng.permutation(reads)
    return due, reads, rng.permutation(turns_by_popularity(
        mix.get("popularity", {"kind": "flat"}), n, n_docs, rng))


def turns_by_popularity(pop: dict, n: int, n_docs: int, rng):
    """The documents of `n` operations, before they are shuffled: flat,
    or Zipf by largest remainders, so that every seed has the same
    counts and only deals the ranks to other documents."""
    if pop["kind"] == "flat":
        return np.arange(n) % n_docs
    if pop["kind"] != "zipf":
        raise ValueError(f"popularity {pop['kind']!r}: flat or zipf")
    share = np.arange(1, n_docs + 1, dtype=np.float64) ** -float(pop["s"])
    want = share / share.sum() * n
    counts = np.floor(want).astype(np.int64)
    short = n - int(counts.sum())
    counts[np.argsort(-(want - counts), kind="stable")[:short]] += 1
    return np.repeat(rng.permutation(n_docs), counts)


def run_open(addr, fleet: Fleet, mix: dict, seed: int, t_open: float,
             seconds: float, rows: list, failures: list) -> None:
    due, reads, turns = schedule(mix, len(fleet.docs), seed, seconds)
    threads = int(mix["threads"])
    timeout = float(mix["timeout_s"])
    n_ops = int(mix["burst"]["ops"])
    lock = threading.Lock()

    def worker(k: int) -> None:
        mine = np.nonzero(turns % threads == k)[0]
        out = []
        for i in mine.tolist():
            doc = fleet.docs[int(turns[i])]
            t_due = t_open + float(due[i])
            sleep_until(t_due)
            t_sent = time.monotonic()
            if reads[i]:
                ok, same, err = get(addr, doc, timeout)
                n = 0
            else:
                ok, n, err = push(addr, doc, n_ops, timeout)
                same = True
            t_done = time.monotonic()
            out.append((t_due, t_sent, t_done, bool(reads[i]), ok, n, same))
            if err or not same:
                with lock:
                    failures.append({"doc": doc.id, "due_s": float(due[i]),
                                     "read": bool(reads[i]),
                                     "error": err or "body != reference"})
        with lock:
            rows.extend(out)

    run_threads(worker, threads)


def run_closed(addr, fleet: Fleet, mix: dict, seed: int, t_open: float,
               seconds: float, rows: list, failures: list) -> None:
    docs = fleet.docs
    timeout = float(mix["timeout_s"])
    n_ops = int(mix["burst"]["ops"])
    clients = min(int(mix.get("clients", len(docs))), len(docs))
    warm_s = float(mix["warm_s"])
    t_start = t_open - warm_s
    t_close = t_open + seconds
    lock = threading.Lock()

    def client(k: int) -> None:
        mine = docs[k::clients]
        out, turn = [], 0
        # the clients' first pushes are spread over the warm-up's first
        # second (see `run_threads`); from then on each follows its own
        # acknowledgements
        t_due = t_start + min(1.0, warm_s / 2) * k / clients
        sleep_until(t_due)      # a push is due when the last was answered
        while True:
            t_sent = time.monotonic()
            if t_sent >= t_close:
                break
            doc = mine[turn % len(mine)]
            turn += 1
            ok, n, err = push(addr, doc, n_ops, timeout)
            t_done = time.monotonic()
            out.append((t_due, t_sent, t_done, False, ok, n, True))
            t_due = t_done
            if err:
                with lock:
                    failures.append({"doc": doc.id,
                                     "due_s": t_sent - t_open,
                                     "read": False, "error": err})
                if doc.tainted:
                    break
        with lock:
            rows.extend(out)

    run_threads(client, clients)


def run_traffic(addr, fleet: Fleet, msg: dict) -> dict:
    """Warm-up traffic, then the window. Returns the raw rows, as
    columns; the parent reduces them (`bench/reduce.py`)."""
    mix, seed = msg["mix"], int(msg["seed"])
    t_open, seconds = float(msg["t_open"]), float(msg["seconds"])
    rows, failures = [], []
    runner = run_closed if mix["loop"] == "closed" else run_open
    runner(addr, fleet, mix, seed, t_open, seconds, rows, failures)
    rows.sort()
    if msg.get("failures_path"):
        with open(msg["failures_path"], "w", encoding="utf8") as f:
            for row in failures:
                f.write(json.dumps(row) + "\n")
    cols = list(zip(*rows)) if rows else [[]] * 7
    return {"loop": mix["loop"], "t_open": t_open, "seconds": seconds,
            "due": cols[0], "sent": cols[1], "done": cols[2],
            "read": cols[3], "ok": cols[4], "ops": cols[5],
            "same": cols[6], "n_failures": len(failures),
            "failures": failures[:MAX_FAILURES_KEPT]}


def warm_round(addr, fleet: Fleet, msg: dict) -> dict:
    """The documents `ids` take one push of `rows` unmergeable inserts
    each, at once."""
    docs = [fleet.by_id[d] for d in msg["ids"]]
    errors = []

    def one(i: int) -> None:
        doc = docs[i]
        rng = np.random.default_rng([int(msg["seed"]), 19, int(msg["round"]),
                                     fleet.docs.index(doc)])
        ok, _n, err = push(addr, doc, int(msg["rows"]), 120.0,
                           scatter_rng=rng)
        if not ok:
            errors.append(f"{doc.id}: {err}")

    run_threads(one, len(docs), gap_s=0.005)
    return {"failed": len(errors), "failures": errors[:5]}


def verify(addr, fleet: Fleet, msg: dict) -> dict:
    """Read each document over HTTP and compare it with the reference.
    `texts` hands the reference's text to the parent, which compares
    the device sessions with it."""
    mismatch, texts = [], {}
    for doc_id in msg["ids"]:
        doc = fleet.by_id[doc_id]
        want = doc.text()
        try:
            status, got = request(addr, "GET", f"/doc/{doc_id}", None, 120.0)
        except (OSError, http.client.HTTPException) as e:
            status, got = 0, str(e).encode()
        if status != 200 or got != want:
            mismatch.append(f"{doc_id}: HTTP {status}, {len(got)} bytes "
                            f"against the reference's {len(want)}")
        if msg.get("texts"):
            texts[doc_id] = want.decode("ascii")
    return {"mismatch": mismatch, "texts": texts}


def build(fleet: Fleet, msg: dict) -> dict:
    t0 = time.monotonic()
    seed = int(msg["seed"])
    fleet.docs = [corpus.PlainDoc(
        d["id"], corpus.doc_text(seed, d["index"], d["ops"]), d["writers"],
        [corpus.Typist(np.random.default_rng([seed, 23, d["index"], w]),
                       msg["burst"]) for w in range(d["writers"])])
        for d in msg["docs"]]
    fleet.by_id = {d.id: d for d in fleet.docs}
    return {"built": len(fleet.docs),
            "chars": sum(len(d.text()) for d in fleet.docs),
            "seconds": time.monotonic() - t0}


def state(fleet: Fleet, _msg: dict) -> dict:
    return {"touched": [d.id for d in fleet.docs if d.touched],
            "tainted": [d.id for d in fleet.docs if d.tainted]}


def set_heads(fleet: Fleet, msg: dict) -> dict:
    for doc_id, tip in msg["tips"].items():
        doc = fleet.by_id[doc_id]
        doc.heads = [tip] * len(doc.regions)
    return {"ok": True}


COMMANDS = {"build": lambda a, f, m: build(f, m),
            "heads": lambda a, f, m: set_heads(f, m),
            "state": lambda a, f, m: state(f, m),
            "warm_round": warm_round, "run": run_traffic, "verify": verify}


def serve(conn) -> None:
    """The child's main: answer the parent's commands until `quit` or
    until the parent goes away."""
    fleet = Fleet()
    addr = None
    while True:
        try:
            msg = conn.recv()
        except (EOFError, OSError):
            break
        if msg.get("cmd") == "quit":
            conn.send({"ok": True})
            break
        if "addr" in msg:
            addr = tuple(msg["addr"])
        try:
            out = COMMANDS[msg["cmd"]](addr, fleet, msg)
        except Exception as e:     # reported to the parent, which fails the run
            out = {"error": f"{e.__class__.__name__}: {e}\n"
                   + traceback.format_exc()[-1500:]}
        conn.send(out)
    conn.close()
    os._exit(0)     # no atexit work: nothing here is worth flushing
