"""A push from the kernel's queue to its thread's last line
(tools/server.py `_Server`, obs/phases.py, serve/scheduler.py): the
listen queue (`http.listen_wait`, `listen_depth*`), the handler thread
(`http.thread_start`, `http.thread_cpu`, `http.thread_after`), the
interpreter's queue (`gil.wait`), the CPU by thread class (`cpu`) and
the flush worker's pause (`sched.pause`, `paced` / `forced`), all rows
of the one phase table. Every wait in here has a time limit of its own.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import time

import pytest

from diamond_types_tpu.obs import Observability
from diamond_types_tpu.obs.phases import PhaseTable
from diamond_types_tpu.serve import scheduler as sched_mod
from diamond_types_tpu.serve.scheduler import MergeScheduler
from diamond_types_tpu.text.oplog import OpLog
from diamond_types_tpu.tools import server as server_mod

pytestmark = pytest.mark.obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU_LIVE = ("accept_loop_s", "pump_s", "flush_workers_s", "autosave_s",
            "gil_probe_s", "http_workers_s", "live_handlers_s", "native_s")
needs_tcp_info = pytest.mark.skipif(
    getattr(socket, "TCP_INFO", None) is None,
    reason="no TCP_INFO on this platform")


@pytest.fixture
def every_thread(monkeypatch):
    """Every connection is clocked, not one in eight."""
    monkeypatch.setattr(server_mod, "CLOCKED_EVERY", 1)


@pytest.fixture
def one_worker(monkeypatch):
    """One resident worker: what its `accept()` leaves in the kernel's
    queue is still there when it samples the socket (with four, the
    other three take it meanwhile)."""
    monkeypatch.setattr(server_mod, "HANDLER_THREADS", 1)


def _serve(**kw):
    kw.setdefault("obs_opts", {"sample_rate": 0.0})
    httpd = server_mod.serve(port=0, engine="host", serve_shards=1, **kw)
    addr = ("127.0.0.1", httpd.server_address[1])
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, addr


def _stop(httpd):
    httpd.shutdown()
    httpd.server_close()


def _edit_bytes(doc: str, text: str = "x") -> bytes:
    body = json.dumps({"agent": "a", "version": None, "ops": [
        {"kind": "ins", "pos": 0, "text": text}]}).encode("utf8")
    return (f"POST /doc/{doc}/edit HTTP/1.1\r\nHost: t\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode("ascii") + body


def _send(addr, data: bytes):
    s = socket.create_connection(addr, timeout=10)
    s.sendall(data)
    return s


def _answer(s) -> bytes:
    """The whole response: the server closes a connection a request."""
    out = b""
    try:
        while True:
            part = s.recv(65536)
            if not part:
                return out
            out += part
    finally:
        s.close()


def _edit(addr, doc, text="x") -> bytes:
    return _answer(_send(addr, _edit_bytes(doc, text)))


def _rows(httpd) -> dict:
    return httpd.store.obs.phases.snapshot()["phases"]


def _wait_for(cond, timeout=10.0):
    """A thread's rows are written by its last line, after the response
    is on the wire."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


def _count(httpd, name: str) -> int:
    return _rows(httpd).get(name, {}).get("count", 0)


def _listen_counts(httpd) -> dict:
    """The listening socket's samples on `http.accept_wait`'s counts
    (`pooled` / `born` ride on the same row: test_handler_pool.py)."""
    return {k: v for k, v in _rows(httpd)["http.accept_wait"]
            .get("counts", {}).items() if k.startswith("listen")}


# ---- the kernel's queue --------------------------------------------------------

def _hold_the_next_accept(httpd, seconds):
    """Arms a hold: the worker that waits at the socket, woken by the
    next connection, sleeps `seconds` before its `accept()`, turn in
    hand (what a wait for the interpreter does there), so that
    connection and what connects meanwhile lie in the kernel. Returns
    (arm, held)."""
    hold, held = threading.Event(), threading.Event()
    get_request = httpd.get_request

    def slow_get_request():
        if hold.is_set():
            hold.clear()
            held.set()
            time.sleep(seconds)
        return get_request()
    httpd.get_request = slow_get_request
    return hold, held


@needs_tcp_info
def test_a_held_accept_shows_as_listen_wait_and_depth(every_thread,
                                                      one_worker):
    httpd, addr = _serve()
    if not httpd._tcp_info:
        _stop(httpd)
        pytest.skip("this kernel has the call and fills nothing in")
    hold, held = _hold_the_next_accept(httpd, 0.08)
    try:
        assert b"200" in _edit(addr, "q")
        assert _wait_for(lambda: _count(httpd, "http.thread_cpu") == 1)
        before = _rows(httpd)["http.listen_wait"]
        assert before["count"] == 1
        assert "counts" not in _rows(httpd)["http.accept_wait"]
        # the first accept after the hold samples the listening socket
        httpd._pooled[0] = server_mod.LISTEN_SAMPLE_EVERY - 1
        hold.set()
        conns = [_send(addr, _edit_bytes("q")) for _ in range(6)]
        assert held.wait(timeout=10)
        for s in conns:
            assert b"200" in _answer(s)
        assert _wait_for(lambda: _count(httpd, "http.thread_cpu") == 7)
        row = _rows(httpd)["http.listen_wait"]
        assert row["count"] == 7
        # each of the six lay in the kernel for most of the hold; a
        # jiffy (4 ms at HZ 250) is the clock's step
        assert row["sum_s"] - before["sum_s"] >= 6 * 0.04
        assert 0.04 <= row["max_s"] <= 5.0
        # the sample is taken just after an accept: five still waited
        counts = _rows(httpd)["http.accept_wait"]["counts"]
        assert counts["listen_samples"] == 1
        assert counts["listen_waiting"] == 1
        assert counts["listen_depth"] >= 5
        assert counts["listen_depth_max"] == counts["listen_depth"]
    finally:
        _stop(httpd)


@needs_tcp_info
def test_a_client_that_sends_late_is_not_charged_to_the_listen_queue(
        every_thread):
    """`tcpi_last_data_recv` runs from the request's bytes, not from
    the connection: an idle server reads under a jiffy or two."""
    httpd, addr = _serve()
    try:
        s = socket.create_connection(addr, timeout=10)
        time.sleep(0.1)         # accepted long before its bytes come
        s.sendall(_edit_bytes("late"))
        assert b"200" in _answer(s)
        assert _wait_for(lambda: _count(httpd, "http.thread_cpu") == 1)
        rows = _rows(httpd)
        if httpd._tcp_info:
            assert rows["http.listen_wait"]["sum_s"] <= 0.08
        # the thread started at once; the request line came 100 ms on
        assert rows["http.thread_start"]["sum_s"] < 0.09
        assert rows["http.accept_wait"]["sum_s"] >= 0.09
    finally:
        _stop(httpd)


@pytest.mark.parametrize("kernel", ["none", "unfilled"])
def test_without_tcp_info_the_queue_is_polled_and_no_row_is_written(
        monkeypatch, kernel, every_thread, one_worker):
    """Not Linux (no `TCP_INFO`), or a kernel that has the call and
    fills nothing in (the listening socket's limit reads 0): no
    `http.listen_wait`, no depth; whether a connection waits is still
    asked, of `select`."""
    if kernel == "none":
        monkeypatch.setattr(server_mod, "_TCP_INFO", None)
    else:
        monkeypatch.setattr(server_mod, "_tcp_info", lambda sock, off: 0)
    httpd, addr = _serve()
    hold, held = _hold_the_next_accept(httpd, 0.05)
    try:
        assert httpd._tcp_info is False
        assert b"200" in _edit(addr, "n")
        httpd._pooled[0] = server_mod.LISTEN_SAMPLE_EVERY - 1
        hold.set()
        conns = [_send(addr, _edit_bytes("n")) for _ in range(3)]
        assert held.wait(timeout=10)
        for s in conns:
            assert b"200" in _answer(s)
        assert _wait_for(lambda: _count(httpd, "http.edit") == 4)
        rows = _rows(httpd)
        assert "http.listen_wait" not in rows
        assert rows["http.accept_wait"]["count"] == 4
        assert _listen_counts(httpd) == {
            "listen_samples": 1, "listen_waiting": 1}
        # an idle server's sample finds nobody waiting
        httpd._pooled[0] = server_mod.LISTEN_SAMPLE_EVERY - 1
        assert b"200" in _edit(addr, "n")
        assert _wait_for(lambda: _count(httpd, "http.edit") == 5)
        assert _listen_counts(httpd) == {
            "listen_samples": 2, "listen_waiting": 1}
    finally:
        _stop(httpd)


# ---- the handler thread ----------------------------------------------------------

def test_thread_start_lies_inside_accept_wait_once_a_request(every_thread):
    httpd, addr = _serve()
    try:
        last = {"http.thread_start": 0.0, "http.accept_wait": 0.0}
        for i in range(1, 13):
            if i % 4:
                assert b"200" in _edit(addr, "t")
            else:       # a request with no root: `/metrics`
                assert b"200" in _answer(_send(
                    addr, b"GET /metrics HTTP/1.1\r\nHost: t\r\n\r\n"))
            assert _wait_for(lambda: _count(httpd, "http.thread_cpu") == i)
            rows = _rows(httpd)
            # both written once a request, root or none
            assert rows["http.thread_start"]["count"] == i
            assert rows["http.accept_wait"]["count"] == i
            start = rows["http.thread_start"]["sum_s"]
            wait = rows["http.accept_wait"]["sum_s"]
            assert 0.0 < start - last["http.thread_start"] \
                <= wait - last["http.accept_wait"]
            last = {"http.thread_start": start, "http.accept_wait": wait}
        rows = _rows(httpd)
        assert rows["http.edit"]["count"] == 9
        # `finish()` and the close, after a root: the nine edits
        assert rows["http.thread_after"]["count"] == 9
        assert rows["http.thread_after"]["sum_s"] > 0.0
        assert httpd.accepted_at == {}
    finally:
        _stop(httpd)


def test_thread_cpu_counts_every_connection_refused_ones_included(
        every_thread):
    httpd, addr = _serve()
    try:
        assert b" 200 " in _edit(addr, "c")
        assert b" 200 " in _answer(_send(
            addr, b"GET /doc/c HTTP/1.1\r\nHost: t\r\n\r\n"))
        # refused before a handler: no root, no `http.accept_wait`
        assert b" 400 " in _answer(_send(addr, b"BAD\r\n\r\n"))
        many = b"".join(b"X-%d: 1\r\n" % i for i in range(120))
        assert b" 431 " in _answer(_send(
            addr, b"GET /doc/c HTTP/1.1\r\n" + many + b"\r\n"))
        assert b" 404 " in _answer(_send(
            addr, b"GET /nowhere/at/all HTTP/1.1\r\nHost: t\r\n\r\n"))
        quiet = _send(addr, b"")
        quiet.shutdown(socket.SHUT_WR)              # said nothing, left
        assert _answer(quiet) == b""
        assert _wait_for(lambda: _count(httpd, "http.thread_cpu") == 6)
        rows = _rows(httpd)
        assert rows["http.thread_cpu"]["count"] == 6
        assert rows["http.thread_start"]["count"] == 6
        assert 0.0 < rows["http.thread_cpu"]["sum_s"] < 5.0
        assert rows["http.accept_wait"]["count"] == 3    # reached a handler
        assert rows["http.thread_after"]["count"] == 2   # had a root
        if "http.listen_wait" in rows:      # rides with `accept_wait`
            assert rows["http.listen_wait"]["count"] == 3
        assert httpd.accepted_at == {}
    finally:
        _stop(httpd)


def test_one_connection_in_eight_is_clocked(one_worker):
    # (of each worker's place: with one, of the server's)
    assert server_mod.CLOCKED_EVERY == 8
    assert server_mod.LISTEN_SAMPLE_EVERY % server_mod.CLOCKED_EVERY == 0
    httpd, addr = _serve()
    try:
        for i in range(20):
            if i == 3:      # a request with no root
                assert b"200" in _answer(_send(
                    addr, b"GET /metrics HTTP/1.1\r\nHost: t\r\n\r\n"))
            else:
                assert b" 200 " in _edit(addr, "e")
        assert _wait_for(lambda: _count(httpd, "http.thread_cpu") == 2)
        rows = _rows(httpd)
        assert rows["http.edit"]["count"] == 19
        assert rows["http.accept_wait"]["count"] == 20      # every one
        for name in ("http.thread_start", "http.thread_cpu",
                     "http.thread_after"):
            assert rows[name]["count"] == 2, name
        if httpd._tcp_info:
            assert rows["http.listen_wait"]["count"] == 2
        assert httpd.accepted_at == {}
    finally:
        _stop(httpd)


def test_a_slow_request_says_what_it_waited_in_the_listen_queue(
        every_thread):
    httpd, addr = _serve()
    try:
        _edit(addr, "s")
        lock = httpd.store.lock
        taken = threading.Event()

        def hold():
            with lock:
                taken.set()
                time.sleep(0.3)
        threading.Thread(target=hold, daemon=True).start()
        assert taken.wait(timeout=10)
        _edit(addr, "s")
        rec = httpd.store.obs.recorder
        assert _wait_for(lambda: any(
            e["kind"] == "slow_request" for e in rec.dump()))
        ev = [e for e in rec.dump() if e["kind"] == "slow_request"][0]
        assert ev["accept_wait_ms"] >= 0.0
        if httpd._tcp_info:
            assert 0.0 <= ev["listen_wait_ms"] < 250.0
        else:
            assert "listen_wait_ms" not in ev
    finally:
        _stop(httpd)


# ---- CPU by thread class -----------------------------------------------------------

@pytest.mark.parametrize("threads,burnt_in", [
    ("resident", "http_workers_s"), ("born", "exited_s")])
def test_the_cpu_block_adds_up_and_a_burst_raises_its_threads_class(
        every_thread, monkeypatch, threads, burnt_in):
    if not os.path.isdir("/proc/self/task"):
        pytest.skip("no /proc here")
    if threads == "born":
        # the stdlib's road, a thread a connection: `handle_request()`
        # in a loop, with no `serve_forever` and so no worker
        httpd = server_mod.serve(port=0, engine="host", serve_shards=1,
                                 obs_opts={"sample_rate": 0.0})
        addr = ("127.0.0.1", httpd.server_address[1])
        httpd.timeout, over = 0.05, threading.Event()

        def one_at_a_time():
            while not over.is_set():
                httpd.handle_request()
        loop = threading.Thread(target=one_at_a_time, daemon=True)
        loop.start()
    else:
        # no stall of this machine replaces a worker mid-test
        monkeypatch.setattr(server_mod, "WATCH_TICK_S", 30.0)
        httpd, addr = _serve()
    try:
        _edit(addr, "b")
        assert _wait_for(lambda: _count(httpd, "http.thread_cpu") == 1)
        table = httpd.store.obs.phases
        before = table.snapshot()
        cpu0 = before["cpu"]
        assert set(cpu0) == set(CPU_LIVE) | {"process_s", "exited_s"}
        for i in range(200):
            assert b" 200 " in _edit(addr, f"b{i % 4}")
        assert _wait_for(lambda: _count(httpd, "http.thread_cpu") == 201)
        after = table.snapshot()
        cpu1 = after["cpu"]
        for cpu in (cpu0, cpu1):
            live = sum(cpu[k] for k in CPU_LIVE)
            assert live + cpu["exited_s"] == pytest.approx(
                cpu["process_s"], rel=0.02)
        assert all(cpu1[k] >= cpu0[k] for k in CPU_LIVE)
        # what the 200 connections' threads burnt is in the resident
        # workers' seconds, or, where the threads came and went, in no
        # live thread's; `http.thread_cpu` summed it from inside, a
        # connection at a time (ticks of 10 ms on the one side)
        inside = after["phases"]["http.thread_cpu"]["sum_s"] \
            - before["phases"]["http.thread_cpu"]["sum_s"]
        burnt = cpu1[burnt_in] - cpu0[burnt_in]
        assert inside > 0.02
        assert 0.5 * inside - 0.03 <= burnt <= 2.0 * inside + 0.1
        if threads == "born":
            assert cpu1["http_workers_s"] == 0.0
        assert (httpd.pooled, httpd.born) == (
            (201, 0) if threads == "resident" else (0, 201))
        # the same block through the scheduler's export
        assert set(httpd.store.scheduler.metrics_json()["phases"]["cpu"]) \
            == set(cpu0)
    finally:
        if threads == "born":
            over.set()
            loop.join(timeout=10)
            assert httpd._workers is None
            httpd.server_close()
        else:
            _stop(httpd)


def test_the_servers_threads_are_filed_under_their_class(tmp_path):
    if not os.path.isdir("/proc/self/task"):
        pytest.skip("no /proc here")
    httpd, addr = _serve(data_dir=str(tmp_path))
    spin, spun = threading.Event(), threading.Event()

    def service_actions():      # on the watch's thread, once a tick
        if spin.is_set():
            spin.clear()
            t_end = time.thread_time() + 0.08
            while time.thread_time() < t_end:
                pass
            spun.set()
    httpd.service_actions = service_actions
    try:
        _edit(addr, "k")
        httpd.store.scheduler.drain()
        names = {t.name for t in threading.enumerate()}
        assert {"merge-pump", "autosave", "gil-probe",
                "flush-worker-0"} <= names
        table = httpd.store.obs.phases
        cpu0 = table.snapshot()["cpu"]
        spin.set()
        assert spun.wait(timeout=10)
        cpu1 = table.snapshot()["cpu"]
        assert cpu1["accept_loop_s"] - cpu0["accept_loop_s"] >= 0.05
        assert cpu1["flush_workers_s"] - cpu0["flush_workers_s"] < 0.05
    finally:
        _stop(httpd)
    assert httpd.store.obs.phases._claimed == {}


def test_where_proc_is_not_there_is_no_cpu_block(monkeypatch):
    table = PhaseTable()
    table.observe("x", 0.5)
    real = os.listdir

    def listdir(path="."):
        if str(path).startswith("/proc"):
            raise FileNotFoundError(path)
        return real(path)
    monkeypatch.setattr(os, "listdir", listdir)
    snap = table.snapshot()
    assert "cpu" not in snap and snap["phases"]["x"]["count"] == 1


# ---- the interpreter's queue -------------------------------------------------------

def _named(before, *names):
    """Live threads of those names (or name prefixes) that were not
    there `before`: other tests of this process may have left theirs."""
    return [t.name for t in threading.enumerate()
            if t not in before and t.name.startswith(names)]


def test_a_thread_that_keeps_the_interpreter_raises_gil_wait():
    before = set(threading.enumerate())
    table = PhaseTable()
    table.start_probe()
    table.start_probe()         # one probe a table
    try:
        assert _named(before, "gil-probe") == ["gil-probe"]
        assert _wait_for(
            lambda: table.snapshot()["phases"].get(
                "gil.wait", {}).get("count", 0) >= 3)
        t_end = time.monotonic() + 0.1
        while time.monotonic() < t_end:
            sum(range(2_000_000))   # one call: the interpreter is kept
        assert _wait_for(
            lambda: table.snapshot()["phases"]["gil.wait"]["max_s"] > 0.003)
        row = table.snapshot()["phases"]["gil.wait"]
        assert row["sum_s"] >= row["max_s"] > 0.003
    finally:
        table.stop_probe()
    assert _named(before, "gil-probe") == []
    n = table.snapshot()["phases"]["gil.wait"]["count"]
    time.sleep(0.06)
    assert table.snapshot()["phases"]["gil.wait"]["count"] == n


def test_the_probe_lives_and_dies_with_the_server():
    before = set(threading.enumerate())
    httpd, addr = _serve()
    try:
        assert _named(before, "gil-probe") == ["gil-probe"]
        assert _wait_for(lambda: _count(httpd, "gil.wait") >= 2)
    finally:
        _stop(httpd)
    # no thread left behind
    assert _named(before, "gil-probe", "merge-pump", "autosave",
                  "flush-worker-") == []


# ---- the flush worker's pause --------------------------------------------------------

def _scheduler(obs, **kw):
    docs = {}

    def resolve(doc_id):
        if doc_id not in docs:
            ol = docs[doc_id] = OpLog()
            ol.doc_id = doc_id
            ol.add_insert_at(ol.get_or_create_agent_id("a"), [], 0,
                             "hello " + doc_id)
        return docs[doc_id]
    sched = MergeScheduler(1, resolve=resolve, engine="host", **kw)
    if obs is not None:
        sched.attach_obs(obs)
    return sched


def test_a_paced_flush_writes_one_pause_and_a_forced_one_none():
    obs = Observability(sample_rate=0.0)
    sched = _scheduler(obs, flush_docs=1, flush_deadline_s=60.0)

    def flushes():
        return obs.phases.snapshot()["phases"]
    try:
        assert sched.submit("p0")["accepted"]
        assert sched.pump(force=True) == 1      # somebody waits: no pause
        sched._wait_idle()
        assert flushes()["sched.flush"]["counts"] == {"forced": 1}
        assert "sched.pause" not in flushes()
        for i in range(1, 4):                   # the worker's own: paced
            assert sched.submit(f"p{i}")["accepted"]
            assert sched.pump() == 1
            sched._wait_idle()
            assert _wait_for(
                lambda: flushes().get("sched.pause", {}).get("count") == i)
        ph = flushes()
        assert ph["sched.flush"]["counts"] == {"forced": 1, "paced": 3}
        assert ph["sched.flush"]["count"] == 4
        # seven parts out for one part in: a root of its own, written
        # when the pause is over
        assert 0.0 < ph["sched.pause"]["sum_s"] < 3.0
        assert ph["sched.pause"]["max_s"] <= ph["sched.pause"]["sum_s"]
        # a host engine's read is the oplog's: it flushes nothing (a
        # device session's sync, on the reader's thread, is `inline`:
        # tests/test_device_reads.py)
        assert sched.submit("p9")["accepted"]
        assert sched.text("p9") == "hello p9"
        ph = flushes()
        assert ph["sched.flush"]["counts"] == {"forced": 1, "paced": 3}
        assert ph["sched.pause"]["count"] == 3
    finally:
        sched.stop_workers()


@pytest.mark.parametrize("reason,wall_s,device_s,pauses", [
    ("size", 0.004, 0.0, 1),            # host work: seven parts out
    ("size", 0.300, 0.270, 0),          # device-bound: no pause, no row
    ("size", 0.010, 0.070, 0),          # the pause comes out at 0
    ("force", 0.004, 0.0, 0),           # somebody waits for it
])
def test_a_pause_is_written_only_where_the_worker_sat_out(
        reason, wall_s, device_s, pauses):
    assert sched_mod.FLUSH_HOST_SHARE == 8
    obs = Observability(sample_rate=0.0)
    sched = _scheduler(obs)
    sched._flush_items = lambda shard, why, items: (wall_s, device_s)
    try:
        sched._dispatch(0, reason, ["item"])
        sched._wait_idle()
    finally:
        sched.stop_workers()
    row = obs.phases.snapshot()["phases"].get("sched.pause")
    if not pauses:
        assert row is None
    else:
        assert row["count"] == 1
        assert 7 * wall_s - 0.001 <= row["sum_s"] <= 7 * wall_s + 0.5


def test_a_scheduler_with_no_bundle_paces_and_writes_nothing():
    sched = _scheduler(None)
    waits = []

    class Stop:
        def wait(self, timeout):
            waits.append(timeout)
    sched._pump_stop = Stop()
    sched._flush_items = lambda shard, why, items: (0.02, 0.0)
    try:
        sched._dispatch(0, "size", ["item"])
        sched._wait_idle()
    finally:
        sched.stop_workers()
    assert waits == [pytest.approx(0.14)]
    assert "phases" not in sched.metrics_json()


# ---- no bundle ---------------------------------------------------------------------------

def test_a_server_with_no_bundle_writes_none_of_it_and_imports_no_jax():
    code = (
        "import sys, json, threading, urllib.request\n"
        "from diamond_types_tpu.serve.scheduler import MergeScheduler\n"
        "from diamond_types_tpu.tools.server import (DocStore,\n"
        "    SyncHandler, _Server)\n"
        "store = DocStore(None)\n"
        "sched = MergeScheduler(1, resolve=store.get, engine='host',\n"
        "                       sync_lock=store.lock)\n"
        "store.attach_scheduler(sched)\n"
        "sched.start_pump()\n"
        "h = _Server(('127.0.0.1', 0),\n"
        "            type('H', (SyncHandler,), {'store': store}))\n"
        "h.store = store\n"
        "t = threading.Thread(target=h.serve_forever, daemon=True)\n"
        "t.start()\n"
        "base = 'http://127.0.0.1:%d' % h.server_address[1]\n"
        "for i in range(40):\n"
        "    req = urllib.request.Request(base + '/doc/j/edit',\n"
        "        data=json.dumps({'agent': 'a', 'version': None, 'ops': [\n"
        "            {'kind': 'ins', 'pos': 0, 'text': 'x'}]}).encode())\n"
        "    urllib.request.urlopen(req, timeout=10).read()\n"
        "sched.drain()\n"
        "doc = json.loads(urllib.request.urlopen(\n"
        "    base + '/metrics', timeout=10).read())\n"
        "assert 'obs' not in doc and 'phases' not in doc['serve'], doc\n"
        "assert h.accepted_at == {} and h.pooled + h.born == 41\n"
        "names = [t.name for t in threading.enumerate()]\n"
        "assert 'gil-probe' not in names, names\n"
        "assert 'merge-pump' in names, names\n"
        "h.shutdown(); h.server_close(); t.join(timeout=10)\n"
        "assert not t.is_alive()\n"
        "assert 'jax' not in sys.modules\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
