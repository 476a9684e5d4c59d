"""Resident handler threads (tools/server.py `_Server`): a connection
is handed to a parked `http-worker-<n>` where one is parked and given
a thread of its own where none is, `pooled` / `born` count which, and
the clocks of a push outside its handler (`http.thread_start`,
`http.thread_cpu`, the `cpu` block) keep their meanings on a thread
that outlives its connection. Every wait in here has a time limit of
its own.
"""

from __future__ import annotations

import inspect
import json
import os
import socket
import sys
import threading
import time

import pytest

from diamond_types_tpu.tools import server as server_mod
from test_push_path_clocks import _answer, _edit, _stop, _wait_for

pytestmark = pytest.mark.obs

WORKER = "http-worker-"


def _serve(**kw):
    kw.setdefault("obs_opts", {"sample_rate": 0.0})
    httpd = server_mod.serve(port=0, engine="host", serve_shards=1, **kw)
    addr = ("127.0.0.1", httpd.server_address[1])
    threading.Thread(target=httpd.serve_forever, args=(0.02,),
                     daemon=True).start()
    return httpd, addr


def _post(doc: str, action: str, req: dict) -> bytes:
    body = json.dumps(req).encode("utf8")
    return (f"POST /doc/{doc}/{action} HTTP/1.1\r\nHost: t\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode("ascii") + body


def _get_bytes(path: str) -> bytes:
    return f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode("ascii")


def _send(addr, data: bytes, rcvbuf=None):
    s = socket.socket()
    s.settimeout(20)
    if rcvbuf is not None:      # before the handshake fixes the window
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    s.connect(addr)
    s.sendall(data)
    return s


def _body(answer: bytes) -> dict:
    return json.loads(answer.split(b"\r\n\r\n", 1)[1])


def _all_parked(httpd):
    """Every worker is back from its connection: it puts its token
    after the close, which the client can see before it."""
    return _wait_for(
        lambda: len(httpd._parked) == server_mod.HANDLER_THREADS)


def _counts(httpd) -> dict:
    return httpd.store.obs.phases.snapshot()["phases"].get(
        "http.accept_wait", {}).get("counts", {})


def _row(httpd, name: str) -> dict:
    return httpd.store.obs.phases.snapshot()["phases"].get(
        name, {"count": 0, "sum_s": 0.0})


def _served_by(httpd) -> list:
    """Names of the threads that ran a handler, in order."""
    names, finish = [], httpd.finish_request

    def finish_request(request, client_address):
        names.append(threading.current_thread().name)
        finish(request, client_address)
    httpd.finish_request = finish_request
    return names


def _route(httpd, path: str, fn) -> None:
    """`GET <path>` runs `fn(handler)` on the handler's thread."""
    handler, do_get = httpd.RequestHandlerClass, \
        httpd.RequestHandlerClass.do_GET

    def do_GET(self):
        if self.path == path:
            return fn(self)
        return do_get(self)
    handler.do_GET = do_GET


def _spin(seconds: float) -> None:
    t_end = time.thread_time() + seconds
    while time.thread_time() < t_end:
        pass


# ---- who serves a connection -------------------------------------------------------

def test_sequential_requests_are_served_by_the_resident_workers():
    httpd, addr = _serve()
    names = _served_by(httpd)
    try:
        for i in range(40):
            if i % 5:
                assert b" 200 " in _edit(addr, f"s{i % 3}")
            else:
                assert b" 200 " in _answer(_send(addr, _get_bytes("/metrics")))
        assert len(names) == 40
        assert all(n.startswith(WORKER) for n in names), set(names)
        assert 1 <= len(set(names)) <= server_mod.HANDLER_THREADS
        assert (httpd.pooled, httpd.born) == (40, 0)
        workers = {t.name for t in threading.enumerate()
                   if t.name.startswith(WORKER)}
        assert workers >= {f"{WORKER}{i}"
                           for i in range(server_mod.HANDLER_THREADS)}
        assert _all_parked(httpd)
        assert httpd.accepted_at == {}
    finally:
        _stop(httpd)


def _hold_long_poll(addr, version):
    return _send(addr, _post("p", "changes",
                             {"version": version, "wait": 30}))


def _hold_silent(addr, version):
    return _send(addr, b"")


def _hold_slow_reader(addr, version):
    # asks for a large document and never reads: its handler blocks in
    # `sendall` once the socket buffers are full
    return _send(addr, _get_bytes("/doc/big"), rcvbuf=4096)


@pytest.mark.parametrize("hold", [_hold_long_poll, _hold_silent,
                                  _hold_slow_reader])
def test_a_parked_pool_gives_the_next_connection_a_thread_of_its_own(hold):
    """The inputs that would starve a bounded pool: each holds its
    thread. With every worker inside such a connection an edit is
    still answered at once, by a born thread."""
    n = server_mod.HANDLER_THREADS
    httpd, addr = _serve()
    names = _served_by(httpd)
    held = []
    try:
        version = _body(_edit(addr, "p", "base"))["version"]
        if hold is _hold_slow_reader:
            assert b" 200 " in _edit(addr, "big", "0123456789abcdef" * (1 << 16))
            get_request = httpd.get_request

            def small_buffers():    # a megabyte does not fit the socket
                request, client_address = get_request()
                request.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
                return request, client_address
            httpd.get_request = small_buffers
        assert _all_parked(httpd)
        del names[:]
        for _ in range(n):
            held.append(hold(addr, version))
        assert _wait_for(lambda: len(names) == n)
        assert all(x.startswith(WORKER) for x in names)
        assert _wait_for(lambda: len(httpd._parked) == 0)
        assert b" 200 " in _edit(addr, "p", "wake")
        assert not names[-1].startswith(WORKER)
        assert httpd.born == 1
        if hold is not _hold_long_poll:     # those the edit has woken
            assert len(httpd._parked) == 0  # the holders still hold
    finally:
        for s in held:
            s.close()
        _stop(httpd)


def test_parked_long_polls_and_silent_clients_then_an_edit_wakes_the_polls():
    n = server_mod.HANDLER_THREADS
    httpd, addr = _serve()
    polls, silent = [], []
    try:
        version = _body(_edit(addr, "p", "base"))["version"]
        assert _all_parked(httpd)
        for _ in range(n):
            polls.append(_hold_long_poll(addr, version))
        assert _wait_for(lambda: httpd.pooled == 1 + n)
        for _ in range(n):
            silent.append(_hold_silent(addr, None))
        assert _wait_for(lambda: httpd.born == n)
        t0 = time.monotonic()
        assert b" 200 " in _edit(addr, "p", "wake")
        assert time.monotonic() - t0 < 5.0
        assert httpd.born == n + 1
        for s in polls:
            out = _body(_answer(s))
            assert out["op"] and "wake" in json.dumps(out["op"])
        assert time.monotonic() - t0 < 10.0     # woken, not timed out
        polls = []
        assert _all_parked(httpd)               # the pollers' workers
    finally:
        for s in polls + silent:
            s.close()
        _stop(httpd)


@pytest.mark.parametrize("fails", ["handler", "handle_error"])
def test_a_handler_that_raises_leaves_its_worker_alive_and_serving(
        monkeypatch, capfd, fails):
    monkeypatch.setattr(server_mod, "HANDLER_THREADS", 1)
    httpd, addr = _serve()
    names = _served_by(httpd)

    def boom(handler):
        raise RuntimeError("boom in a handler")
    _route(httpd, "/boom", boom)
    if fails == "handle_error":     # the server's own last resort fails
        def handle_error(request, client_address):
            raise OSError("no stderr to write to")
        httpd.handle_error = handle_error
    try:
        assert b" 200 " in _edit(addr, "r")
        assert _all_parked(httpd)
        assert _answer(_send(addr, _get_bytes("/boom"))) == b""
        assert _all_parked(httpd)
        assert b" 200 " in _edit(addr, "r")
        assert names == [WORKER + "0"] * 3
        assert [t.is_alive() for t in httpd._workers] == [True]
        assert (httpd.pooled, httpd.born) == (3, 0)
        assert httpd.accepted_at == {}
        err = capfd.readouterr().err
        assert ("no stderr" if fails == "handle_error" else "boom") in err
    finally:
        _stop(httpd)


# ---- the pool's life -----------------------------------------------------------------

def test_a_server_that_never_served_started_none():
    before = set(threading.enumerate())
    httpd = server_mod.serve(port=0, engine="host", serve_shards=1)
    try:
        assert httpd._workers is None
        assert not [t for t in set(threading.enumerate()) - before
                    if t.name.startswith(WORKER)]
    finally:
        httpd.server_close()
    assert httpd._workers == ()
    assert "pooled" not in _counts(httpd) and "born" not in _counts(httpd)


def test_server_close_leaves_no_worker_alive():
    httpd, addr = _serve()
    try:
        for _ in range(6):
            assert b" 200 " in _edit(addr, "c")
        workers = list(httpd._workers)
        assert len(workers) == server_mod.HANDLER_THREADS
        assert all(w.is_alive() and w.daemon for w in workers)
    finally:
        _stop(httpd)
    assert not any(w.is_alive() for w in workers)
    assert httpd._workers == () and not httpd._parked
    # closed for good: a late connection finds nobody parked
    a, b = socket.socketpair()
    try:
        httpd.process_request(a, ("127.0.0.1", 0))
        assert httpd.born == 1 and httpd._workers == ()
        b.close()
    finally:
        a.close()


def test_twenty_servers_opened_and_closed_in_turn_leak_no_thread():
    def threads():
        return {t for t in threading.enumerate() if t.is_alive()}
    before = threads()
    for i in range(20):
        httpd, addr = _serve()
        try:
            assert b" 200 " in _edit(addr, f"l{i}")
            assert b" 200 " in _edit(addr, f"l{i}")
        finally:
            _stop(httpd)
    assert _wait_for(lambda: threads() <= before), \
        sorted(t.name for t in threads() - before)


def test_a_worker_inside_a_connection_at_close_ends_with_it(monkeypatch):
    """`server_close()` takes no connection off a worker and does not
    wait for it past `WORKERS_JOIN_S`: the worker ends when its client
    does, as a thread born for it would."""
    monkeypatch.setattr(server_mod, "WORKERS_JOIN_S", 0.3)
    httpd, addr = _serve()
    quiet = None
    try:
        assert b" 200 " in _edit(addr, "w")
        assert _all_parked(httpd)
        quiet = _hold_silent(addr, None)
        assert _wait_for(lambda: len(httpd._parked)
                         == server_mod.HANDLER_THREADS - 1)
        workers = list(httpd._workers)
    finally:
        t0 = time.monotonic()
        _stop(httpd)
        closed_in = time.monotonic() - t0
    try:
        assert closed_in < 5.0
        assert sum(w.is_alive() for w in workers) == 1
    finally:
        quiet.close()
    assert _wait_for(lambda: not any(w.is_alive() for w in workers))


def test_no_option_reaches_the_pools_size():
    assert server_mod.HANDLER_THREADS >= 1
    names = set(inspect.signature(server_mod.serve).parameters)
    assert not [n for n in names if "thread" in n or "pool" in n]
    with open(inspect.getsourcefile(server_mod), encoding="utf8") as f:
        src = f.read()
    assert not [flag for flag in ("--handler", "--threads", "--pool")
                if flag in src]
    assert "environ" not in inspect.getsource(server_mod._Server)


# ---- PR 38's rows on a thread that outlives its connection ------------------

def test_thread_cpu_on_a_worker_is_its_connections_not_its_lifes(
        monkeypatch):
    monkeypatch.setattr(server_mod, "HANDLER_THREADS", 1)
    monkeypatch.setattr(server_mod, "CLOCKED_EVERY", 1)
    httpd, addr = _serve()
    names = _served_by(httpd)
    life = []

    def burn(handler):
        _spin(0.15)
        handler._send(200, b"{}")

    def light(handler):
        life.append(time.thread_time())
        handler._send(200, b"{}")
    _route(httpd, "/burn", burn)
    _route(httpd, "/light", light)
    try:
        assert b" 200 " in _answer(_send(addr, _get_bytes("/burn")))
        assert _wait_for(lambda: _row(httpd, "http.thread_cpu")["count"] == 1)
        first = _row(httpd, "http.thread_cpu")["sum_s"]
        assert 0.14 <= first < 1.0
        assert _all_parked(httpd)
        assert b" 200 " in _answer(_send(addr, _get_bytes("/light")))
        assert _wait_for(lambda: _row(httpd, "http.thread_cpu")["count"] == 2)
        second = _row(httpd, "http.thread_cpu")["sum_s"] - first
        # the same thread, whose life holds the first connection's CPU
        assert names == [WORKER + "0"] * 2 and life[0] >= 0.14
        assert 0.0 <= second < 0.1
        # a parked worker's wake, not its age
        start = _row(httpd, "http.thread_start")
        assert start["count"] == 2 and 0.0 < start["sum_s"] < 1.0
        assert httpd.accepted_at == {}
    finally:
        _stop(httpd)


def test_pooled_and_born_ride_with_the_listen_sample_and_add_up_at_close():
    every = server_mod.LISTEN_SAMPLE_EVERY
    httpd, addr = _serve()
    try:
        for i in range(every + 3):
            assert b" 200 " in _edit(addr, f"f{i % 2}")
        # folded by the sample at the 32nd accept, before its own count
        got = _counts(httpd)
        assert got["pooled"] == every - 1 and "born" not in got
        assert got["listen_samples"] == 1
        assert _all_parked(httpd)
        held = [_hold_silent(addr, None)
                for _ in range(server_mod.HANDLER_THREADS + 2)]
        assert _wait_for(lambda: httpd.born == 2)
        for s in held:
            s.close()
    finally:
        _stop(httpd)
    got = _counts(httpd)
    accepts = every + 3 + server_mod.HANDLER_THREADS + 2
    assert httpd._accepts == accepts
    assert (got["pooled"], got["born"]) == (accepts - 2, 2)
    assert (httpd.pooled, httpd.born) == (accepts - 2, 2)
    assert httpd.accepted_at == {}
    # a second close folds nothing twice
    httpd._stop_workers()
    assert httpd._unfolded() == {}


def test_the_cpu_block_files_the_workers_under_their_class():
    if not os.path.isdir("/proc/self/task"):
        pytest.skip("no /proc here")
    httpd, addr = _serve()
    _route(httpd, "/burn", lambda h: (_spin(0.08), h._send(200, b"{}")))
    try:
        assert b" 200 " in _edit(addr, "k")
        table = httpd.store.obs.phases
        cpu0 = table.snapshot()["cpu"]
        assert "http_workers_s" in cpu0
        assert b" 200 " in _answer(_send(addr, _get_bytes("/burn")))
        cpu1 = table.snapshot()["cpu"]
        assert cpu1["http_workers_s"] - cpu0["http_workers_s"] >= 0.05
        assert cpu1["live_handlers_s"] - cpu0["live_handlers_s"] < 0.05
        for cpu in (cpu0, cpu1):
            live = sum(v for k, v in cpu.items()
                       if k not in ("process_s", "exited_s"))
            assert live + cpu["exited_s"] == pytest.approx(
                cpu["process_s"], rel=0.02)
    finally:
        _stop(httpd)


# ---- many clients at once ---------------------------------------------------------------

def test_a_crowd_loses_no_connection_and_no_token():
    """More clients than cores and a short switch interval: every push
    is answered, every connection counted once, and afterwards every
    worker is parked with exactly one token."""
    clients, pushes = 24, 25
    httpd, addr = _serve()
    failed = []

    def client(k):
        try:
            for _ in range(pushes):
                if b" 200 " not in _edit(addr, f"crowd{k}"):
                    failed.append(k)
        except OSError as e:
            failed.append((k, repr(e)))
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=client, args=(k,), daemon=True)
                   for k in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        assert failed == []
        assert httpd.pooled + httpd.born == httpd._accepts \
            == clients * pushes
        assert httpd.pooled > 0
        assert _all_parked(httpd)
        time.sleep(0.05)
        assert len(httpd._parked) == server_mod.HANDLER_THREADS
        assert httpd._handoff.empty()
        assert httpd.accepted_at == {}
        assert _row(httpd, "http.edit")["count"] == clients * pushes
    finally:
        sys.setswitchinterval(was)
        _stop(httpd)
    got = _counts(httpd)
    assert got["pooled"] + got.get("born", 0) == clients * pushes
