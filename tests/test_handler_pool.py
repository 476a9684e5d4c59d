"""Resident handler threads (tools/server.py `_Server`): an
`http-worker-<n>` that is back from a connection takes its next one
from the listening socket itself (`accept()` first; its turn at the
socket only to wait where nothing is queued), the `serve_forever`
thread only watches (a worker that one connection holds for longer than a tick is
replaced and ends with it), `pooled` / `born` count which thread a
connection met, and the clocks of a push outside its handler
(`http.thread_start`, `http.thread_cpu`, the `cpu` block) keep their
meanings on a thread that outlives its connection. Every wait in here
has a time limit of its own.
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
    httpd.made = made = []      # every worker this server ever started

    new_worker = httpd._new_worker

    def _new_worker(slot, born):    # the server's own, listed as well
        made.append(new_worker(slot, born))
        return made[-1]
    httpd._new_worker = _new_worker
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    assert _wait_for(lambda: httpd._workers is not None)
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


def _alive(httpd) -> list:
    """The server's resident handler threads, retired ones that still
    hold a connection among them."""
    return [w for w in httpd.made if w.is_alive()]


def _inside(httpd) -> int:
    """How many of the pool's workers are inside a connection."""
    return sum(w.since is not None for w in httpd._workers or ())


def _all_back(httpd):
    """The whole pool is back from its connections (a worker says so
    after the close, which the client can see before it), and nobody
    else is left: one worker waits at the socket, turn in hand, the
    others wait for the turn."""
    n = server_mod.HANDLER_THREADS
    return _wait_for(lambda: len(httpd._workers or ()) == n
                     and _inside(httpd) == 0 and len(_alive(httpd)) == n
                     and httpd._turn.locked())


@pytest.fixture
def no_watch(monkeypatch):
    """A tick no stall of a loaded machine reaches: nobody is replaced."""
    monkeypatch.setattr(server_mod, "WATCH_TICK_S", 30.0)


@pytest.fixture
def tick(monkeypatch):
    """A short tick of the watch: a held worker is replaced after one
    to two of these."""
    monkeypatch.setattr(server_mod, "WATCH_TICK_S", 0.2)
    return 0.2


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

def test_sequential_requests_are_served_by_the_resident_workers(no_watch):
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
        assert _all_back(httpd)
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
def test_a_held_pool_gives_the_next_connection_a_thread_of_its_own(
        hold, monkeypatch):
    """The inputs that would starve a bounded pool: each holds its
    thread. With every worker inside such a connection an edit is
    still answered by a replacement, and the holders keep their
    threads: behind long-polls at once (a poll that starts to wait
    leaves the pool itself: no tick is short enough to help here),
    behind clients that say nothing of themselves within two ticks."""
    n = server_mod.HANDLER_THREADS
    tick = 30.0 if hold is _hold_long_poll else 0.2
    monkeypatch.setattr(server_mod, "WATCH_TICK_S", tick)
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
        assert _all_back(httpd)
        del names[:]
        for _ in range(n):
            held.append(hold(addr, version))
        assert _wait_for(lambda: len(names) == n)
        holders = [w for w in httpd.made if w.name in names]
        assert len(holders) == n and all(w.is_alive() for w in holders)
        if hold is _hold_long_poll:     # each has said that it waits
            assert _wait_for(lambda: len(httpd.made) == 2 * n)
        t0 = time.monotonic()
        assert b" 200 " in _edit(addr, "p", "wake")
        waited = time.monotonic() - t0
        assert names[-1].startswith(WORKER) and names[-1] not in names[:n]
        assert len(httpd._workers) == n
        if hold is _hold_long_poll:
            # the first connection of a replacement, which stood at the
            # socket already (a later poll may have been another's
            # first); nobody was born for the edit: a birth a poll
            assert waited < 2.0     # (the tick is 30 s here)
            assert 1 <= httpd.born <= n and len(httpd.made) == 2 * n
            assert all(w.retired for w in holders)
        else:
            # (the slow readers first check a megabyte out under the
            # store lock, one after the other, and the edit queues
            # behind them; on a loaded machine the watch may have begun
            # before the last holder came: more births than one)
            assert waited < (20.0 if hold is _hold_slow_reader
                             else 2 * tick + 1.0)
            assert httpd.born >= 1 and len(httpd.made) > n
            # (a round of the watch takes those a tick old, so maybe
            # not all at once)
            assert _wait_for(lambda: not set(holders) & set(httpd._workers))
            assert all(w.retired and w.is_alive() for w in holders)
    finally:
        for s in held:
            s.close()
        _stop(httpd)


def test_edits_behind_more_tabs_than_workers_never_wait_for_the_watch(
        monkeypatch):
    """The browser client's traffic (`tools/web_assets.py`): a
    `changes` long-poll a tab, asked again at once after every wake.
    With more tabs on a document than the pool has workers, and no
    watch to speak of, every edit is answered within a thread's birth,
    round after round; a poll that is answered at once (it had
    something to read) costs no thread; and when the tabs close the
    process is back to `HANDLER_THREADS` residents."""
    monkeypatch.setattr(server_mod, "WATCH_TICK_S", 30.0)
    n = server_mod.HANDLER_THREADS
    tabs = 2 * n + 1
    httpd, addr = _serve()
    polls = []
    try:
        version = _body(_edit(addr, "p", "base"))["version"]
        for k in range(3):
            polls = [_hold_long_poll(addr, version) for _ in range(tabs)]
            # every poll waits on a thread of its own, the pool is whole
            assert _wait_for(lambda: len(_alive(httpd)) == n + tabs
                             and _inside(httpd) == 0)
            assert len(httpd.made) == n + (k + 1) * tabs
            t0 = time.monotonic()
            assert b" 200 " in _edit(addr, "p", f"round{k}")
            assert time.monotonic() - t0 < 2.0     # (the tick: 30 s)
            for s in polls:
                out = _body(_answer(s))
                assert f"round{k}" in json.dumps(out["op"])
            polls = []
            # a tab that is behind is answered at once, by the pool
            made = len(httpd.made)
            assert _body(_answer(_hold_long_poll(addr, version)))["op"]
            assert len(httpd.made) == made
            version = out["version"]    # where a tab asks from next
        assert _all_back(httpd)
        assert httpd.pooled + httpd.born == 1 + 3 * (tabs + 2)
    finally:
        for s in polls:
            s.close()
        _stop(httpd)


def test_held_long_polls_and_silent_clients_then_an_edit_wakes_the_polls(
        tick):
    n = server_mod.HANDLER_THREADS
    httpd, addr = _serve()
    polls, silent = [], []
    try:
        version = _body(_edit(addr, "p", "base"))["version"]
        assert _all_back(httpd)
        def served():
            return httpd.pooled + httpd.born
        for _ in range(n):
            polls.append(_hold_long_poll(addr, version))
        assert _wait_for(lambda: served() == 1 + n)
        for _ in range(n):
            silent.append(_hold_silent(addr, None))
        # each silent client is the first connection of a replacement
        # (and on a loaded machine a late poll may have been one too)
        assert _wait_for(lambda: served() == 1 + 2 * n)
        assert httpd.born >= n
        t0 = time.monotonic()
        assert b" 200 " in _edit(addr, "p", "wake")
        assert time.monotonic() - t0 < 5.0
        assert httpd.born >= n + 1 and served() == 2 + 2 * n
        for s in polls:
            out = _body(_answer(s))
            assert out["op"] and "wake" in json.dumps(out["op"])
        assert time.monotonic() - t0 < 10.0     # woken, not timed out
        polls = []
        # the pollers' threads are gone, the silent clients' are not
        assert _wait_for(lambda: len(_alive(httpd)) == 2 * n)
        assert served() == 2 + 2 * n
    finally:
        for s in polls + silent:
            s.close()
        _stop(httpd)


def test_with_every_worker_held_an_edit_waits_two_ticks_at_most_and_the_pool_comes_back(
        tick):
    """Long-polls and silent clients on every worker: an edit is
    answered within two ticks (a thread's birth and this machine's
    load on top), and once the holders have gone the process is back
    to `HANDLER_THREADS` residents, all of them cycling."""
    n = server_mod.HANDLER_THREADS
    httpd, addr = _serve()
    names = _served_by(httpd)
    held = []
    try:
        version = _body(_edit(addr, "p", "base"))["version"]
        assert _all_back(httpd)
        for k in range(n):
            held.append((_hold_silent if k % 2 else _hold_long_poll)(
                addr, version))
        assert _wait_for(lambda: len(names) == 1 + n)
        t0 = time.monotonic()
        assert b" 200 " in _edit(addr, "other", "x")
        waited = time.monotonic() - t0
        assert waited < 2 * tick + 1.0
        assert httpd.born >= 1
        # no birth while workers cycle: once every holder is replaced
        # the next twenty meet the replacements, whatever the holders
        # do (`born` is the first connection of each replacement that
        # got one)
        assert _wait_for(lambda: len(httpd.made) >= 2 * n
                         and _inside(httpd) == 0)
        made = len(httpd.made)
        for _ in range(20):
            assert b" 200 " in _edit(addr, "other", "y")
        assert 1 <= httpd.born <= made - n
        assert httpd.pooled + httpd.born == 22 + n
        assert len(httpd.made) == len(_alive(httpd)) == made
        assert b" 200 " in _edit(addr, "p", "wake")     # ends the polls
        for s in held:
            s.close()
        held = []
        assert _all_back(httpd)
        assert {t.name for t in _alive(httpd)} == {w.name for w in httpd._workers}
        assert names[-1] in {w.name for w in httpd._workers}
    finally:
        for s in held:
            s.close()
        _stop(httpd)


@pytest.mark.parametrize("fails", ["handler", "handle_error"])
def test_a_handler_that_raises_leaves_its_worker_alive_and_serving(
        monkeypatch, capfd, fails, no_watch):
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
        assert _all_back(httpd)
        assert _answer(_send(addr, _get_bytes("/boom"))) == b""
        assert _all_back(httpd)
        assert b" 200 " in _edit(addr, "r")
        assert names == [WORKER + "0"] * 3
        assert [t.is_alive() for t in httpd._workers] == [True]
        assert (httpd.pooled, httpd.born) == (3, 0)
        assert not httpd._workers[0].retired
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
    assert httpd._workers == () and httpd._wake is None
    assert not httpd._turn.locked()
    # closed for good: serving again starts nobody, and a connection on
    # the stdlib's road (`handle_request()`) gets a born thread
    httpd._start_workers()
    assert httpd._workers == () and not _alive(httpd)
    a, b = socket.socketpair()
    try:
        httpd.process_request(a, ("127.0.0.1", 0))
        assert httpd.born == 1 and httpd._workers == ()
        b.close()
    finally:
        a.close()


def test_a_worker_at_the_socket_ends_at_close_at_once(monkeypatch):
    """No connection comes to end the wait: the wake-up does, on a
    socket that is still open (closing a descriptor another thread
    waits on is not relied upon), and `shutdown()` alone is enough to
    stop the accepting."""
    monkeypatch.setattr(server_mod, "WORKERS_JOIN_S", 30.0)
    for how in ("close", "shutdown"):
        httpd, addr = _serve()
        assert _all_back(httpd)
        workers = list(httpd._workers)
        t0 = time.monotonic()
        if how == "close":
            httpd.server_close()
        else:
            httpd.shutdown()
            assert httpd.socket.fileno() >= 0
        assert time.monotonic() - t0 < 5.0      # not the join's limit
        assert not any(w.is_alive() for w in workers)
        if how == "shutdown":
            # nobody accepts: the connection lies in the kernel's queue
            s = _send(addr, _get_bytes("/metrics"))
            s.settimeout(0.3)
            with pytest.raises(socket.timeout):
                s.recv(1)
            s.close()
            assert httpd._workers is None
        _stop(httpd)
        assert not _alive(httpd)


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
        assert _all_back(httpd)
        quiet = _hold_silent(addr, None)
        assert _wait_for(lambda: _inside(httpd) == 1)
        workers = _alive(httpd)
    finally:
        t0 = time.monotonic()
        _stop(httpd)
        closed_in = time.monotonic() - t0
    try:
        assert 0.3 <= closed_in < 5.0
        assert sum(w.is_alive() for w in workers) == 1
    finally:
        quiet.close()
    assert _wait_for(lambda: not any(w.is_alive() for w in workers))


def test_no_option_reaches_the_pools_size():
    assert server_mod.HANDLER_THREADS >= 1
    names = set(inspect.signature(server_mod.serve).parameters)
    assert not [n for n in names
                if "thread" in n or "pool" in n or "tick" in n]
    with open(inspect.getsourcefile(server_mod), encoding="utf8") as f:
        src = f.read()
    assert not [flag for flag in ("--handler", "--threads", "--pool",
                                  "--tick", "--watch")
                if flag in src]
    assert "environ" not in inspect.getsource(server_mod._Server)


# ---- PR 38's rows on a thread that outlives its connection ------------------

def test_thread_cpu_on_a_worker_is_its_connections_not_its_lifes(
        monkeypatch, no_watch):
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
        assert _all_back(httpd)
        assert b" 200 " in _answer(_send(addr, _get_bytes("/light")))
        assert _wait_for(lambda: _row(httpd, "http.thread_cpu")["count"] == 2)
        second = _row(httpd, "http.thread_cpu")["sum_s"] - first
        # the same thread, whose life holds the first connection's CPU
        assert names == [WORKER + "0"] * 2 and life[0] >= 0.14
        assert 0.0 <= second < 0.1
        # from its own `accept()` to its next line, not its age
        start = _row(httpd, "http.thread_start")
        assert start["count"] == 2 and 0.0 < start["sum_s"] < 0.5
        assert httpd.accepted_at == {}
    finally:
        _stop(httpd)


def test_pooled_and_born_ride_with_the_listen_sample_and_add_up_at_close(
        tick, monkeypatch):
    # (a worker numbers the connections of its own place: one place)
    monkeypatch.setattr(server_mod, "HANDLER_THREADS", 1)
    every = server_mod.LISTEN_SAMPLE_EVERY
    httpd, addr = _serve()
    try:
        for i in range(every + 3):
            assert b" 200 " in _edit(addr, f"f{i % 2}")
        # folded by the sample at the 32nd accept, its own count in it
        got = _counts(httpd)
        assert got["pooled"] == every and "born" not in got
        assert got["listen_samples"] == 1
        # sequential clients: a worker waited at the socket for most
        # (one that is just back may find the next queued already)
        assert 0 < got["accept_waited"] <= every + 4
        assert _all_back(httpd)
        held = [_hold_silent(addr, None)
                for _ in range(server_mod.HANDLER_THREADS + 2)]
        accepts = every + 3 + server_mod.HANDLER_THREADS + 2
        assert _wait_for(lambda: httpd.pooled + httpd.born == accepts)
        assert httpd.born >= 2      # the two that found every worker held
        for s in held:
            s.close()
    finally:
        _stop(httpd)
    got = _counts(httpd)
    assert (got["pooled"], got["born"]) == (httpd.pooled, httpd.born)
    assert got["pooled"] + got["born"] == accepts
    assert got["accept_waited"] == httpd.accept_waited > 0
    assert httpd.accepted_at == {}
    # a second close folds nothing twice
    httpd._stop_workers(closed=True)
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


def test_a_replaced_workers_cpu_stays_in_its_class(monkeypatch):
    """A worker that the watch replaced ends with its connection; what
    it burnt stays in `http_workers_s`, which never runs backwards
    between two scrapes, and does not turn up in `exited_s`."""
    if not os.path.isdir("/proc/self/task"):
        pytest.skip("no /proc here")
    monkeypatch.setattr(server_mod, "HANDLER_THREADS", 1)
    monkeypatch.setattr(server_mod, "WATCH_TICK_S", 0.05)
    httpd, addr = _serve()
    _route(httpd, "/burn", lambda h: (_spin(0.3), h._send(200, b"{}")))
    try:
        assert b" 200 " in _edit(addr, "k")
        table = httpd.store.obs.phases
        cpu0 = table.snapshot()["cpu"]
        first = httpd._workers[0]
        assert b" 200 " in _answer(_send(addr, _get_bytes("/burn")))
        assert _wait_for(lambda: not first.is_alive())
        assert first.retired and httpd._workers[0] is not first
        cpu1 = table.snapshot()["cpu"]
        assert cpu1["http_workers_s"] - cpu0["http_workers_s"] >= 0.25
        assert cpu1["exited_s"] - cpu0["exited_s"] < 0.1
        live = sum(v for k, v in cpu1.items()
                   if k not in ("process_s", "exited_s"))
        assert live + cpu1["exited_s"] == pytest.approx(
            cpu1["process_s"], rel=0.03)
    finally:
        _stop(httpd)


# ---- many clients at once ---------------------------------------------------------------

def _crowd(addr, clients: int, pushes: int, path=None) -> list:
    """`clients` threads, `pushes` requests each, a connection a
    request; what failed."""
    failed = []

    def client(k):
        try:
            for i in range(pushes):
                if path is not None and i % 10 == 9:
                    ok = b" 200 " in _answer(_send(addr, _get_bytes(path)))
                else:
                    ok = b" 200 " in _edit(addr, f"crowd{k}")
                if not ok:
                    failed.append(k)
        except OSError as e:
            failed.append((k, repr(e)))
    threads = [threading.Thread(target=client, args=(k,), daemon=True)
               for k in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    return failed


@pytest.fixture
def short_switches():
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    yield
    sys.setswitchinterval(was)


def test_a_crowd_loses_no_connection_and_no_worker(short_switches):
    """More clients than cores and a short switch interval: every push
    is answered, every connection counted once, and afterwards the
    pool is whole, one worker at the socket."""
    clients, pushes = 24, 25
    httpd, addr = _serve()
    try:
        assert _crowd(addr, clients, pushes) == []
        assert httpd.pooled + httpd.born == clients * pushes
        assert httpd.pooled > 0
        assert _all_back(httpd)
        time.sleep(0.05)
        assert _all_back(httpd)
        assert httpd.accepted_at == {}
        assert _row(httpd, "http.edit")["count"] == clients * pushes
    finally:
        _stop(httpd)
    got = _counts(httpd)
    assert got["pooled"] + got.get("born", 0) == clients * pushes


def test_pooled_and_born_lose_no_increment_under_eight_clients(
        monkeypatch, short_switches):
    """Four threads write the counts now, and the watch a fifth: with
    a tick short enough that a slow request costs its worker, `pooled`
    + `born` is still the connections served, exactly."""
    monkeypatch.setattr(server_mod, "WATCH_TICK_S", 0.02)
    clients, pushes = 8, 40
    httpd, addr = _serve()
    names = _served_by(httpd)
    _route(httpd, "/slow", lambda h: (time.sleep(0.08), h._send(200, b"{}")))
    try:
        assert _crowd(addr, clients, pushes, path="/slow") == []
        served = clients * pushes
        assert len(names) == served
        assert httpd.pooled + httpd.born == served
        assert httpd.born >= 1          # a `/slow` cost its worker
        assert httpd.pooled > httpd.born
        assert _all_back(httpd)
    finally:
        _stop(httpd)
    got = _counts(httpd)
    assert (got["pooled"], got["born"]) == (httpd.pooled, httpd.born)
    assert httpd.accepted_at == {}


def test_a_crowd_of_one_shot_clients_loses_none_while_a_worker_is_held(
        short_switches):
    n = server_mod.HANDLER_THREADS
    httpd, addr = _serve()
    quiet = None
    try:
        assert b" 200 " in _edit(addr, "w")
        assert _all_back(httpd)
        quiet = _hold_silent(addr, None)
        assert _wait_for(lambda: _inside(httpd) == 1)
        assert _crowd(addr, 64, 1) == []
        assert httpd.pooled + httpd.born == 2 + 64
        assert _row(httpd, "http.edit")["count"] == 1 + 64
        # the held worker was replaced meanwhile, or is about to be
        assert _wait_for(lambda: len(_alive(httpd)) == n + 1
                         and len(httpd._workers) == n
                         and _inside(httpd) == 0)
    finally:
        if quiet is not None:
            quiet.close()
        _stop(httpd)
    assert _wait_for(lambda: not _alive(httpd))     # the holder's, last
