"""On the edit path a push takes `DocStore.lock` at most once
(tools/server.py `_do_post`, `DocStore`): a resident document comes by
one `dict.get`, the dirty flag is set in the hold that adds the ops,
and `notify` wakes a document's condition only where a long-poll made
one. What must not move with it: a rejected batch leaves everything as
it was, a long-poll is woken, no acknowledged edit misses its file, two
first pushes make one `OpLog`. Every wait in here has a time limit of
its own.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from diamond_types_tpu.encoding.decode import load_oplog
from diamond_types_tpu.tools import server as server_mod
from diamond_types_tpu.wire.frames import (FRAME_OPS, encode_frame,
                                           encode_ops)
from test_push_path_clocks import _count, _serve, _stop, _wait_for

pytestmark = pytest.mark.obs

EDIT_SITES = ("http.edit", "edit.")     # bench/phases.py's


def _post(addr, doc, action, body: bytes, timeout=20):
    """(status, parsed body) of one POST; a 4xx is an answer here."""
    req = urllib.request.Request(
        f"http://{addr[0]}:{addr[1]}/doc/{doc}/{action}", data=body)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _edit_body(kind, agent, version, ops) -> bytes:
    req = {"agent": agent, "version": version, "ops": ops}
    if kind == "frame":
        return encode_frame(FRAME_OPS, encode_ops(req))
    return json.dumps(req).encode("utf8")


def _ins(pos, text):
    return {"kind": "ins", "pos": pos, "text": text}


def _edit_takes(httpd) -> dict:
    sites = httpd.store.obs.phases.snapshot()["locks"].get("store.oplog", {})
    return {s: c["acquires"] for s, c in sites.items()
            if s.startswith(EDIT_SITES)}


# ---- (a) one acquisition an edit -----------------------------------------------

@pytest.mark.parametrize("n", [1, 12])
@pytest.mark.parametrize("kind", ["json", "frame"])
def test_edits_of_a_resident_document_take_the_lock_once_each(kind, n):
    httpd, addr = _serve()
    try:
        httpd.store.get("d")            # resident before the first push
        version = None
        for i in range(n):
            status, out = _post(addr, "d", "edit", _edit_body(
                kind, "a", version, [_ins(i, "x"), _ins(i + 1, "y"),
                                     {"kind": "del", "start": i,
                                      "end": i + 1}]))
            assert status == 200, out
            version = out["version"]
        assert _wait_for(lambda: _count(httpd, "http.edit") == n)
        takes = _edit_takes(httpd)
        # the hold that validates, adds and marks; filed under the step
        # open when it was taken; none at `edit.parse` or `edit.publish`
        assert takes == {"edit.checkout": n}
        assert len(httpd.store.docs["d"]) == 3 * n
        assert "d" in httpd.store.dirty
    finally:
        _stop(httpd)


def test_a_first_push_makes_its_document_under_the_lock_and_only_it():
    httpd, addr = _serve()
    try:
        version = None
        for i in range(4):
            status, out = _post(addr, "fresh", "edit", _edit_body(
                "json", "a", version, [_ins(i, "x")]))
            assert status == 200
            version = out["version"]
        assert _wait_for(lambda: _count(httpd, "http.edit") == 4)
        # `store.get`'s miss: a little over one take a push, once
        assert _edit_takes(httpd) == {"edit.parse": 1, "edit.checkout": 4}
    finally:
        _stop(httpd)


def test_get_answers_a_resident_document_while_the_lock_is_held(tmp_path):
    store = server_mod.DocStore(str(tmp_path))
    ol = store.get("r")
    done = []
    with store.lock:
        t = threading.Thread(target=lambda: done.append(store.get("r")),
                             daemon=True)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive() and done == [ol]
        # the locking form is for a caller without the lock; the edit
        # path's form is for the holder
        store.mark_dirty_locked("r")
        assert "r" in store.dirty
    store.mark_dirty("r")


# ---- (b) a rejected batch ----------------------------------------------------------

@pytest.mark.parametrize("kind", ["json", "frame"])
@pytest.mark.parametrize("bad", [
    _ins(10_000, "z"),                              # past the end
    {"kind": "del", "start": 2, "end": 9_999},      # a span too long
])
def test_a_batch_with_a_bad_op_leaves_everything_as_it_was(tmp_path, kind,
                                                           bad):
    httpd, addr = _serve(data_dir=str(tmp_path))
    try:
        status, out = _post(addr, "b", "edit", _edit_body(
            kind, "a", None, [_ins(0, "hello")]))
        assert status == 200
        store = httpd.store
        store.flush(force=True)         # the dirty set is empty again
        ol = store.docs["b"]
        before = (len(ol), dict(ol._len_memo), dict(store.dirty),
                  list(ol.version))
        assert before[2] == {}
        # the first op is fine, the second is not: nothing of the batch
        # may stay
        status, err = _post(addr, "b", "edit", _edit_body(
            kind, "a", out["version"], [_ins(5, "!"), bad]))
        assert (status, err) == (400, {"error": "bad op"})
        assert (len(ol), dict(ol._len_memo), dict(store.dirty),
                list(ol.version)) == before
        assert store.lock.acquire(timeout=5)
        store.lock.release()
        assert store._conds == {}
        # and the writer goes on from where they were
        status, out = _post(addr, "b", "edit", _edit_body(
            kind, "a", out["version"], [_ins(5, "!")]))
        assert status == 200 and "b" in store.dirty
    finally:
        _stop(httpd)


# ---- (c) the long-poll ---------------------------------------------------------------

def test_a_parked_long_poll_is_woken_by_an_edit_and_nobody_else_gets_a_cond():
    httpd, addr = _serve()
    try:
        store = httpd.store
        status, out = _post(addr, "polled", "edit", _edit_body(
            "json", "a", None, [_ins(0, "x")]))
        assert status == 200
        got = []

        def poll():
            got.append(_post(addr, "polled", "changes", json.dumps(
                {"version": out["version"], "wait": 30}).encode("utf8"),
                timeout=40))
        t = threading.Thread(target=poll, daemon=True)
        t0 = time.monotonic()
        t.start()
        # parked: it made the document's condition and waits on it
        assert _wait_for(lambda: "polled" in store._conds)
        cond = store._conds["polled"]
        assert _wait_for(lambda: len(cond._waiters) == 1)
        assert got == []
        status, _ = _post(addr, "polled", "edit", _edit_body(
            "json", "b", out["version"], [_ins(1, "y")]))
        assert status == 200
        t.join(timeout=10)
        assert not t.is_alive()
        # woken, not timed out (the wait's own steps are 5 s)
        assert time.monotonic() - t0 < 4.5
        status, changes = got[0]
        assert status == 200 and changes["op"]
        # a document nobody polls gets no condition
        status, _ = _post(addr, "quiet", "edit", _edit_body(
            "json", "a", None, [_ins(0, "x")]))
        assert status == 200
        assert set(store._conds) == {"polled"}
        store.notify("quiet")           # nothing to wake, nothing made
        assert set(store._conds) == {"polled"}
    finally:
        _stop(httpd)


def test_a_poll_that_arrives_after_the_edit_reads_it_in_its_own_check():
    httpd, addr = _serve()
    try:
        status, first = _post(addr, "late", "edit", _edit_body(
            "json", "a", None, [_ins(0, "x")]))
        status, _ = _post(addr, "late", "edit", _edit_body(
            "json", "a", first["version"], [_ins(1, "y")]))
        assert status == 200 and httpd.store._conds == {}
        t0 = time.monotonic()
        status, changes = _post(addr, "late", "changes", json.dumps(
            {"version": first["version"], "wait": 30}).encode("utf8"),
            timeout=40)
        assert status == 200 and changes["op"]
        assert time.monotonic() - t0 < 4.5
    finally:
        _stop(httpd)


# ---- (d) no dirty flag lost between apply and mark ---------------------------------------

def test_every_acknowledged_edit_is_in_its_file_after_a_final_pass(tmp_path):
    """Writers push to 8 documents while autosave passes run back to
    back: an edit is in the oplog and marked dirty in ONE hold, so a
    pass sees both or neither, and after a last pass every
    acknowledged character is in its document's file."""
    httpd, addr = _serve(data_dir=str(tmp_path))
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        store = httpd.store
        docs = [f"w{i}" for i in range(8)]
        acked = {d: 0 for d in docs}
        stop = threading.Event()
        errors = []

        def passes():
            while not stop.is_set():
                try:
                    store.flush(force=True)
                except Exception as e:   # the stress test's boundary
                    errors.append(repr(e))
                    return

        def writer(doc):
            version = None
            for i in range(25):
                status, out = _post(addr, doc, "edit", _edit_body(
                    "json" if i % 2 else "frame", "w", version,
                    [_ins(i, "k")]))
                if status != 200:
                    errors.append((doc, status, out))
                    return
                version = out["version"]
                acked[doc] += 1

        flusher = threading.Thread(target=passes, daemon=True)
        flusher.start()
        writers = [threading.Thread(target=writer, args=(d,), daemon=True)
                   for d in docs]
        for t in writers:
            t.start()
        for t in writers:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in writers)
        stop.set()
        flusher.join(timeout=30)
        assert not flusher.is_alive() and errors == []
        # the last pass saves what is flagged, no more: `force` only
        # lifts the wait of `save_interval`
        store.flush(force=True)
        assert store.dirty == {}
        for d in docs:
            assert acked[d] == 25
            with open(os.path.join(str(tmp_path), d + ".dt"), "rb") as f:
                on_disk = load_oplog(f.read())
            assert len(on_disk) == 25, d
            assert on_disk.checkout_tip().snapshot() == "k" * 25
    finally:
        sys.setswitchinterval(old)
        _stop(httpd)


# ---- (e) two first pushes, one OpLog --------------------------------------------------

@pytest.mark.parametrize("through", ["store", "http"])
def test_two_first_pushes_to_an_unknown_document_make_one_oplog(through,
                                                                monkeypatch):
    httpd, addr = _serve()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        store = httpd.store
        made = []
        real = server_mod.OpLog

        def counting():
            ol = real()
            made.append(ol)
            return ol
        monkeypatch.setattr(server_mod, "OpLog", counting)
        for round_ in range(20):
            doc = f"new{round_}"
            made.clear()
            gate = threading.Barrier(2)
            got = []

            def first(agent):
                gate.wait(timeout=10)
                if through == "store":
                    got.append(store.get(doc))
                else:
                    got.append(_post(addr, doc, "edit", _edit_body(
                        "json", agent, None, [_ins(0, agent)])))
            ts = [threading.Thread(target=first, args=(a,), daemon=True)
                  for a in ("a", "b")]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=30)
            assert not any(t.is_alive() for t in ts) and len(got) == 2
            assert len(made) == 1 and store.docs[doc] is made[0]
            if through == "store":
                assert got[0] is got[1] is made[0]
            else:
                assert [s for s, _ in got] == [200, 200]
                assert len(store.docs[doc]) == 2    # both pushes' ops
                text = store.docs[doc].checkout_tip().snapshot()
                assert sorted(text) == ["a", "b"]
    finally:
        sys.setswitchinterval(old)
        _stop(httpd)
