"""The autosave encodes from the oplog's native mirror OUTSIDE
`DocStore.lock`: a pass holds the store-wide lock once, to fix what it
saves (the due documents, their flags cleared, each mirror brought to
the tip by appending), and then encodes every mirror as it stands under
the mirror's own lock alone (`DocStore._flush_pass`, `encode_mirror`,
`NativeContext.mirror_lock`). A document with no mirror is encoded by
the Python writer under the store lock, as before. Lock order:
`store.oplog` -> the mirror's lock, never the reverse.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import urllib.request

import pytest

import diamond_types_tpu.tools.server as srv
from diamond_types_tpu.encoding.decode import load_oplog
from diamond_types_tpu.encoding.encode import (ENCODE_FULL, encode_mirror,
                                               encode_oplog)
from diamond_types_tpu.native import native_available, native_ctx_or_none
from diamond_types_tpu.native.core import NativeContext
from diamond_types_tpu.obs.phases import PhaseTable
from diamond_types_tpu.tools.server import DocStore

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native library unavailable")


def _completes(fn, timeout=10.0):
    """`fn()` on a thread of its own: a deadlock fails the test where
    it would otherwise hang it."""
    out = []
    t = threading.Thread(target=lambda: out.append(fn()), daemon=True)
    t.start()
    t.join(timeout)
    assert not t.is_alive(), "did not complete"
    return out[0]


def _text(blob: bytes) -> str:
    return load_oplog(blob).checkout_tip().snapshot()


def _store(tmp_path, docs=()) -> DocStore:
    """A store with `docs` typed into and dirty."""
    store = DocStore(data_dir=str(tmp_path), save_interval=0.0)
    for name in docs:
        ol = store.get(name)
        with store.lock:
            ol.add_insert(ol.get_or_create_agent_id("u"), 0, name + " text")
        store.mark_dirty(name)
    return store


def _pass_counts(store, force=True) -> dict:
    """One pass under a phase table of its own; the root's counts."""
    table = PhaseTable()
    with table.phase("autosave.pass") as ph:
        store._flush_pass(force, ph)
    return table.snapshot()["phases"]["autosave.pass"]["counts"]


# ---- (a) the store is free while a mirror is encoded --------------------

def _edit(addr, doc, text):
    req = urllib.request.Request(
        f"http://{addr}/doc/{doc}/edit",
        data=json.dumps({"agent": "w", "version": None,
                         "ops": [{"kind": "ins", "pos": 0,
                                  "text": text}]}).encode("utf8"))
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.loads(r.read())


def test_the_store_is_free_while_a_mirror_is_encoded(tmp_path, monkeypatch):
    from diamond_types_tpu.tpu.flush_fuse import FusedDocSession

    httpd = srv.serve(port=0, data_dir=str(tmp_path))
    store = httpd.store
    store.stop_flusher()        # the passes are this test's own
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        _edit(addr, "same", "hello")
        _edit(addr, "other", "world")
        sess = {d: FusedDocSession(store.get(d), cap=64, max_ins=4)
                for d in ("same", "other")}
        _edit(addr, "same", "well, ")
        _edit(addr, "other", "whole ")
        store.flush(force=True)
        _edit(addr, "same", "oh ")          # the one due document
        assert set(store.dirty) == {"same"}
        with store.lock:
            fixed = sess["same"].oplog.checkout_tip().snapshot()

        started, let_go = threading.Event(), threading.Event()
        real = NativeContext.encode_held

        def held_open(ctx, *args):
            with ctx.mirror_lock:   # as the C call holds it
                started.set()
                assert let_go.wait(30)
                return real(ctx, *args)

        monkeypatch.setattr(NativeContext, "encode_held", held_open)
        saver = threading.Thread(target=store.flush, args=(True,),
                                 daemon=True)
        saver.start()
        assert started.wait(10)
        assert not store.lock.locked()

        # the store, the handler and ANOTHER document's walk go on
        assert _completes(lambda: store.get("same")) is sess["same"].oplog
        _completes(lambda: store.mark_dirty("other"))
        _completes(lambda: _edit(addr, "other", "the "))
        assert _completes(lambda: _edit(addr, "same", "ah, "))["version"]

        def walk(doc):
            table = PhaseTable()
            with table.phase("sched.flush"), store.lock:
                plan = sess[doc].plan_tail()
            return plan, table.snapshot()["phases"]["plan.tail"]["counts"]

        plan, counts = _completes(lambda: walk("other"))
        assert plan.n_ops > 0 and counts["mirror_busy_waits"] == 0

        # the SAME document's walk waits that one encode out, holding
        # the store lock, and then gives the plan the Python walk gives
        got = []
        walker = threading.Thread(
            target=lambda: got.append(walk("same")), daemon=True)
        walker.start()
        walker.join(0.3)
        assert walker.is_alive() and saver.is_alive()
        let_go.set()
        walker.join(10)
        saver.join(10)
        assert not walker.is_alive() and not saver.is_alive()
        plan, counts = got[0]
        assert counts["mirror_busy_waits"] == 1 and counts["xf_native"] == 1
        with monkeypatch.context() as m:
            m.setenv("DT_TPU_NO_NATIVE", "1")
            with store.lock:
                oracle = sess["same"].plan_tail()
        for f in ("pos", "dlen", "ilen", "chars"):
            assert (getattr(plan, f) == getattr(oracle, f)).all(), f
        for f in ("n_ops", "new_len", "max_len", "frontier", "synced_to"):
            assert getattr(plan, f) == getattr(oracle, f), f
        assert plan.n_ops > 0
        # what the held encode wrote: the version its flag was cleared at
        assert _text((tmp_path / "same.dt").read_bytes()) == fixed
        assert "same" in store.dirty     # typed in since
        with store.lock:
            tip = sess["same"].oplog.checkout_tip().snapshot()
        assert len(tip) == len(fixed) + len("ah, ")
    finally:
        let_go.set()
        httpd.shutdown()
        httpd.server_close()
    assert _text((tmp_path / "same.dt").read_bytes()) == tip


# ---- (b) edits between the fix and the encode ---------------------------

@pytest.mark.parametrize("mirror", ["as_fixed", "appended_by_a_walk"])
def test_edits_between_the_fix_and_the_encode(tmp_path, monkeypatch,
                                              mirror):
    store = _store(tmp_path, ["d"])
    ol = store.get("d")
    agent = ol.get_or_create_agent_id("u")
    real = srv.encode_mirror

    def late_edit(ctx, doc_id):
        with store.lock:
            ol.add_insert(agent, 0, "later ")
            if mirror == "appended_by_a_walk":
                ctx.sync()
        store.mark_dirty("d")
        return real(ctx, doc_id)

    with monkeypatch.context() as m:
        m.setattr(srv, "encode_mirror", late_edit)
        assert _pass_counts(store, force=False) == {
            "docs": 1, "docs_unlocked": 1, "docs_locked": 0}
    # the file loads, at the fixed version or a later prefix
    saved = _text((tmp_path / "d.dt").read_bytes())
    assert saved == ("d text" if mirror == "as_fixed" else "later d text")
    assert "d" in store.dirty
    store.flush()                       # the next pass writes the tip
    assert "d" not in store.dirty
    blob = (tmp_path / "d.dt").read_bytes()
    assert _text(blob) == "later d text"
    with store.lock:
        assert blob == encode_oplog(ol, ENCODE_FULL)


# ---- (c) the bytes are the locked encode's at the same length -----------

def _linear(ol):
    a = ol.get_or_create_agent_id("a")
    ol.add_insert(a, 0, "hello")
    ol.add_insert(a, 5, " world")
    yield
    ol.add_delete_without_content(a, 0, 1)
    ol.add_insert(a, 0, "J")


def _blind_writers(ol):
    """Two writers from their own heads, who never see each other."""
    a = ol.get_or_create_agent_id("a")
    b = ol.get_or_create_agent_id("b")
    la = ol.add_insert_at(a, [], 0, "aaa")
    lb = ol.add_insert_at(b, [], 0, "bbb")
    la = ol.add_insert_at(a, [la], 3, "AA")
    yield
    lb = ol.add_insert_at(b, [lb], 0, "BB")
    la = ol.add_delete_at(a, [la], 0, 2)
    ol.add_insert_at(b, [lb], 5, "!")


def _deletes_of_deleted_spans(ol):
    a = ol.get_or_create_agent_id("a")
    b = ol.get_or_create_agent_id("b")
    base = ol.add_insert_at(a, [], 0, "abcdefghij")
    da = ol.add_delete_at(a, [base], 2, 6)
    db = ol.add_delete_at(b, [base], 1, 5)
    yield
    dc = ol.add_delete_at(b, [db], 0, 3)        # "afg" of "afghij"
    ol.add_insert_at(a, [da, dc], 0, "<")
    ol.add_delete_at(a, [base], 3, 8)           # deleted three times over


@pytest.mark.parametrize("history", [_linear, _blind_writers,
                                     _deletes_of_deleted_spans])
def test_the_mirror_encodes_to_the_locked_bytes(tmp_path, monkeypatch,
                                                history):
    store = DocStore(data_dir=str(tmp_path), save_interval=0.0)
    ol = store.get("d")
    steps = history(ol)
    next(steps)
    with store.lock:
        want = encode_oplog(ol, ENCODE_FULL)    # syncs the mirror
        ctx = native_ctx_or_none(ol)
    with monkeypatch.context() as m:            # the Python writer agrees
        m.setenv("DT_TPU_NO_NATIVE", "1")
        assert encode_oplog(ol, ENCODE_FULL) == want
    at, text = len(ol), ol.checkout_tip().snapshot()
    assert next(steps, None) is None            # the oplog grows ...
    assert len(ol) > at
    # ... and the mirror, as it stands, still encodes the shorter one
    assert encode_mirror(ctx, ol.doc_id) == want
    assert _text(want) == text
    store.mark_dirty("d")
    assert _pass_counts(store) == {
        "docs": 1, "docs_unlocked": 1, "docs_locked": 0}
    blob = (tmp_path / "d.dt").read_bytes()
    with store.lock:
        assert blob == encode_oplog(ol, ENCODE_FULL) != want
    assert _text(blob) == ol.checkout_tip().snapshot()


# ---- (d) no native engine: under the lock, the same bytes ---------------

def test_no_native_engine_encodes_under_the_lock(tmp_path, monkeypatch):
    store = _store(tmp_path, ["p", "q"])
    with monkeypatch.context() as m:
        m.setenv("DT_TPU_NO_NATIVE", "1")
        assert _pass_counts(store) == {
            "docs": 2, "docs_unlocked": 0, "docs_locked": 2}
    for d in ("p", "q"):       # the native encode's bytes
        ol = store.get(d)
        with store.lock:
            assert (tmp_path / f"{d}.dt").read_bytes() \
                == encode_oplog(ol, ENCODE_FULL)
    # a mirror that has no native encode after all: under the lock too
    store.mark_dirty("p")
    monkeypatch.setattr(NativeContext, "encode_held",
                        lambda ctx, *args: None)
    assert _pass_counts(store) == {
        "docs": 1, "docs_unlocked": 0, "docs_locked": 1}


# ---- (e) acquisitions a pass; a write failure ---------------------------

class _Counting:
    """`DocStore.lock` with its acquisitions counted."""

    def __init__(self, inner) -> None:
        self._inner = inner
        self.acquired = 0

    def acquire(self, *a, **kw):
        self.acquired += 1
        return self._inner.acquire(*a, **kw)

    def release(self):
        return self._inner.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()


def _counted_pass(tmp_path, n: int):
    store = _store(tmp_path, [f"d{i}" for i in range(n)])
    store.lock = _Counting(store.lock)
    counts = _pass_counts(store)
    assert counts == {"docs": n, "docs_unlocked": n, "docs_locked": 0}
    return store


def test_a_pass_takes_the_store_lock_once_whatever_it_saves(tmp_path):
    few = _counted_pass(tmp_path / "few", 2)
    many = _counted_pass(tmp_path / "many", 40)
    assert few.lock.acquired == many.lock.acquired == 1
    assert len(list((tmp_path / "many").glob("*.dt"))) == 40


@pytest.mark.parametrize("n", [3, 24])
def test_a_write_failure_still_remarks_and_backs_off(tmp_path, monkeypatch,
                                                     capsys, n):
    store = _store(tmp_path, [f"d{i}" for i in range(n)])
    store.save_interval = 3.0
    store.lock = _Counting(store.lock)
    real_replace = srv.os.replace

    def flaky(src, dst):
        if dst.endswith("d1.dt"):
            raise OSError(28, "No space left on device")
        return real_replace(src, dst)

    with monkeypatch.context() as m:
        m.setattr(srv.os, "replace", flaky)
        store.flush(force=True)
    # the fix, the failure, and ONE hold to end the others' streaks
    # (taken because a streak exists): none of them a file's
    assert store.lock.acquired == 3
    assert store.flush_failures == {"d1": 1}
    assert set(store.dirty) == {"d1"}
    assert store.dirty["d1"] > time.monotonic()         # backing off
    assert "write failed" in capsys.readouterr().err
    assert len(list(tmp_path.glob("*.dt"))) == n - 1
    store.mark_dirty("d1")                              # an edit cuts it
    store.flush(force=True)
    assert (tmp_path / "d1.dt").exists()
    assert store.flush_failures == {} and not store.dirty


# ---- (f) writers, a flusher and plan walks at once ----------------------

def test_writers_a_flusher_and_walks_at_once(tmp_path, monkeypatch):
    from diamond_types_tpu.analysis import (witness_assert_acyclic,
                                            witness_disable,
                                            witness_enable, witness_reset,
                                            witness_snapshot)
    from diamond_types_tpu.tpu.flush_fuse import FusedDocSession

    docs = ["x", "y", "z"]
    store = _store(tmp_path, docs)
    ols = {d: store.get(d) for d in docs}
    sess = {d: FusedDocSession(ols[d], cap=1 << 12, max_ins=8)
            for d in docs}
    replaced = []       # every file that ever took a document's place
    real_replace = srv.os.replace

    def recording(src, dst):
        # `os` is the whole process's: a server another test left
        # behind may still be saving its own documents
        if os.path.dirname(dst) == str(tmp_path):
            with open(src, "rb") as f:
                replaced.append((dst, f.read()))
        return real_replace(src, dst)

    monkeypatch.setattr(srv.os, "replace", recording)
    stop = threading.Event()
    errs = []

    def guarded(fn):
        def run():
            try:
                fn()
            except Exception as e:      # pragma: no cover
                errs.append(e)
                stop.set()
        return threading.Thread(target=run, daemon=True)

    def writer(name):
        def run():
            head = dict.fromkeys(docs)      # the writer's own last op
            i = 0
            while not stop.is_set():
                d = docs[i % 3]
                ol = ols[d]
                with store.lock:
                    agent = ol.get_or_create_agent_id(name)
                    if i % 11 == 10:        # pulls the other's edits in
                        parents = ol.version
                    else:
                        parents = [] if head[d] is None else [head[d]]
                    head[d] = ol.add_insert_at(agent, parents, 0,
                                               f"{name}{i} ")
                    if i % 5 == 4:          # of the text just typed
                        head[d] = ol.add_delete_at(agent, [head[d]], 0, 2)
                store.mark_dirty(d)
                i += 1
                time.sleep(0.0005)      # a few thousand edits in all
        return run

    def flusher():
        while not stop.is_set():
            store.flush()
            time.sleep(0.002)

    def walker():
        i = 0
        while not stop.is_set():
            s = sess[docs[i % 3]]
            with store.lock:
                plan = s.plan_tail()
                if plan.n_ops:      # the bookkeeping of an adoption
                    s.commit(s.docs, s.lens, plan)
                else:
                    s.commit_host(plan)
            i += 1

    witness_reset()
    witness_enable()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    threads = [guarded(writer("ann")), guarded(writer("bob")),
               guarded(flusher), guarded(walker)]
    try:
        for t in threads:
            t.start()
        stop.wait(2.5)
        stop.set()
        for t in threads:
            t.join(20)
        assert not any(t.is_alive() for t in threads) and not errs
        store.flush(force=True)
        snap = witness_snapshot()
        assert snap["acyclic"] and snap["violations"] == [], snap
        assert "oplog->leaf" in snap["edges"]       # sync under the store
        assert "io->leaf" in snap["edges"]          # encode outside it
        assert not any(e.startswith("leaf->") for e in snap["edges"])
        witness_assert_acyclic()
    finally:
        stop.set()
        sys.setswitchinterval(interval)
        witness_disable()
        witness_reset()
    assert not store.dirty
    for d in docs:
        blob = (tmp_path / f"{d}.dt").read_bytes()
        assert blob == encode_oplog(ols[d], ENCODE_FULL)
        assert _text(blob) == ols[d].checkout_tip().snapshot()
        assert sess[d].synced_to <= len(ols[d])
    # every file ever replaced loads, as a causally closed prefix
    assert len(replaced) > len(docs)
    for dst, blob in replaced:
        ol = load_oplog(blob)
        assert 0 < len(ol) <= len(ols[dst[-4]])
        ol.checkout_tip().snapshot()
