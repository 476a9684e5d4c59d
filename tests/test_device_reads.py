"""Under `MergeScheduler(reads="device")` a `GET /doc/{id}` at the tip
is answered from the document's device session
(`MergeScheduler.read_tip`, `SessionBank.read_row`, `tools/server.py`
`_do_get`), and by the host only where there is none; under the
default, `reads="host"`, it is the host checkout it was.

Driven through a real `serve(engine="device",
sched_opts={"reads": "device"})` on the CPU, against two
references that share nothing with the device row: `bench/corpus.py`'s
`PlainDoc` (bytearrays, no CRDT in it) and the host's own
`checkout_tip()`. Counts and bytes only, never a time; every wait in
here has a time limit of its own.
"""

from __future__ import annotations

import http.client
import json
import threading
import time

import numpy as np
import pytest

from bench import corpus, gen
from bench.run import push_doc
from diamond_types_tpu.serve.scheduler import MergeScheduler
from diamond_types_tpu.tools import server as server_mod
from diamond_types_tpu.tpu import flush_fuse as ff
from diamond_types_tpu.tpu.runtime import COMPILE_STATS
from test_push_path_clocks import _rows, _stop, _wait_for

pytestmark = [pytest.mark.fused, pytest.mark.serve]

BURST = {"ops": 8, "mean_run": 14, "p_back": 0.425}
FUSED = {"cap": 256, "max_ins": 16, "headroom": 2.0}


def _serve(shards=1, engine="device", **so):
    so.setdefault("reads", "device")
    so.setdefault("fused_opts", FUSED)
    so.setdefault("max_sessions_per_shard", 64)
    httpd = server_mod.serve(port=0, engine=engine, serve_shards=shards,
                             sched_opts=so, obs_opts={"sample_rate": 0.0})
    addr = ("127.0.0.1", httpd.server_address[1])
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, addr


def _get(addr, doc_id: str):
    """(status, body, the frontier header as a list)."""
    conn = http.client.HTTPConnection(addr[0], addr[1], timeout=60)
    try:
        conn.request("GET", f"/doc/{doc_id}")
        r = conn.getresponse()
        f = r.getheader("X-DT-Frontier")
        return r.status, r.read(), json.loads(f) if f else None
    finally:
        conn.close()


def _edit(addr, doc_id: str, agent: str, version, ops):
    status, data = gen.request(
        addr, "POST", f"/doc/{doc_id}/edit",
        json.dumps({"agent": agent, "version": version,
                    "ops": ops}).encode())
    assert status == 200, data
    return json.loads(data)["version"]


def _ins(pos, text):
    return {"kind": "ins", "pos": pos, "text": text}


def _load(httpd, addr, doc_id: str, seed: int, n_ops: int, writers=2,
          index=0) -> corpus.PlainDoc:
    """A typed document pushed whole, resident and at its tip, with the
    plain reference beside it."""
    tip = push_doc(f"http://{addr[0]}:{addr[1]}", seed,
                   {"id": doc_id, "index": index, "ops": n_ops})
    httpd.store.scheduler.drain()
    doc = corpus.PlainDoc(
        doc_id, corpus.doc_text(seed, index, n_ops), writers,
        [corpus.Typist(np.random.default_rng([seed, 23, index, w]), BURST)
         for w in range(writers)])
    doc.heads = [tip] * writers
    return doc


def _totals(httpd) -> dict:
    return httpd.store.scheduler.metrics_json()["totals"]


def _get_counts(httpd) -> dict:
    return _rows(httpd).get("http.get", {}).get("counts", {})


def _row_count(httpd, name: str) -> int:
    return _rows(httpd).get(name, {}).get("count", 0)


def _settled(httpd, name: str) -> int:
    """A row's count once the load's own requests have written theirs
    (`push_doc` reads the document's summary first: a `get.checkout`)."""
    last = -1
    while last != _row_count(httpd, name):
        last = _row_count(httpd, name)
        time.sleep(0.05)
    return last


def _covers(frontier, doc: corpus.PlainDoc) -> bool:
    """Does the answered frontier hold every acknowledged edit of every
    writer? A writer's head names its own last op: (agent, seq)."""
    have = {a: s for a, s in frontier}
    return all(have.get(a, -1) >= s for head in doc.heads
               for a, s in head if a.startswith("w"))


def _host_text(httpd, doc_id: str) -> bytes:
    store = httpd.store
    with store.lock:
        return store.docs[doc_id].checkout_tip().snapshot().encode("utf8")


# ---- where the host still answers ------------------------------------------------

@pytest.mark.parametrize("so", [{}, {"reads": "host"}],
                         ids=["default", "host"])
def test_reads_unset_or_host_leave_get_on_the_host_checkout(so):
    """The parent's `GET`: `get.checkout` under the store lock, none of
    the read path's steps or counts, the session untouched; the
    library's `text()` reads the device all the same."""
    httpd = server_mod.serve(
        port=0, engine="device", serve_shards=1,
        sched_opts=dict(so, fused_opts=FUSED),
        obs_opts={"sample_rate": 0.0})
    addr = ("127.0.0.1", httpd.server_address[1])
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    sched = httpd.store.scheduler
    try:
        assert sched.reads == "host"
        doc = _load(httpd, addr, "d", 5, 400)
        sched.stop_pump(drain=True)
        ok, _n, err = gen.push(addr, doc, 8, 20.0)
        assert ok, err
        base = _settled(httpd, "get.checkout")
        synced = sched.banks[0].sessions["d"].synced_to
        for _ in range(3):
            status, body, frontier = _get(addr, "d")
            assert status == 200 and body == doc.text() \
                == _host_text(httpd, "d")
            assert _covers(frontier, doc)
        assert _wait_for(
            lambda: _row_count(httpd, "get.checkout") == base + 3)
        rows = _rows(httpd)
        assert "get.fetch" not in rows and "get.sync" not in rows
        assert not {"device", "host", "at_tip"} & set(_get_counts(httpd))
        # no read flushed anything, and none was counted
        assert sched.banks[0].sessions["d"].synced_to == synced
        assert (_totals(httpd)["reads_from_device"],
                _totals(httpd)["reads_from_host"]) == (0, 0)
        assert sched.text("d").encode() == doc.text()
        assert _totals(httpd)["reads_from_device"] == 1
    finally:
        _stop(httpd)


def test_any_other_value_of_reads_raises():
    with pytest.raises(ValueError, match="reads='nope'"):
        MergeScheduler(1, resolve=lambda d: None, engine="host",
                       reads="nope")
    with pytest.raises(ValueError, match="reads='nope'"):
        server_mod.serve(port=0, engine="device", serve_shards=1,
                         sched_opts={"reads": "nope"},
                         obs_opts={"sample_rate": 0.0})


@pytest.mark.parametrize("shards", [0, 1])
def test_a_host_engine_leaves_get_on_the_host_checkout(shards):
    """No scheduler (`serve_shards=0`, the restart check's server) or a
    host-engine one: `get.checkout`, counted where there is a bank."""
    httpd, addr = _serve(shards=shards, engine="host")
    try:
        doc = _load(httpd, addr, "d", 5, 400) if shards else None
        if doc is None:
            _edit(addr, "d", "a", None, [_ins(0, "hello")])
        base = _settled(httpd, "get.checkout")
        for _ in range(3):
            status, body, frontier = _get(addr, "d")
            assert status == 200 and body == _host_text(httpd, "d")
            assert frontier
        assert _wait_for(
            lambda: _row_count(httpd, "get.checkout") == base + 3)
        rows = _rows(httpd)
        assert "get.fetch" not in rows and "get.sync" not in rows
        assert "device" not in _get_counts(httpd)
        if shards:
            assert _get_counts(httpd).get("host") == 3
            assert _totals(httpd)["reads_from_host"] == 3
            assert _totals(httpd)["reads_from_device"] == 0
    finally:
        _stop(httpd)


# ---- bytes, frontier and counts under traffic ----------------------------------

@pytest.mark.parametrize("pump", ["running", "stopped"])
@pytest.mark.parametrize("seed", [11, 12, 13])
def test_every_body_is_the_reference_and_the_host_checkout(seed, pump):
    httpd, addr = _serve()
    sched = httpd.store.scheduler
    try:
        docs = [_load(httpd, addr, f"d{i}", seed, 300 + 50 * i, index=i)
                for i in range(3)]
        if pump == "stopped":
            sched.stop_pump(drain=True)
        base = _totals(httpd)
        checkouts = _settled(httpd, "get.checkout")
        rng = np.random.default_rng(seed)
        gets = 0
        for _ in range(60):
            doc = docs[int(rng.integers(len(docs)))]
            if rng.random() < 0.5:
                ok, _n, err = gen.push(addr, doc, 8, 20.0)
                assert ok, err
            else:
                status, body, frontier = _get(addr, doc.id)
                gets += 1
                assert status == 200
                assert body == doc.text()
                assert body == _host_text(httpd, doc.id)
                assert _covers(frontier, doc)
        assert _wait_for(lambda: _get_counts(httpd).get("device") == gets)
        end, counts = _totals(httpd), _get_counts(httpd)
        assert end["reads_from_host"] == base["reads_from_host"]
        assert end["reads_from_device"] - base["reads_from_device"] == gets
        assert "host" not in counts
        rows = _rows(httpd)
        synced = rows.get("get.sync", {}).get("count", 0)
        assert counts.get("at_tip", 0) + synced == gets
        if pump == "stopped":
            # nobody else merges: a read behind an edit flushed itself
            assert synced > 0 and synced == sched.metrics_json()[
                "flush_reasons"]["read"]
        assert end["host_fallbacks"] == end["device_errors"] == 0
        assert _row_count(httpd, "get.checkout") == checkouts
        assert rows["get.fetch"]["count"] == gets
    finally:
        _stop(httpd)


def test_a_read_flush_of_one_document_stays_off_the_per_doc_ladder():
    """The ladder replays under the oplog guard; a read's own flush
    replays a lone document as a group of one, outside it."""
    httpd, addr = _serve()
    sched = httpd.store.scheduler
    try:
        doc = _load(httpd, addr, "d", 3, 400)
        sched.stop_pump(drain=True)
        base = sched.metrics_json()
        inline = _rows(httpd)["sched.flush"]["counts"].get("inline", 0)
        ok, _n, err = gen.push(addr, doc, 8, 20.0)
        assert ok, err
        status, body, _f = _get(addr, "d")
        assert status == 200 and body == doc.text()
        end = sched.metrics_json()
        # on the reader's thread: neither forced nor paced
        assert _rows(httpd)["sched.flush"]["counts"]["inline"] \
            == inline + 1
        assert end["fused"]["device_calls"] \
            - base["fused"]["device_calls"] == 1
        assert end["flush_reasons"]["read"] \
            - base["flush_reasons"].get("read", 0) == 1
        assert _wait_for(lambda: "adopt" in _rows(httpd))
        held = httpd.store.obs.phases.snapshot()["locks"]["store.oplog"]
        # the hold that plans, none while the device works
        assert "replay" not in held and "replay.fence" not in held
    finally:
        _stop(httpd)


def test_a_flush_in_flight_is_waited_for_not_raced(monkeypatch):
    httpd, addr = _serve()
    sched = httpd.store.scheduler
    try:
        doc = _load(httpd, addr, "d", 7, 400)
        entered, release = threading.Event(), threading.Event()
        real = ff.fused_replay

        def slow(sessions, plans):
            entered.set()
            assert release.wait(20)
            return real(sessions, plans)

        monkeypatch.setattr(ff, "fused_replay", slow)
        base = _totals(httpd)
        ok, _n, err = gen.push(addr, doc, 8, 20.0)
        assert ok, err
        assert entered.wait(20)         # the worker's flush holds the items
        out = {}
        t = threading.Thread(target=lambda: out.update(
            got=_get(addr, "d")), daemon=True)
        t.start()
        time.sleep(0.3)
        assert t.is_alive()             # no stale row, no host answer
        release.set()
        t.join(20)
        status, body, frontier = out["got"]
        assert status == 200 and body == doc.text()
        assert _covers(frontier, doc)
        assert _wait_for(lambda: _get_counts(httpd).get("device") == 1)
        # it synced (`get.sync`), and by waiting: no flush of its own
        assert _row_count(httpd, "get.sync") == 1
        assert "at_tip" not in _get_counts(httpd)
        assert sched.metrics_json()["flush_reasons"].get("read", 0) == 0
        assert _totals(httpd)["reads_from_host"] == base["reads_from_host"]
    finally:
        release.set()
        _stop(httpd)


def test_frontier_is_the_sessions_and_named_once_a_commit():
    httpd, addr = _serve()
    try:
        doc = _load(httpd, addr, "d", 9, 300)
        ok, _n, err = gen.push(addr, doc, 8, 20.0)
        assert ok, err
        first = _get(addr, "d")
        sess = httpd.store.scheduler.banks[0].sessions["d"]
        ol = httpd.store.docs["d"]
        assert first[2] == [list(x) for x in
                            ol.cg.local_to_remote_frontier(sess.frontier)]
        memo = httpd.store.scheduler._read_frontiers
        named = memo["d"]
        assert _get(addr, "d")[2] == first[2]
        assert memo["d"] is named               # not named again
        assert _wait_for(lambda: _get_counts(httpd).get("at_tip", 0) >= 1)
    finally:
        _stop(httpd)


def test_readers_racing_the_flush_worker_never_see_a_torn_row():
    """More reader threads than cores against one writer and the paced
    flush worker, the interpreter switching every 10 us: every body is
    one state of the page's linear history (a row, its length and its
    frontier from ONE commit), never older than the last edit
    acknowledged before the GET was sent."""
    import copy
    import os
    import sys
    httpd, addr = _serve()
    try:
        doc = _load(httpd, addr, "r", 71, 400)
        index = {bytes(doc.text()): 0}      # text -> place in the history
        acked = [0]
        stop = threading.Event()
        errors = []

        def reader():
            while not stop.is_set():
                floor = acked[0]
                status, body, _f = _get(addr, "r")
                at = index.get(body)
                if status != 200 or at is None or at < floor:
                    errors.append((status, at, floor, len(body)))
                    return

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        readers = [threading.Thread(target=reader, daemon=True)
                   for _ in range(min(2 * (os.cpu_count() or 4), 12))]
        try:
            for t in readers:
                t.start()
            deadline = time.monotonic() + 4.0
            while time.monotonic() < deadline and not errors:
                w = doc.next_writer
                after = copy.deepcopy(doc)
                ops = after.next_push(w, 8)
                after.acknowledge(w, copy.deepcopy(ops), None)
                # a GET may see the edit before its writer sees the ack
                index[bytes(after.text())] = acked[0] + 1
                ok, _n, err = gen.push(addr, doc, 8, 20.0)
                assert ok, err
                assert doc.text() == after.text()
                acked[0] += 1
                time.sleep(0.005)
        finally:
            stop.set()
            for t in readers:
                t.join(20)
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in readers)
        assert errors == []
        assert acked[0] >= 5
        assert _totals(httpd)["reads_from_host"] == 0
        assert _wait_for(
            lambda: _get_counts(httpd).get("device", 0) >= len(readers))
    finally:
        _stop(httpd)


# ---- any text ----------------------------------------------------------------------

TEXTS = {
    "ascii": "plain text\n",
    "latin": "héllo wörld ß",
    "cjk_and_astral": "漢字 \U0001f600 \U00010348 end",
    "nul_and_controls": "a\x00b\tc\x7f",
}


@pytest.mark.parametrize("name", sorted(TEXTS))
def test_any_text_comes_back_as_the_host_encodes_it(name):
    httpd, addr = _serve()
    try:
        text = TEXTS[name]
        v = _edit(addr, "t", "a", None, [_ins(0, text)])
        httpd.store.scheduler.drain()         # the session is built
        v = _edit(addr, "t", "a", v, [_ins(len(text), text[::-1])])
        status, body, frontier = _get(addr, "t")
        assert status == 200
        assert body == (text + text[::-1]).encode("utf8")
        assert body == _host_text(httpd, "t")
        assert frontier == [list(x) for x in v]
        assert _totals(httpd)["reads_from_device"] == 1
    finally:
        _stop(httpd)


def test_an_emptied_document_reads_empty_from_its_session():
    httpd, addr = _serve()
    try:
        v = _edit(addr, "e", "a", None, [_ins(0, "gone")])
        httpd.store.scheduler.drain()
        _edit(addr, "e", "a", v, [{"kind": "del", "start": 0, "end": 4}])
        status, body, _f = _get(addr, "e")
        assert (status, body) == (200, b"")
        assert _host_text(httpd, "e") == b""
        t = _totals(httpd)
        assert (t["reads_from_device"], t["reads_from_host"]) == (1, 0)
    finally:
        _stop(httpd)


def test_a_document_without_a_session_is_the_hosts_and_is_counted():
    httpd, addr = _serve()
    try:
        status, body, frontier = _get(addr, "never-seen")
        assert (status, body, frontier) == (200, b"", [])
        assert _totals(httpd)["reads_from_host"] == 1
        assert _wait_for(lambda: _get_counts(httpd).get("host") == 1)
        assert _row_count(httpd, "get.checkout") == 1
    finally:
        _stop(httpd)


@pytest.mark.parametrize("pump", ["running", "stopped"])
def test_a_session_that_grows_a_class_mid_run_reads_right(pump):
    httpd, addr = _serve()
    sched = httpd.store.scheduler
    try:
        doc = _load(httpd, addr, "g", 21, 300)
        sess = sched.banks[0].sessions["g"]
        cap = sess.cap
        if pump == "stopped":
            sched.stop_pump(drain=True)
        hunks = [corpus.Typist(np.random.default_rng([21, 1, w]),
                               {"paste_every": 1, "paste_chars": [96, 160]})
                 for w in range(2)]
        doc.typists = hunks
        while len(doc.text()) <= cap:
            ok, _n, err = gen.push(addr, doc, 8, 20.0)
            assert ok, err
            status, body, frontier = _get(addr, "g")
            assert status == 200 and body == doc.text()
            assert _covers(frontier, doc)
        sess = sched.banks[0].sessions["g"]
        assert sess.cap > cap and sess.resyncs == 0     # grown, not rebuilt
        assert body == _host_text(httpd, "g")
        assert _totals(httpd)["reads_from_host"] == 0
    finally:
        _stop(httpd)


# ---- nothing compiles a length -------------------------------------------------------

def test_forty_documents_of_forty_lengths_compile_no_read_program():
    """`FusedDocSession.text()` sliced the row on the device: one
    executable a document length. The fetch is the whole row, cut on
    the host: at most one program a capacity class, and here none."""
    httpd, addr = _serve()
    try:
        docs = [_load(httpd, addr, f"l{i:02d}", 31, 120 + 17 * i, index=i)
                for i in range(40)]
        sessions = httpd.store.scheduler.banks[0].sessions
        assert len({sessions[d.id].doc_len for d in docs}) >= 30
        classes = {sessions[d.id].cap for d in docs}
        _get(addr, docs[0].id)
        c0 = COMPILE_STATS.snapshot()
        for d in docs:
            status, body, _f = _get(addr, d.id)
            assert status == 200 and body == d.text()
            assert sessions[d.id].text().encode() == body
        compiled = COMPILE_STATS.delta(COMPILE_STATS.snapshot(), c0)
        assert compiled["compiles"] <= len(classes)
        assert compiled["compiles"] == 0
        assert _totals(httpd)["reads_from_device"] == 41
    finally:
        _stop(httpd)


# ---- four shards ------------------------------------------------------------------------

def test_four_shards_on_four_devices_read_from_their_own_chips():
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    httpd, addr = _serve(shards=4)
    sched = httpd.store.scheduler
    try:
        docs = [_load(httpd, addr, f"s{i}", 41, 250 + 30 * i, index=i)
                for i in range(12)]
        shards = {sched.router.shard_of(d.id) for d in docs}
        assert len(shards) == 4
        rng = np.random.default_rng(41)
        gets = 0
        for _ in range(80):
            doc = docs[int(rng.integers(len(docs)))]
            if rng.random() < 0.4:
                ok, _n, err = gen.push(addr, doc, 8, 20.0)
                assert ok, err
            else:
                status, body, frontier = _get(addr, doc.id)
                gets += 1
                assert status == 200 and body == doc.text()
                assert _covers(frontier, doc)
        for d in docs:
            bank = sched.banks[sched.router.shard_of(d.id)]
            assert bank.sessions[d.id].docs.devices() == {bank.device}
        t = _totals(httpd)
        assert t["reads_from_host"] == 0
        assert t["reads_from_device"] == gets
        assert all(s["reads_from_device"] for s in sched.metrics.shard)
    finally:
        _stop(httpd)


# ---- mesh windows ---------------------------------------------------------------------------

def test_reads_under_mesh_windows_on_four_devices(monkeypatch):
    """`mesh_window=True` (host4-mixed's scheduler): the pump takes the
    items for ONE program over the four chips, so a read finds its
    bucket taken and waits on the window in flight (`_inflight`), or
    flushes the tail itself where none is. Every body is the reference
    and none the host's, with a window held open across a read too."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    httpd, addr = _serve(shards=4, mesh_window=True, flush_docs=2)
    sched = httpd.store.scheduler
    entered, release = threading.Event(), threading.Event()
    try:
        assert sched.mesh_window
        docs = [_load(httpd, addr, f"m{i}", 43, 250 + 30 * i, index=i)
                for i in range(8)]
        base = sched.metrics_json()
        rng = np.random.default_rng(43)
        gets = 0
        for _ in range(60):
            doc = docs[int(rng.integers(len(docs)))]
            if rng.random() < 0.5:
                ok, _n, err = gen.push(addr, doc, 8, 20.0)
                assert ok, err
            else:
                status, body, frontier = _get(addr, doc.id)
                gets += 1
                assert status == 200 and body == doc.text()
                assert _covers(frontier, doc)
        # a window held open on the device while a read comes (the
        # loop's own windows over first: the next one is this push's)
        sched.drain()
        from diamond_types_tpu.parallel import mesh as mesh_mod
        real = mesh_mod.mesh_fused_replay

        def slow(*a, **kw):
            entered.set()
            assert release.wait(20)
            return real(*a, **kw)

        monkeypatch.setattr(mesh_mod, "mesh_fused_replay", slow)
        doc = docs[0]
        ok, _n, err = gen.push(addr, doc, 8, 20.0)
        assert ok, err
        assert entered.wait(20)
        out = {}
        t = threading.Thread(target=lambda: out.update(
            got=_get(addr, doc.id)), daemon=True)
        t.start()
        time.sleep(0.3)
        assert t.is_alive()             # no stale row, no host answer
        release.set()
        t.join(20)
        gets += 1
        status, body, frontier = out["got"]
        assert status == 200 and body == doc.text()
        assert _covers(frontier, doc)
        end = sched.metrics_json()
        assert end["totals"]["reads_from_host"] \
            == base["totals"]["reads_from_host"]
        assert end["totals"]["reads_from_device"] \
            - base["totals"]["reads_from_device"] == gets
        assert end["totals"]["device_errors"] == 0
        assert end["totals"]["host_fallbacks"] == 0
    finally:
        release.set()
        _stop(httpd)


# ---- the library's read is the same read ------------------------------------------------------

def test_text_is_read_tip_and_syncs_a_session_that_is_behind():
    httpd, addr = _serve()
    sched = httpd.store.scheduler
    try:
        doc = _load(httpd, addr, "x", 51, 300)
        sched.stop_pump(drain=True)
        ok, _n, err = gen.push(addr, doc, 8, 20.0)
        assert ok, err
        base = _totals(httpd)
        assert sched.text("x").encode() == doc.text()
        end = _totals(httpd)
        assert end["reads_from_device"] - base["reads_from_device"] == 1
        assert end["reads_from_host"] == base["reads_from_host"]
        assert sched.text("no-such-doc") == ""
        assert _totals(httpd)["reads_from_host"] \
            == base["reads_from_host"] + 1
    finally:
        _stop(httpd)


def test_a_flush_that_raises_fails_the_read_loudly(monkeypatch):
    """No stale row and no quiet host answer for a device that fails."""
    httpd, addr = _serve()
    sched = httpd.store.scheduler
    try:
        doc = _load(httpd, addr, "f", 61, 300)
        sched.stop_pump(drain=True)
        ok, _n, err = gen.push(addr, doc, 8, 20.0)
        assert ok, err

        def boom(sessions, plans):
            raise RuntimeError("the device said no")

        monkeypatch.setattr(ff, "fused_replay", boom)
        base = _totals(httpd)
        status, _body, _f = _get(addr, "f")
        assert status == 500
        end = _totals(httpd)
        assert end["device_errors"] == base["device_errors"] + 1
        assert end["reads_from_host"] == base["reads_from_host"]
        assert end["reads_from_device"] == base["reads_from_device"]
        monkeypatch.undo()
        status, body, _f = _get(addr, "f")
        assert status == 200 and body == doc.text()
    finally:
        _stop(httpd)
