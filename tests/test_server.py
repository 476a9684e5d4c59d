"""In-process server/client sync test (reference: wiki demo, SURVEY.md L8)."""

import threading

from diamond_types_tpu.tools.server import SyncClient, serve


def test_two_clients_collaborate(tmp_path):
    httpd = serve(port=0, data_dir=str(tmp_path))
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        base = f"http://127.0.0.1:{port}"
        a = SyncClient(base, "note", "alice")
        b = SyncClient(base, "note", "bob")

        a.insert(0, "Hello from alice. ")
        a.sync()
        b.pull()
        assert b.text() == "Hello from alice. "

        # Concurrent edits.
        b.insert(len(b.text()), "And bob!")
        a.insert(0, ">> ")
        a.sync()
        b.sync()
        a.sync()
        assert a.text() == b.text()
        assert "And bob!" in a.text() and ">> " in a.text()

        # Server persisted a .dt file readable on its own.
        httpd.RequestHandlerClass.store.flush(force=True)
        from diamond_types_tpu.encoding.decode import load_oplog
        with open(tmp_path / "note.dt", "rb") as f:
            ol = load_oplog(f.read())
        assert ol.checkout_tip().snapshot() == a.text()
    finally:
        httpd.shutdown()
        httpd.server_close()


def _api(base, doc, action, body):
    import json
    import urllib.request
    req = urllib.request.Request(f"{base}/doc/{doc}/{action}",
                                 data=json.dumps(body).encode("utf8"))
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


class DumbClient:
    """Python simulation of the browser editor's loop (web_assets.py):
    positional edits at a remembered version + OT traversal catch-up.
    No CRDT on the client at all."""

    def __init__(self, base, doc, agent):
        import json
        import urllib.request
        self.base, self.doc, self.agent = base, doc, agent
        with urllib.request.urlopen(f"{base}/doc/{doc}/state") as r:
            st = json.loads(r.read())
        self.text, self.version = st["text"], st["version"]

    def edit(self, ops):
        # apply locally the way a textarea already shows the user's typing
        for op in ops:
            if op["kind"] == "ins":
                p = op["pos"]
                self.text = self.text[:p] + op["text"] + self.text[p:]
            else:
                self.text = self.text[:op["start"]] + self.text[op["end"]:]
        r = _api(self.base, self.doc, "edit",
                 {"agent": self.agent, "version": self.version, "ops": ops})
        self.version = r["version"]

    def sync(self):
        from diamond_types_tpu.text import ot
        r = _api(self.base, self.doc, "changes", {"version": self.version})
        self.text = ot.apply(self.text, r["op"])
        self.version = r["version"]


def test_browser_dumb_clients_converge(tmp_path):
    """Two positional browser clients + one CRDT client, concurrent edits,
    everyone converges (reference: wiki demo end-user edit loop)."""
    httpd = serve(port=0, data_dir=str(tmp_path))
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        base = f"http://127.0.0.1:{port}"
        w1 = DumbClient(base, "page", "web-one")
        w1.edit([{"kind": "ins", "pos": 0, "text": "The quick brown fox"}])

        w2 = DumbClient(base, "page", "web-two")
        w2.sync()
        assert w2.text == "The quick brown fox"

        # Concurrent: w1 edits the head, w2 the tail, crdt client the middle.
        c = SyncClient(base, "page", "carol")
        c.pull()
        w1.edit([{"kind": "ins", "pos": 0, "text": ">> "}])
        w2.edit([{"kind": "del", "start": 10, "end": 16},
                 {"kind": "ins", "pos": 10, "text": "red"}])
        c.insert(4, "very ")
        c.sync()
        for cl in (w1, w2):
            cl.sync()
        c.sync()
        w1.sync()
        assert w1.text == w2.text == c.text()
        assert w1.text.startswith(">> ")
        assert "red" in w1.text and "very" in w1.text
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_browser_pages_and_graph_endpoints(tmp_path):
    import json
    import urllib.request
    httpd = serve(port=0, data_dir=str(tmp_path))
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        base = f"http://127.0.0.1:{port}"
        w = DumbClient(base, "g", "web")
        w.edit([{"kind": "ins", "pos": 0, "text": "hello"}])
        w.edit([{"kind": "ins", "pos": 5, "text": " world"}])

        for page in ("/", "/edit/g", "/vis/g"):
            with urllib.request.urlopen(base + page) as r:
                html = r.read().decode("utf8")
            assert "<title>" in html or "<h1>" in html

        with urllib.request.urlopen(base + "/doc/g/graph") as r:
            g = json.loads(r.read())
        assert g["runs"] and g["runs"][0]["agent"] == "web"
        last = g["runs"][-1]["end"] - 1
        at = _api(base, "g", "at", {"lv": last})
        assert at["text"] == "hello world"
        at0 = _api(base, "g", "at", {"lv": 4})
        assert at0["text"] == "hello"
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_edit_endpoint_rejects_bad_ops(tmp_path):
    import json
    import urllib.error
    import urllib.request
    httpd = serve(port=0, data_dir=str(tmp_path))
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        base = f"http://127.0.0.1:{port}"
        w = DumbClient(base, "v", "web")
        w.edit([{"kind": "ins", "pos": 0, "text": "hello"}])
        for bad in ([{"kind": "ins", "pos": 0, "text": ""}],       # empty
                    [{"kind": "ins", "pos": 99, "text": "x"}],     # range
                    [{"kind": "del", "start": 2, "end": 2}],       # empty
                    [{"kind": "del", "start": 0, "end": 99}],      # range
                    [{"kind": "nop"}]):                            # kind
            try:
                _api(base, "v", "edit",
                     {"agent": "web", "version": w.version, "ops": bad})
                raise AssertionError(f"accepted bad op {bad}")
            except urllib.error.HTTPError as e:
                assert e.code == 400
        # a batch failing validation must not half-apply: doc unchanged
        try:
            _api(base, "v", "edit", {"agent": "web", "version": w.version,
                 "ops": [{"kind": "ins", "pos": 0, "text": "A"},
                         {"kind": "del", "start": 50, "end": 60}]})
            raise AssertionError("accepted half-bad batch")
        except urllib.error.HTTPError as e:
            assert e.code == 400
        import urllib.request as u
        with u.urlopen(f"{base}/doc/v") as r:
            assert r.read().decode() == "hello"

        # Coerced-validation hole (ADVICE r2): a float pos passes int()
        # validation but must not reach add_insert_at unconverted -> 400.
        for bad in ([{"kind": "ins", "pos": 1.5, "text": "x"}],
                    [{"kind": "ins", "pos": "2", "text": "x"}],
                    [{"kind": "del", "start": 0.5, "end": 2}]):
            try:
                _api(base, "v", "edit",
                     {"agent": "web", "version": w.version, "ops": bad})
                raise AssertionError(f"accepted non-int op {bad}")
            except urllib.error.HTTPError as e:
                assert e.code == 400
        # Malformed bodies on browser endpoints -> 400, not a closed
        # connection / handler crash (ADVICE r2).
        for action, payload in (
                ("at", {}),                      # missing lv
                ("at", {"lv": "zero"}),          # non-numeric lv
                ("at", {"lv": 10**9}),           # out of range lv
                ("at", {"lv": -1}),              # negative lv
                ("edit", {"agent": "web"}),      # missing ops
                ("edit", {"agent": 7, "version": [],
                          "ops": [{"kind": "ins", "pos": 0, "text": "x"}]}),
                ("changes", {"wait": "soon"})):  # non-numeric wait
            try:
                _api(base, "v", action, payload)
                raise AssertionError(f"accepted bad {action} {payload}")
            except urllib.error.HTTPError as e:
                assert e.code == 400
        # Raw non-JSON body -> 400 as well.
        req = urllib.request.Request(f"{base}/doc/v/at", data=b"not json")
        try:
            urllib.request.urlopen(req)
            raise AssertionError("accepted non-JSON body")
        except urllib.error.HTTPError as e:
            assert e.code == 400
        with u.urlopen(f"{base}/doc/v") as r:
            assert r.read().decode() == "hello"
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_flush_races_concurrent_edits(tmp_path):
    """Whatever the autosave reads of a Python oplog it must read under
    the store lock (the mirror's sync(), the whole encode of a document
    with no mirror); a native mirror is encoded outside it, under the
    mirror's own lock. Hammer /edit from two threads while forcing
    flushes; the persisted .dt must always load (ADVICE r2 medium:
    flush() used to encode the live oplog outside the lock)."""
    from diamond_types_tpu.encoding.decode import load_oplog
    httpd = serve(port=0, data_dir=str(tmp_path))
    store = httpd.RequestHandlerClass.store
    store.save_interval = 0.0  # every flush() call is "due"
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        base = f"http://127.0.0.1:{port}"
        errs = []

        def hammer(name):
            try:
                w = DumbClient(base, "r", name)
                for i in range(40):
                    w.edit([{"kind": "ins", "pos": 0, "text": f"{name}{i} "}])
                    w.sync()
            except Exception as e:  # pragma: no cover
                errs.append(e)

        ts = [threading.Thread(target=hammer, args=(n,))
              for n in ("alice", "bob")]
        for th in ts:
            th.start()
        for th in ts:
            th.join()
        assert not errs
        store.flush(force=True)
        ol = load_oplog((tmp_path / "r.dt").read_bytes())
        assert len(ol) > 0 and "alice0" in ol.checkout_tip().snapshot()
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_flush_encode_failure_backoff(tmp_path, capsys):
    """A doc whose encode persistently fails must back off exponentially
    instead of spamming a full traceback + O(doc) encode on every pass
    (ADVICE r4); a new edit cuts the backoff, a success clears it."""
    from diamond_types_tpu.tools.server import DocStore

    store = DocStore(data_dir=str(tmp_path), save_interval=0.0)

    class Bomb:
        """Stands in for an OpLog poisoned before input validation."""
        armed = True

    real_encode = None
    import diamond_types_tpu.tools.server as srv
    real_encode = srv.encode_oplog

    def fake_encode(ol, *a, **k):
        if isinstance(ol, Bomb) and ol.armed:
            raise ValueError("poisoned")
        if isinstance(ol, Bomb):
            return b"ok"
        return real_encode(ol, *a, **k)

    srv.encode_oplog = fake_encode
    try:
        bomb = Bomb()
        store.docs["bad"] = bomb
        store.mark_dirty("bad")
        for _ in range(6):
            store.flush()
        # backoff engaged: the doc is dirty with a FUTURE due time and
        # far fewer than 6 tracebacks were printed
        assert store.flush_failures["bad"] >= 1
        assert store.dirty["bad"] > __import__("time").monotonic()
        err = capsys.readouterr().err
        assert err.count("Traceback") == 1      # first failure only
        fails_before = store.flush_failures["bad"]
        # a new edit cuts the standing backoff -> prompt retry
        store.mark_dirty("bad")
        store.flush()
        assert store.flush_failures["bad"] == fails_before + 1
        # and a success clears the failure state entirely
        bomb.armed = False
        store.mark_dirty("bad")
        store.flush()
        assert "bad" not in store.flush_failures
        assert (tmp_path / "bad.dt").read_bytes() == b"ok"
    finally:
        srv.encode_oplog = real_encode


def test_flush_write_failure_remarks_dirty(tmp_path, capsys):
    """A disk-write failure (ENOSPC/EIO) on one doc must not abort the
    write loop or silently drop the already-cleared dirty flags — the
    failing doc re-enters the backoff cycle and later docs still write."""
    import diamond_types_tpu.tools.server as srv
    from diamond_types_tpu.tools.server import DocStore
    from diamond_types_tpu.text.oplog import OpLog

    store = DocStore(data_dir=str(tmp_path), save_interval=0.0)
    for name, text in (("aa", "first"), ("bb", "second")):
        ol = OpLog()
        ag = ol.get_or_create_agent_id("u")
        ol.add_insert_at(ag, [], 0, text)
        store.docs[name] = ol
        store.mark_dirty(name)

    real_replace = srv.os.replace
    def flaky_replace(src, dst):
        if dst.endswith("aa.dt"):
            raise OSError(28, "No space left on device")
        return real_replace(src, dst)

    srv.os.replace = flaky_replace
    try:
        store.flush()
        # bb still persisted despite aa's write failure; aa is re-dirty
        # with backoff and counted
        assert (tmp_path / "bb.dt").exists()
        assert not (tmp_path / "aa.dt").exists()
        assert store.flush_failures["aa"] >= 1
        assert "aa" in store.dirty and "bb" not in store.dirty
        assert "write failed" in capsys.readouterr().err
    finally:
        srv.os.replace = real_replace
    # recovery: disk "freed", edit cuts the backoff, write succeeds
    store.mark_dirty("aa")
    store.flush()
    assert (tmp_path / "aa.dt").exists()
    assert "aa" not in store.flush_failures


def test_changes_long_poll_streams_edits(tmp_path):
    """A waiting /changes request returns as soon as another client edits
    (braid-subscription equivalent of the reference wiki streaming)."""
    import time as _time
    httpd = serve(port=0, data_dir=str(tmp_path))
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        base = f"http://127.0.0.1:{port}"
        w = DumbClient(base, "lp", "writer")
        w.edit([{"kind": "ins", "pos": 0, "text": "start"}])
        r = DumbClient(base, "lp", "reader")
        r.sync()
        result = {}

        def waiter():
            t0 = _time.monotonic()
            resp = _api(base, "lp", "changes",
                        {"version": r.version, "wait": 10})
            result["latency"] = _time.monotonic() - t0
            result["resp"] = resp

        th = threading.Thread(target=waiter)
        th.start()
        _time.sleep(0.4)                 # waiter is now parked
        w.edit([{"kind": "ins", "pos": 5, "text": "!"}])
        th.join(timeout=8)
        assert not th.is_alive(), "long-poll never woke"
        assert result["latency"] < 5, "woke by timeout, not by notify"
        from diamond_types_tpu.text import ot
        assert ot.apply(r.text, result["resp"]["op"]) == "start!"

        # and an idle wait times out quickly with an empty traversal
        r.sync()
        t0 = _time.monotonic()
        resp = _api(base, "lp", "changes", {"version": r.version,
                                            "wait": 0.5})
        assert resp["op"] == [] and _time.monotonic() - t0 < 3
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_history_strip_endpoint(monkeypatch):
    """/doc/{id}/history returns snapshots oldest-first. DT_SERVER_DEVICE
    routes the whole strip through ONE batched texts_at_versions call
    (tests run on the CPU backend; a server defaults to host checkouts
    so a request handler never initialises a JAX backend)."""
    import json
    import threading
    import urllib.request
    from diamond_types_tpu.tools.server import serve

    monkeypatch.setenv("DT_SERVER_DEVICE", "1")
    srv = serve(port=0, data_dir=None)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        base = f"http://127.0.0.1:{port}"
        # build a concurrent doc via two pushes
        from diamond_types_tpu import OpLog
        from diamond_types_tpu.encoding.encode import ENCODE_FULL, encode_oplog
        ol = OpLog()
        a = ol.get_or_create_agent_id("a")
        b = ol.get_or_create_agent_id("b")
        v = [ol.add_insert_at(a, [], 0, "base text here")]
        ol.add_insert_at(a, v, 0, "A1 ")
        ol.add_insert_at(b, v, 14, " B1")
        blob = encode_oplog(ol, ENCODE_FULL)
        req = urllib.request.Request(base + "/doc/h1/push", data=blob)
        urllib.request.urlopen(req).read()

        req = urllib.request.Request(
            base + "/doc/h1/history",
            data=json.dumps({"n": 8}).encode("utf8"))
        out = json.loads(urllib.request.urlopen(req).read())
        snaps = out["snapshots"]
        assert len(snaps) >= 2
        assert snaps[-1]["text"] == ol.checkout_tip().snapshot()
        lvs = [s["lv"] for s in snaps]
        assert lvs == sorted(lvs)
        # every snapshot is a real historical doc
        for s in snaps:
            f = ol.cg.graph.find_dominators([s["lv"]])
            # strip versions are entry frontiers, not single-lv dominators;
            # at minimum the text matches SOME consistent version: check
            # the final one exactly (above) and types here
            assert isinstance(s["text"], str)
    finally:
        srv.shutdown()
        srv.server_close()


def test_history_strip_host_path():
    """Default (no DT_SERVER_DEVICE): host-checkout sampling, including
    the merged tip for concurrent histories."""
    from diamond_types_tpu import OpLog
    from diamond_types_tpu.tools.server import doc_history_strip
    ol = OpLog()
    a = ol.get_or_create_agent_id("a")
    b = ol.get_or_create_agent_id("b")
    v = [ol.add_insert_at(a, [], 0, "0123456789")]
    ol.add_insert_at(a, v, 0, "A")
    ol.add_insert_at(b, v, 10, "B")
    snaps = doc_history_strip(ol, 6)
    assert len(snaps) >= 2
    assert snaps[-1]["text"] == ol.checkout_tip().snapshot()
    assert [s["lv"] for s in snaps] == sorted(s["lv"] for s in snaps)


class _CrdtPeer:
    """A minimal Python twin of the in-browser CRDT peer (web_assets.
    CRDT_HTML): pushes ORIGINAL unit ops with explicit parent versions,
    pulls missing ops by summary. Exercises /doc/{id}/ops end to end."""

    def __init__(self, base, doc, name):
        import urllib.request
        self._rq = urllib.request
        self.base, self.doc, self.name = base, doc, name
        self.seq = 0
        self.frontier = []         # [[agent, seq]...]
        self.pending = []
        self.known = {}            # agent -> next seq

    def edit_ins(self, pos, text):
        for i, ch in enumerate(text):
            op = {"agent": self.name, "seq": self.seq,
                  "parents": self.frontier, "kind": "ins",
                  "pos": pos + i, "content": ch}
            self.frontier = [[self.name, self.seq]]
            self.seq += 1
            self.pending.append(op)
        self.known[self.name] = self.seq

    def edit_del(self, pos, n):
        for _ in range(n):
            op = {"agent": self.name, "seq": self.seq,
                  "parents": self.frontier, "kind": "del",
                  "pos": pos, "len": 1}
            self.frontier = [[self.name, self.seq]]
            self.seq += 1
            self.pending.append(op)
        self.known[self.name] = self.seq

    def sync(self):
        import json
        body = json.dumps({"have": self.known, "push": self.pending})
        req = self._rq.Request(f"{self.base}/doc/{self.doc}/ops",
                               data=body.encode("utf8"))
        out = json.loads(self._rq.urlopen(req).read())
        self.pending = []
        for row in out["ops"]:
            units = len(row.get("content") or "") if row["kind"] == "ins" \
                else row["len"]
            nxt = self.known.get(row["agent"], 0)
            self.known[row["agent"]] = max(nxt, row["seq"] + units)
        f = {a: s for a, s in self.frontier}
        for a, s in out["version"]:
            if a != self.name:
                f[a] = max(f.get(a, -1), s)
        self.frontier = [[a, s] for a, s in f.items()]
        return out


def _boot_server(tmp_path=None):
    import threading
    from diamond_types_tpu.tools.server import serve
    srv = serve(port=0, data_dir=None)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, f"http://127.0.0.1:{port}"


def test_crdt_peer_protocol_concurrent():
    """Two peers edit OFFLINE from a shared version, then sync: the
    server folds their original ops through the CRDT; pulled rows carry
    explicit parents so a browser engine can merge locally."""
    srv, base = _boot_server()
    try:
        p1 = _CrdtPeer(base, "cdoc", "anna")
        p2 = _CrdtPeer(base, "cdoc", "bert")
        p1.edit_ins(0, "hello world")
        p1.sync()
        p2.sync()                      # bert pulls anna's ops
        # both edit concurrently (offline) at the same gap
        p1.edit_ins(5, "-A")
        p2.edit_ins(5, "-B")
        p1.edit_del(0, 1)              # anna also deletes 'h'
        p1.sync()
        p2.sync()
        p1.sync()
        # server text is the converged CRDT result
        store = srv.RequestHandlerClass.store
        ol = store.get("cdoc")
        text = ol.checkout_tip().snapshot()
        assert "-A" in text and "-B" in text
        assert text.startswith("ello") and text.endswith("world")
        # a fresh peer pulling everything sees rows that rebuild the doc
        p3 = _CrdtPeer(base, "cdoc", "cara")
        out = p3.sync()
        total_units = sum(len(r.get("content") or "") if r["kind"] == "ins"
                          else r["len"] for r in out["ops"])
        assert total_units == len(ol)
        # idempotent re-push: replaying anna's first op is a no-op
        p4 = _CrdtPeer(base, "cdoc", "anna")
        p4.seq = 0
        p4.edit_ins(0, "h")            # same (anna, 0) id
        p4.pending[0]["parents"] = []
        p4.sync()
        assert ol.checkout_tip().snapshot() == text
    finally:
        srv.shutdown()
        srv.server_close()


def test_crdt_peer_offline_convergence_order_free():
    """Sync order must not matter (op exchange is causal + idempotent)."""
    srv, base = _boot_server()
    try:
        a = _CrdtPeer(base, "odoc", "aa")
        b = _CrdtPeer(base, "odoc", "bb")
        a.edit_ins(0, "base ")
        a.sync()
        b.sync()
        a.edit_ins(5, "AAA")
        b.edit_ins(5, "BBB")
        b.sync()                       # reversed order vs previous test
        a.sync()
        b.sync()
        store = srv.RequestHandlerClass.store
        text = store.get("odoc").checkout_tip().snapshot()
        assert text == "base AAABBB" or text == "base BBBAAA"
        # deterministic: agent 'aa' < 'bb' -> AAA first
        assert text == "base AAABBB"
    finally:
        srv.shutdown()
        srv.server_close()


def test_crdt_ops_endpoint_rejects_out_of_range(tmp_path):
    """ADVICE r3 (high): /doc/{id}/ops must validate pos/len against the
    document AT THE OP'S PARENTS before mutating — an accepted
    out-of-range op is persisted and poisons every future merge."""
    import json
    import urllib.error
    import urllib.request
    srv, base = _boot_server()
    try:
        p = _CrdtPeer(base, "vdoc", "anna")
        p.edit_ins(0, "hello")
        p.sync()
        store = srv.RequestHandlerClass.store
        ol = store.get("vdoc")
        assert ol.checkout_tip().snapshot() == "hello"
        frontier = [["anna", 4]]

        def push(op):
            body = json.dumps({"have": {}, "push": [op]}).encode("utf8")
            req = urllib.request.Request(base + "/doc/vdoc/ops", data=body)
            return urllib.request.urlopen(req)

        bad = [
            {"agent": "evil", "seq": 0, "parents": frontier,
             "kind": "ins", "pos": 999, "content": "X"},      # ins > len
            {"agent": "evil", "seq": 0, "parents": frontier,
             "kind": "ins", "pos": -1, "content": "X"},       # negative
            {"agent": "evil", "seq": 0, "parents": frontier,
             "kind": "ins", "pos": 0, "content": ""},         # empty ins
            {"agent": "evil", "seq": 0, "parents": frontier,
             "kind": "del", "pos": 3, "len": 99},             # del > len
            {"agent": "evil", "seq": 0, "parents": frontier,
             "kind": "del", "pos": 0, "len": 0},              # empty del
            {"agent": "evil", "seq": 0, "parents": frontier,
             "kind": "del", "pos": -2, "len": 1},             # negative
        ]
        for op in bad:
            try:
                push(op)
                raise AssertionError(f"accepted bad op {op}")
            except urllib.error.HTTPError as e:
                assert e.code == 400, op
        # nothing was persisted; the doc still merges cleanly
        assert ol.checkout_tip().snapshot() == "hello"
        # boundary ops ARE valid: ins at len, del of last char
        push({"agent": "evil", "seq": 0, "parents": frontier,
              "kind": "ins", "pos": 5, "content": "!"})
        push({"agent": "evil", "seq": 1, "parents": [["evil", 0]],
              "kind": "del", "pos": 5, "len": 1})
        assert ol.checkout_tip().snapshot() == "hello"
    finally:
        srv.shutdown()
        srv.server_close()


def test_crdt_ops_minimal_frontier_stored(tmp_path):
    """ADVICE r3 (low): clients track frontiers as per-agent max-seq maps,
    so pushed parents may include dominated heads; the server must store
    the MINIMAL frontier (reference invariant: frontiers are minimal)."""
    import json
    import urllib.request
    srv, base = _boot_server()
    try:
        a = _CrdtPeer(base, "mdoc", "aa")
        a.edit_ins(0, "xy")
        a.sync()
        b = _CrdtPeer(base, "mdoc", "bb")
        b.sync()
        b.edit_ins(2, "z")   # bb's op builds on aa's tip
        b.sync()
        # now push an op whose parents list BOTH aa's tip (dominated by
        # bb's op) and bb's op — the max-seq-map shape from the advice
        body = json.dumps({"have": {}, "push": [
            {"agent": "cc", "seq": 0,
             "parents": [["aa", 1], ["bb", 0]],
             "kind": "ins", "pos": 3, "content": "!"}]}).encode("utf8")
        urllib.request.urlopen(
            urllib.request.Request(base + "/doc/mdoc/ops", data=body))
        store = srv.RequestHandlerClass.store
        ol = store.get("mdoc")
        lv = ol.cg.remote_to_local_frontier([("cc", 0)])[0]
        parents = ol.cg.graph.parents_at(lv)
        # minimal: only bb's op (aa's tip is its ancestor)
        assert list(parents) == \
            list(ol.cg.remote_to_local_frontier([("bb", 0)])), \
            f"non-minimal parents stored: {parents}"
        assert ol.checkout_tip().snapshot() == "xyz!"
    finally:
        srv.shutdown()
        srv.server_close()


def test_crdt_ops_rejects_lone_surrogates():
    """JSON delivers lone surrogates; accepting one poisons every later
    encode (utf-8 wire / utf-32 arena) and breaks the flush pass."""
    import json
    import urllib.error
    import urllib.request
    srv, base = _boot_server()
    try:
        def push(op):
            body = json.dumps({"push": [op]}).encode("utf8",
                                                     "surrogatepass")
            req = urllib.request.Request(base + "/doc/s/ops", data=body)
            return urllib.request.urlopen(req)

        push({"agent": "ok", "seq": 0, "parents": [],
              "kind": "ins", "pos": 0, "content": "hi"})
        for op in (
            {"agent": "evil", "seq": 0, "parents": [["ok", 1]],
             "kind": "ins", "pos": 0, "content": "\ud800"},
            {"agent": "ev\udfffil", "seq": 0, "parents": [["ok", 1]],
             "kind": "ins", "pos": 0, "content": "x"},
        ):
            try:
                push(op)
                raise AssertionError(f"accepted surrogate op {op!r}")
            except urllib.error.HTTPError as e:
                assert e.code == 400
        store = srv.RequestHandlerClass.store
        ol = store.get("s")
        # the doc still encodes (flush path) and reads back
        from diamond_types_tpu.encoding.encode import (ENCODE_FULL,
                                                       encode_oplog)
        encode_oplog(ol, ENCODE_FULL)
        assert ol.checkout_tip().snapshot() == "hi"
    finally:
        srv.shutdown()
        srv.server_close()


def test_dumb_client_astral_positions(tmp_path):
    """Browser endpoints speak CODE-POINT positions (the fixed JS clients
    diff over Array.from; raw UTF-16 indices would drift past astral
    chars). The Python DumbClient has code-point semantics natively —
    this pins the contract end to end across /edit + /changes with
    astral content."""
    import threading
    from diamond_types_tpu.tools.server import serve
    httpd = serve(port=0, data_dir=str(tmp_path))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        w1 = DumbClient(base, "astro", "web-one")
        w1.edit([{"kind": "ins", "pos": 0,
                  "text": "a\U0001F600b\U0001F3F4c"}])   # 5 code points
        w2 = DumbClient(base, "astro", "web-two")
        w2.sync()
        assert w2.text == "a\U0001F600b\U0001F3F4c"
        # edit AFTER the astral chars: pos 4 = before 'c' in code points
        w2.edit([{"kind": "ins", "pos": 4, "text": "!"}])
        w1.edit([{"kind": "del", "start": 1, "end": 2}])  # delete emoji
        w1.sync()
        w2.sync()
        w1.sync()
        assert w1.text == w2.text == "ab\U0001F3F4!c"
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_crdt_peer_astral_unit_ops():
    """The /ops peer protocol is code-point addressed: run rows expand
    into one unit op per CODE POINT (the fixed JS pull loop uses
    Array.from; unit-indexing would split astral chars into lone
    surrogates with over-counted seqs)."""
    srv, base = _boot_server()
    try:
        p1 = _CrdtPeer(base, "adoc", "anna")
        p1.edit_ins(0, "x\U0001F600y")     # 3 code points, 3 unit ops
        p1.sync()
        p2 = _CrdtPeer(base, "adoc", "bert")
        out = p2.sync()
        total_units = sum(len(r.get("content") or "") if r["kind"] == "ins"
                          else r["len"] for r in out["ops"])
        assert total_units == 3            # not 4 UTF-16 units
        assert p2.known["anna"] == 3       # seq accounting by code point
        p2.edit_ins(2, "\U0001F3F4")       # insert BETWEEN emoji and y
        p2.sync()
        p1.sync()
        store = srv.RequestHandlerClass.store
        text = store.get("adoc").checkout_tip().snapshot()
        assert text == "x\U0001F600\U0001F3F4y"
    finally:
        srv.shutdown()
        srv.server_close()


# ---- the length at a writer's version: remembered, not checked out ----------

def _checkouts(monkeypatch):
    """Every full checkout an oplog pays from here on, by frontier."""
    from diamond_types_tpu.text.oplog import OpLog
    calls = []
    real = OpLog.checkout

    def checkout(ol, frontier):
        calls.append(tuple(frontier))
        return real(ol, frontier)

    monkeypatch.setattr(OpLog, "checkout", checkout)
    return calls


def _edit_counts(srv):
    """`len_hit` / `len_miss` of the `http.edit` row. A request's rows
    are written when its root closes, after the response."""
    import time
    table = srv.RequestHandlerClass.store.obs.phases
    deadline = time.monotonic() + 5.0
    seen = None
    while time.monotonic() < deadline:
        row = table.snapshot()["phases"].get("http.edit") or {}
        # the row also counts which parser took each request (`lean` /
        # `stdlib`, tests/test_http_lean.py): not the memo's
        now = (row.get("count", 0),
               {k: v for k, v in (row.get("counts") or {}).items()
                if k.startswith("len_")})
        if now == seen:
            return now[1]
        seen = now
        time.sleep(0.05)
    return seen[1]


def test_edit_a_writers_second_push_finds_the_length_remembered(monkeypatch):
    srv, base = _boot_server()
    try:
        calls = _checkouts(monkeypatch)
        w1 = DumbClient(base, "memo", "web-one")     # /state: a checkout
        del calls[:]
        w1.edit([{"kind": "ins", "pos": 0, "text": "hello \U0001F600"}])
        assert calls == [()]                # the empty version: a miss
        w1.edit([{"kind": "ins", "pos": 7, "text": "!"},
                 {"kind": "del", "start": 0, "end": 1}])
        w1.edit([{"kind": "ins", "pos": 7, "text": "?"}])
        assert calls == [()]                # from their own head: hits
        assert _edit_counts(srv) == {"len_miss": 1, "len_hit": 2}
        # a second writer starts at the tip the first one left: a hit;
        # then both type from their own heads and never pay a checkout
        w2 = DumbClient(base, "memo", "web-two")
        del calls[:]
        w2.edit([{"kind": "ins", "pos": 0, "text": ">"}])
        w1.edit([{"kind": "ins", "pos": 8, "text": "<"}])
        w2.edit([{"kind": "del", "start": 0, "end": 1}])
        assert calls == []
        assert _edit_counts(srv) == {"len_miss": 1, "len_hit": 5}
        # pulling the peer's edits leaves two heads nothing remembers:
        # one checkout at exactly that version, and hits again after it
        w1.sync()
        assert len(w1.version) == 2
        del calls[:]
        w1.edit([{"kind": "ins", "pos": len(w1.text), "text": "."}])
        assert len(calls) == 1 and len(calls[0]) == 2
        w1.edit([{"kind": "ins", "pos": len(w1.text), "text": "."}])
        assert len(calls) == 1
        assert _edit_counts(srv) == {"len_miss": 2, "len_hit": 6}
        w2.sync()
        w1.sync()
        assert w1.text == w2.text == "ello \U0001F600!?<.."
        ol = srv.RequestHandlerClass.store.get("memo")
        assert ol.checkout_tip().snapshot() == w1.text
    finally:
        srv.shutdown()
        srv.server_close()


def test_edit_a_refused_batch_leaves_oplog_memo_and_next_answer(monkeypatch):
    import json
    import urllib.error
    import urllib.request
    srv, base = _boot_server()
    try:
        w = DumbClient(base, "refuse", "web-one")
        w.edit([{"kind": "ins", "pos": 0, "text": "hello"}])
        ol = srv.RequestHandlerClass.store.get("refuse")
        n_ops, memo = len(ol), dict(ol._len_memo)
        calls = _checkouts(monkeypatch)
        bad = [
            [{"kind": "ins", "pos": 6, "text": "x"}],            # past end
            [{"kind": "ins", "pos": 5, "text": "ok"},            # the second
             {"kind": "del", "start": 3, "end": 8}],             # op is bad
            [{"kind": "ins", "pos": 0, "text": ""}],             # no text
            [{"kind": "ins", "pos": 0, "text": "\ud800"}],       # surrogate
            [{"kind": "ins", "pos": 0, "text": 7}],
            [{"kind": "del", "start": 2, "end": 2}],
        ]
        for ops in bad:
            body = json.dumps({"agent": "web-one", "version": w.version,
                               "ops": ops}).encode("utf8", "surrogatepass")
            try:
                urllib.request.urlopen(urllib.request.Request(
                    f"{base}/doc/refuse/edit", data=body))
                raise AssertionError(f"accepted {ops}")
            except urllib.error.HTTPError as e:
                assert e.code == 400, ops
                assert json.loads(e.read()) == {"error": "bad op"}, ops
        assert len(ol) == n_ops and ol._len_memo == memo and calls == []
        # the length the refusals were held against is the next push's
        # too: position 5 is the end, 6 is still past it
        w.edit([{"kind": "ins", "pos": 5, "text": "!"},
                {"kind": "del", "start": 0, "end": 6}])
        assert calls == []
        assert ol.checkout_tip().snapshot() == ""
        del calls[:]
        assert _edit_counts(srv) == {"len_miss": 1,
                                     "len_hit": len(bad) + 1}
        # a refusal at a version nothing remembers pays its checkout
        # and remembers that version alone
        head = ol.cg.local_to_remote_frontier([2])
        body = json.dumps({"agent": "web-two", "version": head,
                           "ops": [{"kind": "ins", "pos": 4, "text": "x"}]})
        try:
            urllib.request.urlopen(urllib.request.Request(
                f"{base}/doc/refuse/edit", data=body.encode("utf8")))
            raise AssertionError("accepted an insert past the end")
        except urllib.error.HTTPError as e:
            assert e.code == 400
        assert calls == [(2,)] and ol.length_at([2]) == 3
        assert len(ol._len_memo) == len(memo) + 2
        assert ol.cg.agent_assignment.try_get_agent("web-two") is None
    finally:
        srv.shutdown()
        srv.server_close()


def test_crdt_ops_batches_pay_one_checkout_a_version_nothing_remembers(
        monkeypatch):
    """The per-request cache is gone: the oplog's own memo carries the
    length from one op of a chain to the next, and from one request to
    the next."""
    import json
    import urllib.error
    import urllib.request
    srv, base = _boot_server()
    try:
        calls = _checkouts(monkeypatch)
        a = _CrdtPeer(base, "chain", "anna")
        a.edit_ins(0, "hello \U0001F600 world")     # 13 unit ops, one chain
        a.edit_del(0, 6)
        a.sync()
        assert calls == [()]
        a.edit_ins(7, "!!")                 # continues the chain: none
        a.sync()
        assert calls == [()]
        ol = srv.RequestHandlerClass.store.get("chain")
        assert ol.checkout_tip().snapshot() == "\U0001F600 world!!"
        del calls[:]
        # a batch that is not one chain: each op from a version of its own
        b = _CrdtPeer(base, "chain", "bert")
        mid = [["anna", 4]]                 # "hello": never asked before
        b.pending = [
            {"agent": "bert", "seq": 0, "parents": mid, "kind": "ins",
             "pos": 5, "content": "A"},
            {"agent": "bert", "seq": 1, "parents": a.frontier,
             "kind": "ins", "pos": 0, "content": "B"},
            {"agent": "bert", "seq": 2, "parents": [["bert", 0]],
             "kind": "del", "pos": 0, "len": 6},
        ]
        b.sync()
        assert calls == [(4,)]              # the tip and bert's own: known
        assert ol.length_at(ol.cg.remote_to_local_frontier(
            [("bert", 7)])) == 0
        text = ol.checkout_tip().snapshot()
        assert sorted(text) == sorted("B\U0001F600 world!!")
        # a bad op in the middle: the ops before it stay, the length it
        # was refused against is exact, and the next batch goes on
        del calls[:]
        n_ops = len(ol)
        tip = ol.cg.local_to_remote_frontier(ol.version)
        c = _CrdtPeer(base, "chain", "cara")
        c.pending = [
            {"agent": "cara", "seq": 0, "parents": tip, "kind": "ins",
             "pos": len(text), "content": "."},
            {"agent": "cara", "seq": 1, "parents": [["cara", 0]],
             "kind": "ins", "pos": len(text) + 2, "content": "x"},
        ]
        try:
            c.sync()
            raise AssertionError("accepted an insert past the end")
        except urllib.error.HTTPError as e:
            assert e.code == 400
        assert len(ol) == n_ops + 1
        c.pending = [
            {"agent": "cara", "seq": 1, "parents": [["cara", 0]],
             "kind": "ins", "pos": len(text) + 1, "content": "x"}]
        c.sync()
        assert len(calls) == 1 and len(calls[0]) == len(tip) == 2
        assert ol.checkout_tip().snapshot() == text + ".x"
    finally:
        srv.shutdown()
        srv.server_close()


def test_the_listen_queue_holds_a_burst_of_connections():
    """More clients than the stdlib's backlog of 5 connect before the
    accept loop runs: none may fall out of the listen queue, where it
    would wait a second or more for TCP to retransmit."""
    import socket
    from diamond_types_tpu.tools.server import _Server
    assert _Server.request_queue_size >= 64
    srv = serve(port=0, data_dir=None)      # listening, nobody accepts yet
    conns = []
    try:
        for _ in range(40):
            c = socket.create_connection(srv.server_address, timeout=0.9)
            c.sendall(b"GET /doc/burst/state HTTP/1.0\r\n\r\n")
            conns.append(c)
    finally:
        # `shutdown()` waits for a loop that has run
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        try:
            for c in conns:
                c.settimeout(10)
                assert c.recv(64).startswith(b"HTTP/1.0 200")
        finally:
            for c in conns:
                c.close()
            srv.shutdown()
            srv.server_close()
