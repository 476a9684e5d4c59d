"""Differential test of the in-browser CRDT engine's ALGORITHM.

The engine is SINGLE-SOURCED (VERDICT r4 #5): the replay algorithm lives
in diamond_types_tpu/tools/crdt_replay_src.py, which this suite executes
directly AND which web_assets transpiles to the shipped JS at import
time (tools/py2js.py; an out-of-subset edit fails generation). There is
no hand-written mirror left to drift — the code fuzzed here IS the code
the browser runs, modulo the mechanical transpilation mapping documented
in py2js's header.
"""

import random

import pytest

from diamond_types_tpu import OpLog
from diamond_types_tpu.tools.crdt_replay_src import replay as _replay_mirror
from diamond_types_tpu.tools.server import _crdt_apply_op


def _oracle_text(ops):
    ol = OpLog()
    # Feed in topo order, gated ALSO on per-agent seq contiguity: the
    # server protocol receives each client's stream in seq order even
    # when seq order is not causal order (same-agent concurrency, e.g.
    # git imports), and _crdt_apply_op rejects seq gaps.
    done = set()
    next_seq = {}
    rest = list(ops)
    while rest:
        progressed = False
        nxt = []
        for o in sorted(rest, key=lambda o: (o["agent"], o["seq"])):
            if o["seq"] != next_seq.get(o["agent"], 0):
                nxt.append(o)
                continue
            if all((a, s) in done for (a, s) in o["parents"]):
                row = {"agent": o["agent"], "seq": o["seq"],
                       "parents": o["parents"], "kind": o["kind"],
                       "pos": o["pos"]}
                if o["kind"] == "ins":
                    row["content"] = o["ch"]
                else:
                    row["len"] = 1
                _crdt_apply_op(ol, row)
                done.add((o["agent"], o["seq"]))
                next_seq[o["agent"]] = o["seq"] + 1
                progressed = True
            else:
                nxt.append(o)
        assert progressed
        rest = nxt
    return ol.checkout_tip().snapshot()


ALPHABET = "abcdefgh XY12\u00a9\u0394\u2190\U00010190"  # incl. BMP + astral


@pytest.mark.parametrize("seed", range(30))
def test_browser_engine_vs_oracle(seed):
    """Random concurrent unit-op histories: the browser replay algorithm
    must converge to EXACTLY the oplog engines' text."""
    rng = random.Random(4400 + seed)
    agents = ["anna", "bert", "cleo"]
    ops = []
    heads = {}     # agent -> (frontier, text)
    shared_frontier, shared_text = [], ""
    for a in agents:
        heads[a] = ([], "")
    for step in range(40):
        a = agents[rng.randrange(3)]
        frontier, text = heads[a]
        seq = sum(1 for o in ops if o["agent"] == a)
        if not text or rng.random() < 0.7:
            pos = rng.randint(0, len(text))
            ch = rng.choice(ALPHABET)
            ops.append({"agent": a, "seq": seq, "parents": frontier,
                        "kind": "ins", "pos": pos, "ch": ch})
            text = text[:pos] + ch + text[pos:]
        else:
            pos = rng.randrange(len(text))
            ops.append({"agent": a, "seq": seq, "parents": frontier,
                        "kind": "del", "pos": pos, "ch": None})
            text = text[:pos] + text[pos + 1:]
        heads[a] = ([[a, seq]], text)
        if rng.random() < 0.3:
            # peer pulls everything known so far (frontier = all heads)
            f = []
            for a2 in agents:
                s2 = sum(1 for o in ops if o["agent"] == a2)
                if s2:
                    f.append([a2, s2 - 1])
            merged = _replay_mirror(ops)
            heads[a] = (f, merged)
    got = _replay_mirror(ops)
    exp = _oracle_text(ops)
    assert got == exp, f"seed {seed}: {got!r} != {exp!r}"


def _golden_fixture():
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "crdt_client_golden.json")
    with open(path) as f:
        return json.load(f)


def test_golden_vectors_mirror():
    """Every golden conformance vector replays to its oracle-blessed text
    through the Python mirror (vectors cover same-gap concurrency,
    doc-end ties, same-agent branches and scanning-rollback shapes;
    generated + oracle-verified by tests/gen_crdt_golden.py)."""
    fx = _golden_fixture()
    assert len(fx["vectors"]) >= 40
    for v in fx["vectors"]:
        got = _replay_mirror(v["ops"])
        assert got == v["expect"], \
            f"vector {v['name']}: {got!r} != {v['expect']!r}"


def test_golden_fixture_pins_engine_source():
    """Drift detection: the fixture records the sha256 of the SINGLE
    SOURCE (crdt_replay_src.py) it was blessed against. If this fails,
    the engine algorithm changed: re-run the oracle blessing and
    regenerate with python -m tests.gen_crdt_golden. (The shipped JS
    cannot drift independently — it is generated from this source at
    import time; hand-editing it is impossible.)"""
    import hashlib
    import inspect

    from diamond_types_tpu.tools import crdt_replay_src
    fx = _golden_fixture()
    cur = hashlib.sha256(
        inspect.getsource(crdt_replay_src).encode("utf8")).hexdigest()
    assert cur == fx["src_sha256"], (
        "crdt_replay_src.py drifted from the golden fixture — see this "
        "test's docstring for the regen steps")


def test_conformance_runner_embeds_shipped_js():
    """The node runner must contain the engine source verbatim — it IS
    the executable form of the shipped JS for environments with a JS
    runtime (none exists in this image)."""
    import os
    from diamond_types_tpu.tools.web_assets import crdt_engine_js
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "crdt_conformance.mjs")
    with open(path) as f:
        runner = f.read()
    assert crdt_engine_js() in runner

def test_transpiler_rejects_out_of_subset_source(tmp_path):
    """The generation-time assertion: an engine edit outside the
    transpilable subset must fail loudly, not ship silently-wrong JS."""
    import importlib.util

    from diamond_types_tpu.tools.py2js import (UnsupportedConstruct,
                                               transpile_module)
    path = tmp_path / "bad_engine.py"
    path.write_text("def replay(ops):\n"
                    "    return [o for o in ops]  # comprehension\n")
    spec = importlib.util.spec_from_file_location("bad_engine", str(path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with pytest.raises(UnsupportedConstruct):
        transpile_module(mod)


def test_astral_agent_names_rejected_at_edge():
    """Agent ordering is a convergence tie-break; JS compares UTF-16
    units, Python code points, and they diverge exactly on astral
    chars — so the server edge rejects astral agent names (the single
    source's documented precondition, now enforced)."""
    from diamond_types_tpu.tools.server import _agent_name_ok
    assert _agent_name_ok("anna")
    assert _agent_name_ok("ﬀligature")     # BMP is fine
    assert not _agent_name_ok("\U0001F600grin")  # astral: rejected
    assert not _agent_name_ok("")
    assert not _agent_name_ok(None)
    with pytest.raises(ValueError, match="bad agent name"):
        _crdt_apply_op(OpLog(), {"agent": "\U0001F600", "seq": 0,
                                 "parents": [], "kind": "ins", "pos": 0,
                                 "content": "x"})


def test_page_embeds_generated_engine():
    """The editor page carries the transpiled engine verbatim, and the
    legacy hand-written replay is gone — the generated function is the
    only replay in the page."""
    from diamond_types_tpu.tools.web_assets import CRDT_HTML, crdt_engine_js
    js = crdt_engine_js()
    assert js in CRDT_HTML
    assert CRDT_HTML.count("function replay(") == 1
    assert "replay(eng.ops)" in CRDT_HTML


def test_astral_agent_patch_rejected_on_push(tmp_path):
    """The BINARY push path enforces the same agent-name rules as the
    JSON paths — a patch registering an astral-named agent is rejected
    before decode_into can poison the doc."""
    import threading
    import urllib.error
    import urllib.request

    from diamond_types_tpu.encoding.encode import encode_oplog
    from diamond_types_tpu.text.crdt import ListCRDT
    from diamond_types_tpu.tools.server import serve
    httpd = serve(port=0, data_dir=str(tmp_path))
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        base = f"http://127.0.0.1:{port}"
        c = ListCRDT()
        ag = c.get_or_create_agent_id("\U0001F600grin")
        c.insert(ag, 0, "astral")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                base + "/doc/p/push", encode_oplog(c.oplog)))
        assert ei.value.code == 400
        with urllib.request.urlopen(base + "/doc/p") as r:
            assert r.read() == b""       # nothing applied
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_transpiler_rejects_chained_assignment(tmp_path):
    import importlib.util

    from diamond_types_tpu.tools.py2js import (UnsupportedConstruct,
                                               transpile_module)
    path = tmp_path / "chain_engine.py"
    path.write_text("def replay(ops):\n"
                    "    a = b = len(ops)\n"
                    "    return a\n")
    spec = importlib.util.spec_from_file_location("chain_engine", str(path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with pytest.raises(UnsupportedConstruct):
        transpile_module(mod)
