"""Tests of the benchmark itself. They run on the CPU and prove nothing
about the chip: the manifest against the contract's limits, every
cell's files found by name, what a push is, the reductions on small
recorded inputs, a rehearsal of each cell at `--tiny` size
(`JAX_PLATFORMS=cpu`) and of two cells that are data under
`bench/tests/extra/` alone, and the controls, which must come out
`correct: false`.

    python -m pytest bench/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import corpus, reduce  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf8") as f:
        return json.load(f)


def test_manifest_keys_names_and_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    cells = [w["name"] for w in bench["workloads"]]
    assert len(cells) == len(set(cells))
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(1, len(cells) // 2)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and 1 <= len(c["source"]) <= 200
        assert c["name"] in {w["config"] for w in bench["workloads"]}


def test_every_cell_reports_what_it_must(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        own = [m for m in bench["end_to_end"] if m["name"] != "setup_s"
               and w["name"] in m.get("workloads", [w["name"]])]
        assert own, f"{w['name']} reports no end-to-end metric"
        layer = [m for m in bench["per_layer"]
                 if w["name"] in m.get("workloads", [w["name"]])]
        assert layer
        for m in layer:
            moved = e2e[m["moves"]]
            assert w["name"] in moved.get("workloads", [w["name"]]), \
                f"{m['name']} moves {m['moves']}, which {w['name']} " \
                "does not report"


def test_files_found_by_name(bench):
    for c in bench["configs"]:
        assert c["file"].startswith("bench/")
        with open(os.path.join(ROOT, c["file"]), encoding="utf8") as f:
            cfg = json.load(f)
        for key in c["reduced"]:
            assert key in cfg and key in cfg["reduced"]
        assert cfg["guarantees"]
        assert sum(c["docs"] for c in cfg["fleet"]) == cfg["fleet_docs"]
        for cls in cfg["fleet"] + cfg["tiny"]:
            assert {"prefix", "docs", "cap", "writers"} <= set(cls)
            assert ("ops" in cls) != ("chars" in cls)
    for w in bench["workloads"]:
        path = os.path.join(ROOT, "bench", "mixes", w["traffic"] + ".json")
        with open(path, encoding="utf8") as f:
            mix = json.load(f)
        assert mix["loop"] in ("open", "closed") and mix["warm_shapes"]
        assert mix["burst"]["ops"] >= 1
    for m in bench["end_to_end"] + bench["per_layer"]:
        base = os.path.join(ROOT, "bench", "metrics", m["name"])
        assert os.path.exists(base + ".json") or os.path.exists(base + ".py")
    stray = {f.rsplit(".", 1)[0]
             for f in os.listdir(os.path.join(ROOT, "bench", "metrics"))
             if not f.startswith("__")} \
        - {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    assert not stray, f"readers no metric names: {stray}"


def test_seeds_get_the_same_work_in_another_order():
    with open(os.path.join(ROOT, "bench/configs/b1-notes.json")) as f:
        cfg = json.load(f)
    a = corpus.class_ops(cfg["fleet"][0], 1, 0)
    b = corpus.class_ops(cfg["fleet"][0], 2**31 + 11, 0)
    assert a != b and sorted(a) == sorted(b)
    from bench import gen
    mix = {"rate_per_s": 50.0, "warm_s": 1.0, "get_share": 0.9}
    d1, r1, t1 = gen.schedule(mix, 8, 1, 10.0)
    d2, r2, t2 = gen.schedule(mix, 8, 3000000019, 10.0)
    assert len(d1) == len(d2) == 550 and r1.sum() == r2.sum() == 495
    assert sorted(t1.tolist()) == sorted(t2.tolist())
    assert abs(d1[-1] - d2[-1]) < 1e-6 and (d1 != d2).any()
    zipf = dict(mix, popularity={"kind": "zipf", "s": 0.99})
    _d, _r, z1 = gen.schedule(zipf, 8, 1, 10.0)
    _d, _r, z2 = gen.schedule(zipf, 8, 3000000019, 10.0)
    c1, c2 = (sorted(np.bincount(z, minlength=8).tolist()) for z in (z1, z2))
    assert c1 == c2 and sum(c1) == 550 and c1[-1] > 4 * c1[0]


def test_a_push_is_typing_at_a_cursor():
    """Runs at a cursor that carry over from push to push (the shape of
    the cited keystroke traces), which the oplog merges: two runs a
    push or fewer, three keystrokes in ten a backspace."""
    typist = corpus.Typist(np.random.default_rng(1),
                           {"ops": 8, "mean_run": 14, "p_back": 0.425})
    text, runs, dels, last = bytearray(b"x" * 1000), 0, 0, None
    for _ in range(2000):
        ops = typist.next_push(len(text), 8)
        assert len(ops) == 8
        for op in ops:
            if op["kind"] == "ins":
                assert len(op["text"]) == 1 and 0 <= op["pos"] <= len(text)
                here = ("ins", op["pos"] - 1)
            else:
                assert op["end"] == op["start"] + 1 <= len(text)
                here = ("del", op["start"] + 1)
                dels += 1
            runs += here != last
            last = (op["kind"], op.get("pos", op.get("start")))
            corpus.apply_plain(text, [op])
    assert 0.27 < dels / 16000 < 0.33
    assert runs / 2000 < 2.0
    # about 0.4 characters stay for each keystroke, as in the documents
    assert 0.35 < (len(text) - 1000) / 16000 < 0.46


def test_pastes_and_warm_rows():
    typist = corpus.Typist(np.random.default_rng(2), {
        "ops": 8, "paste_every": 4, "paste_chars": [2048, 4096]})
    pushes = [typist.next_push(5000, 8) for _ in range(40)]
    pastes = [p for p in pushes if len(p) == 1]
    assert len(pastes) == 10
    assert all(2048 <= len(p[0]["text"]) <= 4096 for p in pastes)
    ops = corpus.scatter(np.random.default_rng(3), 600, 64)
    pos = [op["pos"] for op in ops]
    assert len(ops) == 64 and all(a - b >= 2 for a, b in zip(pos, pos[1:]))


def test_two_writers_merge_to_the_concatenation_of_their_regions():
    text = corpus.doc_text(3, 0, 4000)
    shape = {"ops": 8, "mean_run": 14, "p_back": 0.425}
    doc = corpus.PlainDoc("p", bytearray(text), 2, [
        corpus.Typist(np.random.default_rng(w), shape) for w in (0, 1)])
    for _ in range(50):
        for w in (0, 1):
            ops = doc.next_push(w, 8)
            lo = doc.starts[w] + (1 if w else 0)
            hi = doc.starts[w] + len(doc.regions[w]) - (0 if w else 1)
            for op in ops:      # never in the other writer's region
                assert lo <= op.get("pos", op.get("start")) <= hi + 40
            doc.acknowledge(w, ops, None)
    assert doc.text()[doc.starts[1] - 1 + (len(doc.regions[0])
                                           - doc.starts[1])] \
        == text[doc.starts[1] - 1]      # the guard survives


def test_reduce_gen_open_and_closed():
    rows = {"t_open": 100.0, "seconds": 10.0, "n_failures": 0,
            "due": [99.0, 101.0, 102.0, 109.5, 111.0],
            "sent": [99.0, 101.001, 102.0, 109.5, 111.0],
            "done": [99.5, 101.011, 102.02, 110.5, 111.5],
            "read": [False, False, True, False, False],
            "ok": [True, True, True, False, True],
            "ops": [8, 8, 0, 8, 8], "same": [True] * 5}
    g = reduce.reduce_gen(dict(rows, loop="open"))
    assert (g["attempted"], g["failed"], g["pushes"], g["reads"]) \
        == (3, 1, 1, 1)
    assert g["acked_edits_per_s"] == pytest.approx(0.8)
    assert g["edit_ack_ms"]["p50"] == pytest.approx(11.0, abs=1e-6)
    assert g["late_ms"]["max"] == pytest.approx(1.0, abs=1e-6)
    g = reduce.reduce_gen(dict(rows, loop="closed"))
    assert g["attempted"] == 2      # completed inside the window


def test_reduce_trace_busy_and_gaps():
    tr = {"devices": {"/device:TPU:0": [(1.0, 2.0, "%while = s32[] while()"),
                                        (1.5, 3.0, "fusion.1"),
                                        (9.5, 12.0, "fusion.1")]},
          "spans": {reduce.WINDOW_SPAN: [(0.0, 10.0)],
                    "http_edit": [(3.0, 6.0)], "lock_wait": [(4.0, 5.0)]}}
    red = reduce.reduce_trace(tr)
    assert red["busy_s"] == pytest.approx(2.5)
    assert red["window_s"] == pytest.approx(10.0)
    gaps = dict(red["idle_gaps"])
    assert gaps["lock_wait"] == pytest.approx(1.0)
    assert gaps["http_edit"] == pytest.approx(2.0)
    assert gaps["waiting_for_arrivals"] == pytest.approx(4.5)
    assert dict(red["device_ops"])["fusion.1"] == pytest.approx(2.0)


def rehearse(*argv, script="bench/run.py"):
    """One `--tiny` run on the CPU in a process of its own: marked in
    its own output as proving nothing about the chip."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, script, *argv, "--tiny"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=900)
    return p.returncode, p.stdout.strip().splitlines(), p.stderr


EXTRA = "bench/tests/extra/BENCHMARK.json"


@pytest.mark.parametrize("cell,trace,manifest", [
    ("b4-papers.edit-sat", 0, None),
    ("b4-papers.edit-sat", 1, None),
    ("b1-notes.edit-steady", 0, None),
    ("b1-notes.edit-steady", 1, None),
    # cells that exist only here, to prove that they are data only: a
    # fleet of two classes with three writers a document, pastes, Zipf
    # popularity and reads; a closed loop of 3 clients over 8 documents
    ("mixed.paste-zipf", 0, EXTRA),
    ("mixed.sat-few", 0, EXTRA)])
def test_rehearsal_on_the_cpu(bench, cell, trace, manifest):
    more = ()
    if manifest:
        more = ("--manifest", manifest)
        with open(os.path.join(ROOT, manifest), encoding="utf8") as f:
            bench = json.load(f)
    rc, lines, err = rehearse("--workload", cell, "--seed", "3000000019",
                              "--seconds", "3", "--trace", str(trace), *more)
    assert rc == 0, err[-2000:]
    out = json.loads(lines[-1])
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(out) == keys | ({"breakdown"} if trace else set())
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["rehearsal"].startswith("cpu --tiny")
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in bench[kind]
               if cell in m.get("workloads", [cell])}
    assert out["metrics"] and set(out["metrics"]) <= set(allowed)
    for name, v in out["metrics"].items():
        assert v["unit"] == allowed[name]
    if trace:
        assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
        assert len(out["breakdown"]["device_ops"]) <= 10
    else:
        assert "setup_s" in out["metrics"] and len(out["metrics"]) == 2


@pytest.mark.parametrize("name,cell", [
    ("lose_final_flush", "b1-notes.edit-steady"),
    ("withhold_edit", "b4-papers.edit-sat"),
    ("replay_unchanged", "b4-papers.edit-sat"),
    ("replay_wrong_char", "b1-notes.edit-steady")])
def test_a_broken_timed_path_is_not_correct(name, cell):
    rc, lines, err = rehearse("--break", name, "--workload", cell,
                              "--seed", "41", "--seconds", "3",
                              "--trace", "0",
                              script="bench/tests/controls.py")
    out = json.loads(lines[-1])
    assert out["break"] == name and out["correct"] is False, err[-2000:]
    assert rc == 0


def test_no_accelerator_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "b1-notes.edit-steady", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip().splitlines()[-1:] or \
        not p.stdout.strip().splitlines()[-1].startswith('{"correct"')
