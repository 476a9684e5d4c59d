"""The `host4-mixed` deployment on the conftest's virtual devices: four
merge-scheduler shards, one a device, a fleet of two capacity classes
(two-writer and one-writer documents, `bench/configs/host4-mixed.json`'s
`tiny`), every flush window one `shard_map` program a class.

CPU runs prove parity and counts, never a time: what is pinned here is
(a) HTTP body = `scheduler.text()` = the plain reference for every
document after typed pushes, with no fallback; (b) every session's row
and length on its bank's device after every window; (c) the mesh rung's
`rows_off_home` / `ici_bytes` against a count made from the sessions'
devices (a dispatch is laid out by home chip: only a row with no home
on the mesh crosses); (d) the arena's hits and misses; (e) the new
steps close on their roots, and a scheduler without `mesh_window`
writes none of them; and the repair that keeps a class's dispatch at
`shards x flush_docs` rows or fewer.
"""

import json
import os
import threading
import urllib.request

import numpy as np
import pytest

from bench import corpus, gen
from bench import run as bench_run
from diamond_types_tpu.obs.phases import PhaseTable
from diamond_types_tpu.parallel import arena
from diamond_types_tpu.parallel import mesh as pm
from diamond_types_tpu.serve.scheduler import MergeScheduler
from diamond_types_tpu.text.oplog import OpLog
from diamond_types_tpu.tpu import flush_fuse as ff

pytestmark = [pytest.mark.mesh, pytest.mark.fused, pytest.mark.serve]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3000000019
SHARDS = 4
BURST = {"ops": 8, "mean_run": 14, "p_back": 0.425}
MESH_STEPS = ("mesh.pack", "mesh.stage", "mesh.dispatch", "mesh.fence",
              "mesh.adopt")
WINDOW_STEPS = ("window.plan", "window.replay", "window.adopt")
ZERO = ("reads_from_host", "host_fallbacks", "device_errors",
        "warmup_errors", "pump_errors")


@pytest.fixture(autouse=True)
def _fresh_steering_and_arenas():
    """Steering and the arenas are process-global: a class another
    test file warmed would pad these windows onto another batch."""
    from diamond_types_tpu.tpu.steer import STEER
    STEER.reset(table=True)
    arena.reset_arenas()
    yield
    STEER.reset(table=True)
    arena.reset_arenas()


def _config():
    with open(os.path.join(ROOT, "bench/configs/host4-mixed.json"),
              encoding="utf8") as f:
        return json.load(f)


def _serve(data_dir, classes, **sched):
    """The configuration's own scheduler settings on four shards."""
    from diamond_types_tpu.tools.server import serve
    so = dict(_config()["sched_opts"], **sched)
    so["fused_opts"] = dict(so["fused_opts"],
                            cap=min(c["cap"] for c in classes))
    so.update(max_sessions_per_shard=64, max_pending=256, warmup=False)
    httpd = serve(port=0, data_dir=str(data_dir), engine="device",
                  serve_shards=SHARDS, sched_opts=so,
                  obs_opts={"sample_rate": 0.0})
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, ("127.0.0.1", httpd.server_address[1])


def _stop(httpd):
    httpd.shutdown()
    httpd.server_close()


def _load(httpd, addr, classes):
    """The fleet as `bench/run.py` loads it, and its plain reference
    as `bench/gen.py` builds it."""
    docs = bench_run.fleet_docs(classes, SHARDS, SEED)
    base = f"http://{addr[0]}:{addr[1]}"
    fleet = []
    for d in docs:
        tip = bench_run.push_doc(base, SEED, d)
        plain = corpus.PlainDoc(
            d["id"], corpus.doc_text(SEED, d["index"], d["ops"]),
            d["writers"],
            [corpus.Typist(np.random.default_rng([SEED, 23, d["index"], w]),
                           BURST) for w in range(d["writers"])])
        plain.heads = [tip] * d["writers"]
        fleet.append(plain)
    httpd.store.scheduler.drain()
    return fleet


def _body(addr, doc_id) -> bytes:
    with urllib.request.urlopen(
            f"http://{addr[0]}:{addr[1]}/doc/{doc_id}", timeout=30) as r:
        return r.read()


def _counts(table, name):
    return table.snapshot()["phases"].get(name, {}).get("counts", {})


def _closes(ph, root, steps):
    assert ph[root]["sum_s"] == pytest.approx(
        sum(ph[s]["sum_s"] for s in steps) + ph[root + ".other"]["sum_s"],
        abs=1e-6)


class Recorder:
    """What an independent observer of `mesh_fused_replay` sees: each
    dispatch's session order, where each session lived, the padded
    batch it was given, and whether the arena handed its state back."""

    def __init__(self, monkeypatch):
        self.calls = []
        self.handed_back = []
        inner, acquire = pm.mesh_fused_replay, arena.acquire

        def replay(mesh, sessions, plans):
            homes = [next(iter(s.docs.devices())) for s in sessions]
            out = inner(mesh, sessions, plans)
            self.calls.append((list(mesh.devices.flat), homes, out[2],
                               sessions[0].cap, len(self.handed_back)))
            return out

        def acquired(*a):
            got = acquire(*a)
            self.handed_back.append(got is not None)
            return got

        monkeypatch.setattr(pm, "mesh_fused_replay", replay)
        monkeypatch.setattr(arena, "acquire", acquired)

    def expected(self):
        """rows, rows off home, interconnect bytes: a row whose home is
        no device of the mesh crosses on its way back, and on its way
        in too unless the arena handed the state back. Every other row
        is replayed in its home chip's block."""
        rows = off = ici = 0
        for devs, homes, bp, cap, n_acquired in self.calls:
            away = sum(h not in devs for h in homes)
            hit = self.handed_back[n_acquired - 1]
            rows += len(homes)
            off += away
            ici += away * (1 if hit else 2) * (4 * cap + 4)
        return rows, off, ici


def test_a_two_class_fleet_on_four_shards_through_mesh_windows(
        tmp_path, monkeypatch):
    classes = _config()["tiny"]
    assert len({c["cap"] for c in classes}) == 2
    assert {c["writers"] for c in classes} == {1, 2}
    rec = Recorder(monkeypatch)
    httpd, addr = _serve(tmp_path, classes)
    try:
        sched = httpd.store.scheduler
        table = httpd.store.obs.phases
        assert sched.mesh_window and len(sched.banks) == SHARDS
        assert len({b.device for b in sched.banks}) == SHARDS
        fleet = _load(httpd, addr, classes)
        sched.stop_pump(drain=True)     # windows when the test says so
        seen_caps = set()
        for _round in range(3):
            for doc in fleet:
                for _w in doc.regions:      # each writer, own head
                    ok, n, err = gen.push(addr, doc, BURST["ops"], 30.0)
                    assert ok and n == BURST["ops"], err
            sched.drain()
            # (b) every session's row and length on its bank's device
            for bank in sched.banks:
                for doc_id, sess in bank.sessions.items():
                    assert sess.docs.devices() == {bank.device}, doc_id
                    assert sess.lens.devices() == {bank.device}, doc_id
                    seen_caps.add(sess.cap)
        assert seen_caps == {c["cap"] for c in classes}
        # (a) three-way equality, every document, no fallback
        for doc in fleet:
            want = doc.text()
            assert _body(addr, doc.id) == want, doc.id
            assert sched.text(doc.id).encode() == want, doc.id
        totals = sched.metrics_json()["totals"]
        assert {k: totals[k] for k in ZERO} == dict.fromkeys(ZERO, 0)
        win = sched.metrics_json()["window"]
        assert win["shape_classes"] >= 2 and win["mesh_docs"] > 0
        # (c) the rung's own counts against the observer's
        got = _counts(table, "mesh.replay")
        rows, off, ici = rec.expected()
        assert rows == win["mesh_docs"]
        assert (got["rows"], got["rows_off_home"], got["ici_bytes"]) \
            == (rows, off, ici)
        assert off == 0     # every row is replayed on its own chip
        for c in classes:
            cap = c["cap"]
            mine = [call for call in rec.calls if call[3] == cap]
            assert got[f"cap.{cap}.dispatches"] == len(mine)
            assert got[f"cap.{cap}.docs"] == sum(len(c[1]) for c in mine)
            assert got[f"cap.{cap}.padded_rows"] == sum(c[2] for c in mine)
        assert got.get("arena_hits", 0) + got["arena_misses"] \
            == len(rec.calls) == len(rec.handed_back)
        # where the windows' documents went
        flush = _counts(table, "sched.flush")
        assert flush["window_mesh_docs"] == rows
        assert flush["window_serial_docs"] == 0
        assert flush["window_docs"] >= rows
        assert flush["homes_off_bank"] == 0
        # (e) the steps close on their roots
        ph = table.snapshot()["phases"]
        assert ph["mesh.replay"]["count"] == len(rec.calls)
        for step in MESH_STEPS:
            assert ph[step]["count"] == len(rec.calls), step
        _closes(ph, "mesh.replay", MESH_STEPS)
        windows = ph["window.plan"]["count"]
        assert windows == ph["window.replay"]["count"] \
            == ph["window.adopt"]["count"] >= 3
        assert ph["bank.plan"]["count"] >= windows
        sites = table.snapshot()["locks"]["store.oplog"]
        assert {"bank.plan", "adopt"} <= set(sites)
    finally:
        _stop(httpd)


def test_a_scheduler_without_mesh_windows_writes_none_of_it(tmp_path):
    classes = _config()["tiny"][1:]
    httpd, addr = _serve(tmp_path, classes, mesh_window=False)
    try:
        sched = httpd.store.scheduler
        assert not sched.mesh_window
        fleet = _load(httpd, addr, classes)
        for doc in fleet[:4]:
            assert gen.push(addr, doc, BURST["ops"], 30.0)[0]
        sched.drain()
        for doc in fleet[:4]:
            assert sched.text(doc.id).encode() == doc.text()
        snap = httpd.store.obs.phases.snapshot()
        assert snap["phases"]["sched.flush"]["count"] >= 1
        # its flushes by kind, and none of a window's counts
        assert set(snap["phases"]["sched.flush"]["counts"]) \
            <= {"paced", "forced", "inline"}
        new = [n for n in snap["phases"]
               if n.startswith(("mesh.", "window."))]
        assert new == []
        assert not any(s.startswith(("mesh.", "window."))
                       for sites in snap["locks"].values() for s in sites)
    finally:
        _stop(httpd)


# ---- the rung alone, sessions placed by hand -----------------------------------

def _session(doc_id, dev, text="hello world"):
    import jax
    ol = OpLog()
    ol.doc_id = doc_id
    agent = ol.get_or_create_agent_id("a")
    ol.add_insert_at(agent, [], 0, text)
    with jax.default_device(dev):
        sess = ff.FusedDocSession(ol, cap=64, max_ins=4)
    assert sess.docs.devices() == {dev}
    return ol, sess


def _type(ol, pos, text):
    ol.add_insert_at(ol.get_or_create_agent_id("a"), list(ol.version),
                     pos, text)


def test_rows_off_home_interconnect_bytes_and_the_arena():
    """Six sessions on devices 0, 0, 1, 2, 3, 3 in a batch padded to 8
    (two slots a device): every row lies in its own chip's block,
    whatever the order of the list. A seventh lives on a device that
    is none of the mesh's: it alone crosses."""
    import jax
    mesh = pm.serve_mesh(SHARDS)
    devs = list(mesh.devices.flat)
    homes = [devs[i] for i in (0, 0, 1, 2, 3, 3)]
    pairs = [_session(f"d{i}", dev) for i, dev in enumerate(homes)]
    sessions = [s for _ol, s in pairs]
    cap = sessions[0].cap       # the floor class, whatever was asked
    row = 4 * cap + 4
    table = PhaseTable()

    def window(sess_list):
        for ol, _s in pairs:
            _type(ol, 0, "x")
        with table.phase("sched.flush"):
            plans = [s.plan_tail() for s in sess_list]
            ok, _dev_s, bp, _staged = pm.mesh_fused_replay(
                mesh, sess_list, plans)
        assert all(ok) and bp == 8
        return _counts(table, "mesh.replay")

    got = window(sessions)
    # stacked at home: nothing came over, nothing goes back
    assert (got["rows"], got["rows_off_home"], got["ici_bytes"]) \
        == (6, 0, 0)
    assert (got.get("arena_hits", 0), got["arena_misses"]) == (0, 1)
    assert [s.docs.devices() for s in sessions] == [{h} for h in homes]
    assert [s._arena_tag[2] for s in sessions] == [0, 1, 2, 4, 6, 7]
    assert (got[f"cap.{cap}.dispatches"], got[f"cap.{cap}.docs"],
            got[f"cap.{cap}.padded_rows"]) == (1, 6, 8)
    # (d) the same session list again: the arena hands the state back
    got = window(sessions)
    assert (got["arena_hits"], got["arena_misses"]) == (1, 1)
    assert (got["rows_off_home"], got["ici_bytes"]) == (0, 0)
    # another order is another layout (the two rows of device 0 and of
    # device 3 swap slots): a miss, and still every row at home
    got = window(sessions[::-1])
    assert (got["arena_hits"], got["arena_misses"]) == (1, 2)
    assert [s._arena_tag[2] for s in sessions] == [1, 0, 2, 4, 7, 6]
    assert (got["rows_off_home"], got["ici_bytes"]) == (0, 0)
    assert [s.docs.devices() for s in sessions] == [{h} for h in homes]
    assert [s.text() for s in sessions] == ["xxxhello world"] * 6
    # a row that lives on no device of the mesh joins the emptiest
    # block (device 1's or 2's), comes over and goes back home
    far = jax.devices()[SHARDS + 1]
    pairs.append(_session("far", far, text="xxxhello world"))
    sessions.append(pairs[-1][1])
    got = window(sessions)
    assert (got["rows"], got["rows_off_home"], got["ici_bytes"]) \
        == (6 * 3 + 7, 1, 2 * row)
    assert sessions[-1]._arena_tag[2] in (3, 5)
    assert sessions[-1].docs.devices() == {far} \
        == sessions[-1].lens.devices()
    assert [s.docs.devices() for s in sessions[:6]] == [{h} for h in homes]
    assert [s.text() for s in sessions] == ["xxxxhello world"] * 7
    ph = table.snapshot()["phases"]
    _closes(ph, "mesh.replay", MESH_STEPS)
    # with no root open on the thread the rung records nowhere
    for ol, _s in pairs:
        _type(ol, 0, "y")
    def rows():     # the `cpu` block is read anew at every snapshot
        return {k: v for k, v in table.snapshot().items() if k != "cpu"}
    before = rows()
    ok, *_ = pm.mesh_fused_replay(mesh, sessions,
                                  [s.plan_tail() for s in sessions])
    assert all(ok) and rows() == before


# ---- several buckets of a shard due at once --------------------------------------

@pytest.mark.parametrize("stated, most", [(None, 2 * 4), (4, 4)],
                         ids=["default", "mesh_window_rows=4"])
def test_a_class_goes_out_in_dispatches_of_shards_x_flush_docs(
        monkeypatch, stated, most):
    """Two shape buckets a shard are due in one window: 16 documents of
    one capacity class on 2 shards at flush_docs 4. Unchunked that is
    one batch of 16, a class no flush of one bucket a shard (8 rows at
    most) and no warm-up has compiled. A deployment may state the most
    rows of a dispatch itself (`mesh_window_rows`)."""
    ols = {}
    for i in range(16):
        ol = ols[f"d{i:02d}"] = OpLog()
        ol.doc_id = f"d{i:02d}"
        ol.add_insert_at(ol.get_or_create_agent_id("a"), [], 0, "seed")
    sched = MergeScheduler(2, resolve=lambda d: ols[d], engine="device",
                           fused_opts={"cap": 64, "max_ins": 4},
                           flush_docs=4, flush_deadline_s=60.0,
                           flush_workers=False, mesh_window=True,
                           mesh_window_rows=stated,
                           place_on_devices=True, max_pending=64,
                           max_sessions_per_shard=16)
    batches = []
    inner = pm.mesh_fused_replay

    def replay(mesh, sessions, plans):
        batches.append(len(sessions))
        return inner(mesh, sessions, plans)

    monkeypatch.setattr(pm, "mesh_fused_replay", replay)
    ids = sorted(ols, key=lambda d: (sched.router.shard_of(d), d))
    by_shard = [[d for d in ids if sched.router.shard_of(d) == s]
                for s in range(2)]
    assert min(len(b) for b in by_shard) >= 5
    for d in ids:       # sessions resident at the seed text
        assert sched.submit(d)["accepted"]
    sched.drain()
    # four documents a shard with 1 op pending, four more (or what the
    # router left) with 3: two buckets a shard, all due at once
    picked = []
    for docs in by_shard:
        for k, d in enumerate(docs[:8]):
            n = 1 if k < 4 else 3
            for j in range(n):
                _type(ols[d], 2 * j, "ab")
            assert sched.submit(d, n_ops=n)["accepted"]
            picked.append(d)
    batches.clear()
    assert sched.pump(force=True) == len(picked) > 8
    assert sum(batches) == len(picked)
    assert max(batches) <= most and len(batches) >= 2
    for d in picked:
        assert sched.text(d) == ols[d].checkout_tip().snapshot()


def test_mesh_window_rows_below_one_is_refused():
    with pytest.raises(ValueError, match="mesh_window_rows"):
        MergeScheduler(2, resolve=lambda d: None, engine="device",
                       mesh_window=True, mesh_window_rows=0)


def test_a_row_left_off_its_banks_chip_is_counted(monkeypatch):
    """`homes_off_bank` compares each committed row with its BANK's
    device, which the mesh rung does not know: a rung that left rows
    where they were computed would be counted."""
    from diamond_types_tpu.obs import Observability
    ols = {}
    for i in range(8):
        ol = ols[f"d{i}"] = OpLog()
        ol.doc_id = f"d{i}"
        ol.add_insert_at(ol.get_or_create_agent_id("a"), [], 0, "seed")
    sched = MergeScheduler(SHARDS, resolve=lambda d: ols[d],
                           engine="device",
                           fused_opts={"cap": 64, "max_ins": 4},
                           flush_workers=False, mesh_window=True,
                           place_on_devices=True)
    sched.attach_obs(Observability())

    def window():
        for d, ol in ols.items():
            _type(ol, 0, "x")
            assert sched.submit(d)["accepted"]
        sched.drain()
        return _counts(sched.obs.phases, "sched.flush")

    window()                                # sessions built
    assert window()["homes_off_bank"] == 0
    # a rung that lays chip k's rows into chip k+1's block, and
    # leaves them where they were computed
    inner = pm.home_blocks

    def shifted(mesh, sessions):
        blocks, _astray = inner(mesh, sessions)
        return blocks[-1:] + blocks[:-1], set()

    monkeypatch.setattr(pm, "home_blocks", shifted)
    got = window()
    assert 0 < got["homes_off_bank"] <= 8
    assert got["window_mesh_docs"] == 3 * 8 - 8


def test_drain_waits_for_the_pump_threads_window(monkeypatch):
    """A mesh window runs on the pump thread, its items already off the
    queue. `drain()` on another thread must wait for it as it waits
    for a flush worker's batch: the harness loads a fleet, drains and
    then demands every document resident (four of five runs on the
    four-chip host found the last notes pushed not yet so)."""
    import time
    ols = {}
    for i in range(8):
        ol = ols[f"d{i}"] = OpLog()
        ol.doc_id = f"d{i}"
        ol.add_insert_at(ol.get_or_create_agent_id("a"), [], 0, "seed")
    sched = MergeScheduler(SHARDS, resolve=lambda d: ols[d],
                           engine="device",
                           fused_opts={"cap": 64, "max_ins": 4},
                           flush_deadline_s=0.01, mesh_window=True,
                           place_on_devices=True)
    for d in ols:
        assert sched.submit(d)["accepted"]
    sched.drain()                           # sessions resident
    inner, entered = pm.mesh_fused_replay, threading.Event()

    def slow_replay(mesh, sessions, plans):
        entered.set()
        time.sleep(0.5)
        return inner(mesh, sessions, plans)

    monkeypatch.setattr(pm, "mesh_fused_replay", slow_replay)
    sched.start_pump()
    try:
        for d, ol in ols.items():
            _type(ol, 0, "x")
            assert sched.submit(d)["accepted"]
        assert entered.wait(timeout=10)     # the pump thread has them
        deadline = time.monotonic() + 10
        while sched.queue.total_depth() and time.monotonic() < deadline:
            time.sleep(0.005)
        assert sched.queue.total_depth() == 0
        sched.drain()
        behind = [d for b in sched.banks for d, s in b.sessions.items()
                  if s.synced_to < len(ols[d])]
        assert behind == []
    finally:
        sched.stop_pump()
