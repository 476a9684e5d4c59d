"""Mesh flush windows: ONE shard_map dispatch per flush window.

Covers the PR's tentpole top to bottom:
  * padding contract — `pad_batch_count` shape classes and the
    `lens = -1` sentinel rows surviving the replay kernel untouched;
  * `mesh_fused_replay` byte parity against the per-shard fused path
    and the host oracle on randomized mixed buckets;
  * scheduler-level three-way byte parity (mesh window vs. per-shard
    fused vs. host engine) on identical edit streams;
  * cross-shard poison isolation — a violating doc in shard A's bucket
    cannot corrupt shard B's rows in the shared super-batch;
  * dispatch accounting — `device_calls_per_window == 1.0` with >= 2
    shards' buckets due, vs. one call per bucket on the control;
  * mesh warmup pre-compilation, fencing at window assembly, the prom
    window families, and the --mesh-window CLI flag;
  * the layout by home chip — uneven blocks byte-equal to the one-chip
    rung, every row replayed and left where it lives, `2 x ndev + 1`
    programs a dispatch, nothing compiled by a live uneven window;
  * the pump thread's pacing of its windows (`FLUSH_HOST_SHARE`).

Runs on the CPU-simulated mesh (conftest pins JAX_PLATFORMS=cpu and an
8-device virtual host platform).
"""

import random
import threading

import numpy as np
import pytest

from diamond_types_tpu.parallel import mesh as pm
from diamond_types_tpu.serve.metrics import ServeMetrics
from diamond_types_tpu.serve.scheduler import MergeScheduler
from diamond_types_tpu.text.oplog import OpLog
from diamond_types_tpu.tpu import flush_fuse as ff

pytestmark = [pytest.mark.mesh, pytest.mark.fused, pytest.mark.serve]

FUSED_OPTS = {"cap": 256, "max_ins": 4}


def _mk_oplog(doc_id: str) -> OpLog:
    ol = OpLog()
    ol.doc_id = doc_id
    return ol


def _random_edits(ol: OpLog, rng: random.Random, n: int,
                  agent: str = "a") -> None:
    a = ol.get_or_create_agent_id(agent)
    for _ in range(n):
        cur = len(ol.checkout_tip().snapshot())
        if cur and rng.random() < 0.3:
            pos = rng.randrange(cur)
            end = min(pos + rng.randint(1, 9), cur)
            ol.add_delete_without_content(a, pos, end)
        else:
            pos = rng.randint(0, cur)
            s = "".join(rng.choice("abcdefgh") for _ in
                        range(rng.randint(1, 11)))
            ol.add_insert(a, pos, s)


def _mk_sched(ols, n_shards, **kw):
    kw.setdefault("engine", "device")
    kw.setdefault("fused_opts", FUSED_OPTS)
    kw.setdefault("flush_docs", 8)
    kw.setdefault("flush_deadline_s", 10.0)
    kw.setdefault("flush_workers", False)
    return MergeScheduler(n_shards, resolve=lambda d: ols[d], **kw)


# ---- padding contract ----------------------------------------------------

def test_pad_batch_count_classes():
    """Divides the mesh, n_devices * pow2 rounding, O(log) classes."""
    assert pm.pad_batch_count(1, 4) == 4
    assert pm.pad_batch_count(4, 4) == 4
    assert pm.pad_batch_count(5, 4) == 8
    assert pm.pad_batch_count(9, 4) == 16
    assert pm.pad_batch_count(3, 2) == 4
    classes = {pm.pad_batch_count(b, 4) for b in range(1, 257)}
    for c in classes:
        assert c % 4 == 0
    # pow2 rounding keeps the jit-cache class count logarithmic
    assert len(classes) <= 8


def test_pad_batch_to_mesh_sentinel_rows_survive_kernel():
    """Padding rows (zero ops + lens=-1 sentinel) must pass through
    the replay kernel unchanged — identifiably inert end to end."""
    import jax.numpy as jnp
    b, n, mi, cap = 3, 2, 2, 16
    pos = np.zeros((b, n), np.int32)
    dlen = np.zeros((b, n), np.int32)
    ilen = np.zeros((b, n), np.int32)
    ilen[:, 0] = 2                      # every real row inserts "xx"
    chars = np.full((b, n, mi), ord("x"), np.int32)
    ppos, pdlen, pilen, pchars, bp = pm.pad_batch_to_mesh(
        pos, dlen, ilen, chars, 4)
    assert bp == 4 and ppos.shape == (4, n)
    docs = jnp.zeros((bp, cap), jnp.int32)
    lens = jnp.full((bp,), -1, jnp.int32).at[:b].set(0)
    run = ff.make_replay_body(mi)
    _out, out_lens = run(docs, lens, jnp.asarray(ppos),
                         jnp.asarray(pdlen), jnp.asarray(pilen),
                         jnp.asarray(pchars))
    got = np.asarray(out_lens)
    assert list(got[:b]) == [2, 2, 2]   # real rows replayed
    assert got[b] == -1                 # sentinel survived


# ---- mesh replay parity --------------------------------------------------

def test_mesh_fused_replay_randomized_parity():
    """Mesh-sharded super-batch replay == per-shard fused replay ==
    host checkout, on randomized mixed buckets re-windowed across
    rounds (committed rows re-enter later super-batches)."""
    rng = random.Random(11)
    mesh = pm.serve_mesh(4)
    ols = [_mk_oplog(f"d{i}") for i in range(6)]
    ols_f = [_mk_oplog(f"d{i}") for i in range(6)]
    rng_f = random.Random(11)
    for i, (ol, olf) in enumerate(zip(ols, ols_f)):
        _random_edits(ol, rng, 2 + i)
        _random_edits(olf, rng_f, 2 + i)
    sess = [ff.FusedDocSession(ol, **FUSED_OPTS) for ol in ols]
    sess_f = [ff.FusedDocSession(ol, **FUSED_OPTS) for ol in ols_f]
    for rnd in range(3):
        for i, (ol, olf) in enumerate(zip(ols, ols_f)):
            _random_edits(ol, rng, 1 + (i + rnd) % 3)
            _random_edits(olf, rng_f, 1 + (i + rnd) % 3)
            if rnd == 1:
                for o in (ol, olf):
                    b = o.get_or_create_agent_id("b")
                    o.add_insert_at(b, [], 0, "Z" * (i + 1))
        plans = [s.plan_tail() for s in sess]
        ok, _dev, bp, _staged = pm.mesh_fused_replay(mesh, sess, plans)
        assert all(ok)
        assert bp % 4 == 0 and bp >= len(sess)
        ok_f, _ = ff.fused_replay(sess_f,
                                  [s.plan_tail() for s in sess_f])
        assert all(ok_f)
        for s, sf, ol in zip(sess, sess_f, ols):
            assert s.text() == ol.checkout_tip().snapshot()
            assert s.text() == sf.text()


# ---- scheduler-level parity ----------------------------------------------

def test_scheduler_three_way_byte_parity():
    """Identical edit streams through (a) mesh-window scheduler,
    (b) per-shard fused scheduler, (c) host-engine scheduler: every
    doc byte-identical across all three."""
    def mk_logs():
        logs = {}
        for i in range(10):
            ol = _mk_oplog(f"d{i}")
            a = ol.get_or_create_agent_id("seed")
            ol.add_insert(a, 0, f"doc{i}: ")
            logs[f"d{i}"] = ol
        return logs

    logs = [mk_logs() for _ in range(3)]
    scheds = [
        _mk_sched(logs[0], 4, mesh_window=True),
        _mk_sched(logs[1], 4, mesh_window=False),
        _mk_sched(logs[2], 4, engine="host"),
    ]
    assert scheds[0].mesh_window and not scheds[1].mesh_window
    rngs = [random.Random(7) for _ in range(3)]
    for _rnd in range(5):
        for i in range(10):
            d = f"d{i}"
            for lg, r in zip(logs, rngs):
                _random_edits(lg[d], r, 2)
            for s in scheds:
                assert s.submit(d, n_ops=2)["accepted"]
        for s in scheds:
            s.pump(force=True)
    for i in range(10):
        d = f"d{i}"
        texts = [s.text(d) for s in scheds]
        assert texts[0] == texts[1] == texts[2]
        assert texts[0] == logs[0][d].checkout_tip().snapshot()
    m = scheds[0].metrics_json()
    assert m["totals"]["host_fallbacks"] == 0
    assert m["window"]["mesh_docs"] > 0


# ---- cross-shard poison isolation ----------------------------------------

def _docs_on_two_shards(sched, n=2):
    by_shard = {0: [], 1: []}
    i = 0
    while any(len(v) < n for v in by_shard.values()):
        d = f"w{i:03d}"
        s = sched.router.shard_of(d)
        if s in by_shard and len(by_shard[s]) < n:
            by_shard[s].append(d)
        i += 1
        assert i < 4096
    return by_shard


def test_cross_shard_poison_isolation(monkeypatch):
    """A violating doc in shard 0's bucket poisons only ITS row of the
    shared super-batch: shard 1's docs (and shard 0's healthy doc)
    commit device state and stay byte-correct; the violator is evicted
    to the host oracle."""
    ols = {}
    sched = _mk_sched(ols, 2, mesh_window=True)
    by_shard = _docs_on_two_shards(sched)
    docs = by_shard[0] + by_shard[1]
    rng = random.Random(9)
    for d in docs:
        ols[d] = _mk_oplog(d)
        _random_edits(ols[d], rng, 3)
        assert sched.submit(d, n_ops=3)["accepted"]
    sched.pump(force=True)              # builds sessions
    for d in docs:
        _random_edits(ols[d], rng, 2)
        assert sched.submit(d, n_ops=2)["accepted"]

    victim = by_shard[0][0]
    real_plan = ff.FusedDocSession.plan_tail

    def bad_plan(self):
        plan = real_plan(self)
        if self.oplog.doc_id == victim and plan.n_ops:
            plan.dlen[0] = self.max_ins + 1   # device poisons to -1
        return plan

    monkeypatch.setattr(ff.FusedDocSession, "plan_tail", bad_plan)
    sched.pump(force=True)
    monkeypatch.undo()
    m = sched.metrics_json()
    assert m["totals"]["host_fallbacks"] == 1
    assert victim not in sched.banks[0].sessions     # evicted
    for d in by_shard[1]:
        assert d in sched.banks[1].sessions          # untouched shard
    for d in docs:
        assert sched.text(d) == ols[d].checkout_tip().snapshot()


# ---- dispatch accounting -------------------------------------------------

def test_window_rung_failure_propagates(monkeypatch):
    """A mesh flush window whose replay raises: counted on the failing
    class's shard, recorded as rung "mesh", raised once the window is
    wound up — no quieter path replays the window (not the per-shard
    `fused_replay`, not the per-doc one), and the host oracle keeps
    every doc byte-correct."""
    from diamond_types_tpu.obs import Observability
    ols = {}
    sched = _mk_sched(ols, 1, mesh_window=True)
    sched.attach_obs(Observability())
    rng = random.Random(37)
    docs = [f"d{i}" for i in range(4)]
    quieter = []
    for rnd in range(3):
        for d in docs:
            if rnd == 0:
                ols[d] = _mk_oplog(d)
            _random_edits(ols[d], rng, 2)
            assert sched.submit(d, n_ops=2)["accepted"]
        if rnd == 2:
            def boom(*a, **k):
                raise RuntimeError("injected rung failure")
            # the window's call-time import re-resolves the attribute
            monkeypatch.setattr(pm, "mesh_fused_replay", boom)
            monkeypatch.setattr(
                ff, "fused_replay",
                lambda *a, **k: quieter.append("fused") or boom())
            monkeypatch.setattr(
                ff.FusedDocSession, "sync",
                lambda self: quieter.append("per_doc") or boom())
            with pytest.raises(RuntimeError, match="injected rung"):
                sched.pump(force=True)
        else:
            sched.pump(force=True)
    monkeypatch.undo()
    assert quieter == []
    m = sched.metrics_json()
    # the failed window is accounted, with no dispatch to its name
    assert m["window"]["windows"] == 3
    assert m["window"]["device_windows"] == 1
    assert m["totals"]["device_errors"] == 1
    ev = [e for e in sched.obs.recorder.dump()
          if e["kind"] == "device_error"]
    assert [e["rung"] for e in ev] == ["mesh"]
    for d in docs:
        assert sched.text(d) == ols[d].checkout_tip().snapshot()


def test_one_dispatch_per_window_vs_per_shard_control():
    """>= 2 shards' buckets due in one window: the mesh path issues
    exactly ONE device program (device_calls_per_window == 1.0); the
    per-shard control pays one dispatch per due bucket."""
    from diamond_types_tpu.obs.devprof import PROFILER

    def run(mesh_window):
        ols = {}
        sched = _mk_sched(ols, 2, mesh_window=mesh_window)
        by_shard = _docs_on_two_shards(sched)
        docs = by_shard[0] + by_shard[1]
        rng = random.Random(3)
        for rnd in range(3):
            for d in docs:
                if rnd == 0:
                    ols[d] = _mk_oplog(d)
                _random_edits(ols[d], rng, 2)
                assert sched.submit(d, n_ops=2)["accepted"]
            sched.pump(force=True)
        for d in docs:
            assert sched.text(d) == ols[d].checkout_tip().snapshot()
        return sched.metrics_json()

    PROFILER.reset()
    PROFILER.enabled = True
    try:
        m = run(mesh_window=True)
        w = m["window"]
        # round 1 builds (no device work); rounds 2-3 each fold BOTH
        # shards' buckets into one dispatch
        assert w["windows"] == 3
        assert w["device_windows"] == 2
        assert w["dispatches"] == 2
        assert w["device_calls_per_window"] == 1.0
        assert w["mesh_docs"] == 8                  # 4 docs x 2 rounds
        assert w["mesh_padded_rows"] >= w["mesh_docs"]
        assert 0 < w["mesh_occupancy"] <= 1
        assert w["shards_hist"] == {"2": 3}
        assert m["fused"]["device_calls"] == 0      # no per-shard rung
        dp = PROFILER.snapshot()
        assert dp["mesh_window"]["dispatches"] == 2
        assert dp["mesh_window"]["docs"] == 8
        assert "mesh" in dp["jit_cache"]
    finally:
        PROFILER.enabled = False
    mc = run(mesh_window=False)
    wc = mc["window"]
    # the control pays one handoff per due bucket: 2 shards -> 2
    assert wc["device_calls_per_window"] == 2.0
    assert wc["mesh_docs"] == 0


def test_mesh_commit_keeps_each_session_on_its_banks_device():
    """Placed shards: after mesh windows every session's state — docs
    row and length — sits on its own bank's device and nowhere else,
    window after window (a row slice of the sharded result comes back
    replicated over the mesh; the commit cuts it from its shard and
    sends it home), and a mixed window takes one program per class."""
    ols = {}
    sched = _mk_sched(ols, 4, mesh_window=True, place_on_devices=True)
    assert len({b.device for b in sched.banks}) == 4
    rng = random.Random(11)
    docs = [f"place-{i}" for i in range(12)]
    for d in docs:
        ols[d] = _mk_oplog(d)
        # two capacity classes, so windows are mixed
        ols[d].add_insert(ols[d].get_or_create_agent_id("a"), 0,
                          "y" * (900 if d.endswith(("0", "5")) else 30))
    for rnd in range(4):
        for d in docs:
            _random_edits(ols[d], rng, 2)
            assert sched.submit(d, n_ops=2)["accepted"]
        sched.pump(force=True)
        for bank in sched.banks:
            for sess in bank.sessions.values():
                assert sess.docs.devices() == {bank.device}
                assert sess.lens.devices() == {bank.device}
    for d in docs:
        assert sched.text(d) == ols[d].checkout_tip().snapshot()
    m = sched.metrics_json()
    w = m["window"]
    assert len({s.cap for b in sched.banks
                for s in b.sessions.values()}) == 2
    assert w["mesh_docs"] == 3 * len(docs)
    assert w["dispatches"] == w["shape_classes"] == 2 * w["device_windows"]
    assert m["totals"]["reads_from_host"] == 0
    assert m["totals"]["device_errors"] == 0
    # the bank's slot accounting is what its chip holds: one copy each
    for bank in sched.banks:
        assert bank.footprint_slots() == sum(
            s.cap for s in bank.sessions.values())


# ---- warmup --------------------------------------------------------------

def test_warmup_precompiles_mesh_shape_classes():
    """warmup_fused_cache(mesh_shards=N) compiles every padded-B mesh
    class; a second warmup over the same shapes is all cache hits."""
    from diamond_types_tpu.obs.devprof import PROFILER
    PROFILER.reset()
    PROFILER.enabled = True
    try:
        n = ff.warmup_fused_cache(flush_docs=2, cap=64, max_ins=2,
                                  shape_classes=(1,), mesh_shards=2)
        # fused batches {1, 2} + mesh padded-B classes {2, 4}
        assert n == 4
        snap1 = PROFILER.snapshot()["jit_cache"]["mesh"]
        assert snap1["misses"] == 2
        ff.warmup_fused_cache(flush_docs=2, cap=64, max_ins=2,
                              shape_classes=(1,), mesh_shards=2)
        snap2 = PROFILER.snapshot()["jit_cache"]["mesh"]
        assert snap2["hits"] >= snap1["hits"] + 2
        assert snap2["misses"] == snap1["misses"]
    finally:
        PROFILER.enabled = False


def test_scheduler_warmup_covers_first_window():
    """A warmed mesh-window scheduler's first real dispatch must hit
    the mesh jit cache, not compile on the flush path."""
    from diamond_types_tpu.obs.devprof import PROFILER
    ols = {}
    PROFILER.reset()
    PROFILER.enabled = True
    try:
        sched = _mk_sched(ols, 2, mesh_window=True, warmup=True,
                          fused_opts={"cap": 64, "max_ins": 2})
        sched.banks[0].join_warmup()
        misses0 = PROFILER.snapshot()["jit_cache"]["mesh"]["misses"]
        by_shard = _docs_on_two_shards(sched)
        docs = by_shard[0] + by_shard[1]
        rng = random.Random(5)
        for rnd in range(2):
            for d in docs:
                if rnd == 0:
                    ols[d] = _mk_oplog(d)
                _random_edits(ols[d], rng, 1)
                assert sched.submit(d, n_ops=1)["accepted"]
            sched.pump(force=True)
        snap = PROFILER.snapshot()["jit_cache"]["mesh"]
        assert snap["misses"] == misses0     # zero cold compiles
        assert snap["hits"] > 0
    finally:
        PROFILER.enabled = False
    for d in docs:
        assert sched.text(d) == ols[d].checkout_tip().snapshot()


# ---- fencing at window assembly ------------------------------------------

def test_fencing_recheck_at_window_assembly():
    """Work admitted under a lease epoch the host no longer holds is
    dropped when the WINDOW is assembled — it never joins the
    super-batch, and the window records zero dispatches."""
    ols = {}
    sched = _mk_sched(ols, 1, mesh_window=True)
    epoch = {"n": 1}
    sched.epoch_of = lambda d: epoch["n"]
    d = "fenced-doc"
    ols[d] = _mk_oplog(d)
    a = ols[d].get_or_create_agent_id("a")
    ols[d].add_insert(a, 0, "hello")
    assert sched.submit(d, n_ops=1)["accepted"]
    epoch["n"] = 2        # the lease moved between admit and window
    sched.pump(force=True)
    m = sched.metrics_json()
    assert m["totals"]["fenced"] == 1
    assert m["totals"]["syncs"] == 0
    assert m["window"]["windows"] == 1
    assert m["window"]["dispatches"] == 0
    assert m["window"]["device_windows"] == 0
    assert d not in sched.banks[0].sessions


# ---- prom rendering ------------------------------------------------------

def test_prom_renders_window_block():
    from diamond_types_tpu.obs.prom import render_metrics
    m = ServeMetrics(2, 4, 64)
    m.record_window(1, 6, 2, mesh_docs=6, padded_rows=8)
    m.record_window(0, 0, 1)
    text = render_metrics({"serve": m.snapshot()})
    assert "dt_serve_window_windows_total 2" in text
    assert "dt_serve_window_device_windows_total 1" in text
    assert "dt_serve_window_dispatches_total 1" in text
    assert "dt_serve_window_device_calls_per_window 1.0" in text
    assert "dt_serve_window_mesh_docs_total 6" in text
    assert "dt_serve_window_mesh_occupancy 0.75" in text
    assert 'dt_serve_window_shards_total{shards="2"} 1' in text
    lines = [ln for ln in text.splitlines() if ln.startswith("# TYPE")]
    assert len(lines) == len(set(lines))


# ---- CLI -----------------------------------------------------------------

def test_cli_mesh_window_flag_smoke(capsys):
    """--mesh-window / --no-mesh-window parse; the dry-run report
    carries the window block and the device-calls-per-window figure."""
    from diamond_types_tpu.tools.cli import main
    rc = main(["serve-bench", "--dry-run", "--mesh-window",
               "--no-workers", "--steady-rounds", "0"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "parity OK" in out
    assert "device calls/window" in out


# ---- runtime lock witness ------------------------------------------------

def test_concurrent_windows_witness_acyclic():
    """The runtime lock witness, enabled across concurrent pump and
    read traffic over mesh flush windows, observes an acyclic
    lock-class order graph — no thread was ever seen holding a
    higher-level lock while acquiring a lower one."""
    import threading

    from diamond_types_tpu.analysis import (witness_assert_acyclic,
                                            witness_disable,
                                            witness_enable,
                                            witness_reset,
                                            witness_snapshot)
    witness_reset()
    witness_enable()
    try:
        ols = {}
        sched = _mk_sched(ols, 2, mesh_window=True)
        by_shard = _docs_on_two_shards(sched)
        docs = by_shard[0] + by_shard[1]
        rng = random.Random(17)
        for d in docs:
            ols[d] = _mk_oplog(d)
        for rnd in range(3):
            # edits + submits are single-threaded (raw OpLog appends
            # are not a locked surface); the lock-bearing paths — pump
            # windows and reads — then run concurrently
            for d in docs:
                _random_edits(ols[d], rng, 2)
                assert sched.submit(d, n_ops=2)["accepted"]
            errs = []

            def pumper():
                try:
                    sched.pump(force=True)
                except Exception as e:     # pragma: no cover
                    errs.append(e)

            def reader():
                try:
                    for d in docs:
                        sched.text(d)
                except Exception as e:     # pragma: no cover
                    errs.append(e)

            threads = [threading.Thread(target=pumper) for _ in range(2)]
            threads += [threading.Thread(target=reader) for _ in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errs
        for d in docs:
            assert sched.text(d) == ols[d].checkout_tip().snapshot()
        snap = witness_snapshot()
        assert snap["enabled"]
        assert snap["acquires"] > 0
        assert snap["edge_count"] > 0
        assert snap["acyclic"], snap
        assert snap["violations"] == []
        witness_assert_acyclic()
    finally:
        witness_disable()
        witness_reset()


# ---- a dispatch laid out by home chip ------------------------------------------

def _placed_sessions(per_chip, cap, text="hello world"):
    """Twin lists of sessions over fresh oplogs: `per_chip[k]` of the
    first live on mesh device k, the twins on the default device (what
    `fused_replay` is given)."""
    import jax
    mesh = pm.serve_mesh(4)
    ols, sess, twins = [], [], []
    for k, dev in enumerate(mesh.devices.flat):
        for j in range(per_chip[k]):
            pair = []
            for _ in range(2):
                ol = _mk_oplog(f"c{k}-{j}")
                ol.add_insert(ol.get_or_create_agent_id("a"), 0,
                              f"{k}{j} " + text)
                pair.append(ol)
            with jax.default_device(dev):
                sess.append(ff.FusedDocSession(pair[0], cap=cap, max_ins=4))
            twins.append(ff.FusedDocSession(pair[1], cap=cap, max_ins=4))
            ols.append(pair)
            assert sess[-1].docs.devices() == {dev}
    return mesh, ols, sess, twins


def _replay_counts(table):
    return table.snapshot()["phases"]["mesh.replay"]["counts"]


@pytest.mark.parametrize("cap", [256, 1024])
def test_uneven_blocks_are_byte_equal_to_the_one_chip_rung(cap):
    """5 / 1 / 0 / 2 sessions a chip: the batch is 4 x 8 slots, the
    padding lies between the blocks, and every row and length equals
    what `fused_replay` makes of the same plans, on the chip the
    session lives on."""
    from diamond_types_tpu.obs.phases import PhaseTable
    from diamond_types_tpu.tpu.steer import STEER
    STEER.reset(table=True)
    mesh, ols, sess, twins = _placed_sessions((5, 1, 0, 2), cap)
    assert sess[0].cap == cap
    homes = [s.docs.devices() for s in sess]
    rng, rng_t = random.Random(23), random.Random(23)
    table = PhaseTable()
    for rnd in range(3):
        for (ol, ol_t) in ols:
            _random_edits(ol, rng, 1 + rnd)
            _random_edits(ol_t, rng_t, 1 + rnd)
        with table.phase("sched.flush"):
            ok, _d, bp, _st = pm.mesh_fused_replay(
                mesh, sess, [s.plan_tail() for s in sess])
        ok_t, _d = ff.fused_replay(twins, [s.plan_tail() for s in twins])
        assert all(ok) and all(ok_t) and bp == 32
        for s, t, (ol, _olt) in zip(sess, twins, ols):
            assert np.array_equal(np.asarray(s.docs), np.asarray(t.docs))
            assert int(s.lens) == int(t.lens) == s.doc_len
            assert s.text() == ol.checkout_tip().snapshot()
        assert [s.docs.devices() for s in sess] == homes
        assert [s.lens.devices() for s in sess] == homes
    got = _replay_counts(table)
    assert (got["rows"], got["rows_off_home"], got["ici_bytes"]) \
        == (24, 0, 0)
    assert got[f"cap.{cap}.padded_rows"] == 3 * 32
    STEER.reset(table=True)


def test_a_poisoned_row_keeps_its_session_and_a_homeless_one_replays():
    """One plan's projection is tampered with: its session keeps the
    very buffers it had, the others commit. A session whose row is
    spread over the mesh has no home: it replays all the same, in the
    emptiest block, and is the one row counted off home."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from diamond_types_tpu.obs.phases import PhaseTable
    mesh, ols, sess, _twins = _placed_sessions((5, 1, 0, 2), 256)
    rng = random.Random(29)
    for ol, _t in ols:
        _random_edits(ol, rng, 2)
    plans = [s.plan_tail() for s in sess]
    plans[3].new_len += 1
    row, length, text = sess[3].docs, sess[3].lens, sess[3].text()
    ok, *_ = pm.mesh_fused_replay(mesh, sess, plans)
    assert ok == [i != 3 for i in range(8)]
    assert sess[3].docs is row and sess[3].lens is length
    assert sess[3].text() == text
    assert getattr(sess[3], "_arena_tag", None) is None
    for i, (s, (ol, _t)) in enumerate(zip(sess, ols)):
        if i != 3:
            assert s.text() == ol.checkout_tip().snapshot()
    # the next window replays its whole tail, from its own row
    everywhere = NamedSharding(mesh, PartitionSpec())
    sess[7].docs = jax.device_put(sess[7].docs, everywhere)
    sess[7].lens = jax.device_put(sess[7].lens, everywhere)
    assert len(sess[7].docs.devices()) == 4
    for ol, _t in ols:
        _random_edits(ol, rng, 1)
    table = PhaseTable()
    with table.phase("sched.flush"):
        ok, *_ = pm.mesh_fused_replay(mesh, sess,
                                      [s.plan_tail() for s in sess])
    assert all(ok)
    got = _replay_counts(table)
    assert (got["rows"], got["rows_off_home"]) == (8, 1)
    # it came over once and has no home to go back to; chip 2's block
    # was the emptiest
    assert got["ici_bytes"] == 4 * 256 + 4
    assert sess[7].docs.devices() == {list(mesh.devices.flat)[2]}
    for s, (ol, _t) in zip(sess, ols):
        assert s.text() == ol.checkout_tip().snapshot()


def test_a_dispatch_is_two_programs_a_chip_and_the_replay(monkeypatch):
    """Whatever the blocks hold: one stack and one unstack a mesh
    device and the one `shard_map` program, 9 on four chips; 5 where
    the arena hands the state back and nothing is stacked."""
    from diamond_types_tpu.parallel import arena
    arena.reset_arenas()
    calls = []
    stack, unstack = ff._row_programs()
    inner_fn = pm.mesh_flush_fn

    def counted(name, fn):
        def call(*a):
            calls.append(name)
            return fn(*a)
        return call

    monkeypatch.setattr(ff, "_row_programs", lambda: (
        counted("stack", stack), counted("unstack", unstack)))
    monkeypatch.setattr(pm, "mesh_flush_fn", lambda *a: counted(
        "replay", inner_fn(*a)))
    mesh, ols, sess, _twins = _placed_sessions((5, 1, 0, 2), 256)
    rng = random.Random(31)
    for want in ({"stack": 4, "replay": 1, "unstack": 4},
                 {"replay": 1, "unstack": 4}):
        for ol, _t in ols:
            _random_edits(ol, rng, 2)
        plans = [s.plan_tail() for s in sess]
        calls.clear()
        ok, *_ = pm.mesh_fused_replay(mesh, sess, plans)
        assert all(ok)
        assert {n: calls.count(n) for n in set(calls)} == want
        assert len(calls) <= 2 * 4 + 1
    arena.reset_arenas()


def _ids_by_shard(sched, n):
    """`n` document ids for every shard, as the router sends them."""
    by = [[] for _ in sched.banks]
    i = 0
    while any(len(ids) < n for ids in by):
        d = f"u{i:04d}"
        ids = by[sched.router.shard_of(d)]
        if len(ids) < n:
            ids.append(d)
        i += 1
        assert i < 1 << 14
    return by


def test_an_uneven_window_of_two_classes_leaves_every_row_on_its_bank():
    """The scheduler's side of it: 5 / 1 / 0 / 2 documents a shard in
    each of two capacity classes, one dispatch a class, no row off
    home and none off its bank's chip."""
    from diamond_types_tpu.obs import Observability
    ols = {}
    sched = _mk_sched(ols, 4, mesh_window=True, place_on_devices=True,
                      max_sessions_per_shard=16, flush_docs=16)
    sched.attach_obs(Observability())
    by = _ids_by_shard(sched, 10)
    ids = by[0] + by[1][:2] + by[3][:4]
    for ids_s in by:
        for k, d in enumerate(ids_s):       # the classes alternate
            ols[d] = _mk_oplog(d)
            ols[d].add_insert(ols[d].get_or_create_agent_id("a"), 0,
                              "y" * (900 if k % 2 else 30))
    rng = random.Random(37)
    for rnd in range(3):
        for d in ids:
            _random_edits(ols[d], rng, 2)
            assert sched.submit(d, n_ops=2)["accepted"]
        sched.pump(force=True)
        for bank in sched.banks:
            for sess in bank.sessions.values():
                assert sess.docs.devices() == {bank.device} \
                    == sess.lens.devices()
    for d in ids:
        assert sched.text(d) == ols[d].checkout_tip().snapshot()
    snap = sched.obs.phases.snapshot()["phases"]
    flush = snap["sched.flush"]["counts"]
    replay = snap["mesh.replay"]["counts"]
    assert flush["homes_off_bank"] == 0 and flush["forced"] == 3
    assert replay["rows"] == flush["window_mesh_docs"] == 2 * 16
    assert replay["rows_off_home"] == 0 and replay["ici_bytes"] == 0
    caps = sorted({s.cap for b in sched.banks
                   for s in b.sessions.values()})
    assert len(caps) == 2
    for cap in caps:    # blocks of 5 / 1 / 0 / 2: one dispatch of 4 x 8
        assert replay[f"cap.{cap}.dispatches"] == 2
        assert replay[f"cap.{cap}.docs"] == 2 * 8
        assert replay[f"cap.{cap}.padded_rows"] == 2 * 32
    assert sched.metrics_json()["totals"]["reads_from_host"] == 0


def test_a_live_uneven_window_compiles_nothing():
    """After the boot warm-up and warm rounds that spread their
    documents evenly (one and two a chip), a live window whose blocks
    are 5 / 1 / 0 / 2 is no chip's first of its size: nothing compiles."""
    from diamond_types_tpu.tpu.runtime import COMPILE_STATS
    from diamond_types_tpu.tpu.steer import STEER
    STEER.reset(table=True)
    opts = {"cap": 256, "max_ins": 4}
    ff.warmup_fused_cache(flush_docs=8, mesh_shards=4, **opts)
    ols = {}
    sched = _mk_sched(ols, 4, mesh_window=True, place_on_devices=True,
                      fused_opts=opts, max_sessions_per_shard=16)
    by = _ids_by_shard(sched, 5)
    even = [d for ids in by for d in ids[:2]]
    uneven = by[0] + by[1][:1] + by[3][:2]
    rng = random.Random(41)
    for d in set(even) | set(uneven):
        ols[d] = _mk_oplog(d)
        _random_edits(ols[d], rng, 2)
        assert sched.submit(d, n_ops=2)["accepted"]
    sched.pump(force=True)                  # sessions built
    for docs in (even[::2], even):          # the warm rounds
        for d in docs:
            _random_edits(ols[d], rng, 2)
            assert sched.submit(d, n_ops=2)["accepted"]
        sched.pump(force=True)
    base = COMPILE_STATS.snapshot()
    for _rnd in range(2):
        for d in uneven:
            _random_edits(ols[d], rng, 2)
            assert sched.submit(d, n_ops=2)["accepted"]
        assert sched.pump(force=True) == len(uneven)
    assert COMPILE_STATS.delta(COMPILE_STATS.snapshot(),
                               base)["compiles"] == 0
    assert sched.metrics_json()["window"]["mesh_docs"] >= 2 * len(uneven)
    for d in ols:
        assert sched.text(d) == ols[d].checkout_tip().snapshot()
    STEER.reset(table=True)


def test_a_bank_warms_its_blocks_as_a_window_will_run_them():
    """A bank builds its sessions under `jax.default_device(its
    chip)`, which is part of a jitted program's cache key; a window
    stacks and cuts under none. The block programs a bank warms at its
    first session of a capacity class are the window's own: running
    them again as a window does compiles nothing."""
    from diamond_types_tpu.tpu.runtime import COMPILE_STATS
    ols = {}
    opts = {"cap": 2048, "max_ins": 4}      # no other test's class
    sched = _mk_sched(ols, 4, mesh_window=True, place_on_devices=True,
                      fused_opts=opts)
    by = _ids_by_shard(sched, 1)
    rng = random.Random(47)
    for (d,) in by:
        ols[d] = _mk_oplog(d)
        _random_edits(ols[d], rng, 2)
        assert sched.submit(d, n_ops=2)["accepted"]
    sched.pump(force=True)                  # a session a bank: built
    assert {s.cap for b in sched.banks for s in b.sessions.values()} \
        == {2048}
    base = COMPILE_STATS.snapshot()
    pm.warm_block_programs([b.device for b in sched.banks], 2048,
                           pm.block_classes(4, 4 * 8))
    assert COMPILE_STATS.delta(COMPILE_STATS.snapshot(),
                               base)["compiles"] == 0


# ---- the pump thread paces its windows ---------------------------------------------

class _Stop:
    """`_pump_stop` for a loop that runs `turns` times: every wait is
    written down, none is slept."""

    def __init__(self, turns):
        self.turns, self.waits = turns, []

    def wait(self, timeout):
        self.waits.append(timeout)
        return self.waits.count(self.interval) > self.turns

    def set(self):
        pass


def _paced_sched(monkeypatch, wall_s, device_s, turns=2, **kw):
    """A mesh-window scheduler with two documents on two shards, each
    a bucket of its own (`flush_docs` 1), whose windows run for real
    and say they took `wall_s`, `device_s` of it at the fence."""
    from diamond_types_tpu.obs import Observability
    ols = {}
    kw.setdefault("mesh_window", True)
    sched = _mk_sched(ols, 2, flush_docs=1, flush_deadline_s=0.05, **kw)
    sched.attach_obs(Observability())
    by_shard = _docs_on_two_shards(sched, n=1)
    docs = by_shard[0] + by_shard[1]
    rng = random.Random(43)
    for d in docs:
        ols[d] = _mk_oplog(d)
        _random_edits(ols[d], rng, 2)
        assert sched.submit(d, n_ops=2)["accepted"]
    sched.drain()                               # sessions built
    taken = []
    if sched.mesh_window:
        inner = sched._flush_window

        def window(batch, paced):
            taken.append((len(batch), paced, threading.current_thread()))
            return inner(batch, paced)[0], wall_s, device_s
        monkeypatch.setattr(sched, "_flush_window", window)
    stop = sched._pump_stop = _Stop(turns)
    stop.interval = 0.025                       # flush_deadline_s / 2

    def push():
        for d in docs:
            _random_edits(ols[d], rng, 1)
            assert sched.submit(d, n_ops=1)["accepted"]
    return sched, ols, docs, push, taken, stop


def _kinds(sched):
    ph = sched.obs.phases.snapshot()["phases"]
    return ({k: v for k, v in ph["sched.flush"]["counts"].items()
             if k in ("paced", "forced", "inline")},
            ph.get("sched.pause", {}).get("count", 0))



@pytest.mark.parametrize("wall_s,device_s,pause", [
    (0.020, 0.0, 0.140),        # all host work: seven parts out
    (0.030, 0.010, 0.130),      # the device's wait counts as out
    (0.300, 0.270, None),       # device-bound: never held back
    (2.000, 0.0, 0.700),        # capped: one deadline a bucket taken
])
def test_the_pump_loop_paces_its_windows(monkeypatch, wall_s, device_s,
                                         pause):
    """After a window that was not forced the pump thread sits out
    `(FLUSH_HOST_SHARE - 1) x host_s - device_s` on `_pump_stop`
    inside a `sched.pause` root, `host_s` capped at one flush deadline
    a bucket taken, and the window counts `paced`."""
    from diamond_types_tpu.serve import scheduler as sched_mod
    assert sched_mod.FLUSH_HOST_SHARE == 8
    sched, ols, docs, push, taken, stop = _paced_sched(
        monkeypatch, wall_s, device_s)
    before = _kinds(sched)
    push()
    sched.start_pump()
    pump = sched._pump_thread
    pump.join(timeout=30)
    assert not pump.is_alive()
    # one window of both buckets, on the pump thread, then idle turns
    assert taken == [(2, True, pump)]
    want = [0.025] + ([] if pause is None else [pytest.approx(pause)]) \
        + [0.025] * 2
    assert stop.waits == want
    kinds, pauses = _kinds(sched)
    assert kinds.get("paced", 0) == before[0].get("paced", 0) + 1
    assert kinds.get("inline", 0) == before[0].get("inline", 0)
    assert pauses == before[1] + (pause is not None)
    for d in docs:
        assert sched.text(d) == ols[d].checkout_tip().snapshot()


@pytest.mark.parametrize("how", ["drain", "forced", "inline"])
def test_a_window_off_the_pump_loop_is_never_paced(monkeypatch, how):
    """A drain, a forced pump and another thread's own `pump()` run
    their windows at once and write no `sched.pause`."""
    sched, ols, docs, push, taken, stop = _paced_sched(
        monkeypatch, 0.020, 0.0)
    before = _kinds(sched)
    push()
    if how == "drain":
        sched.drain()
    elif how == "forced":
        assert sched.pump(force=True) == 2
    else:
        import time
        assert sched.pump(now=time.monotonic() + 1.0) == 2
    assert taken == [(2, False, threading.current_thread())]
    assert stop.waits == []
    kinds, pauses = _kinds(sched)
    kind = "inline" if how == "inline" else "forced"
    assert kinds.get(kind, 0) == before[0].get(kind, 0) + 1
    assert kinds.get("paced", 0) == 0 and pauses == 0
    for d in docs:
        assert sched.text(d) == ols[d].checkout_tip().snapshot()


def test_stop_pump_ends_a_pause_at_once(monkeypatch):
    """A pause of seventy seconds (a window that says it took ten, at
    a deadline of ten a bucket) ends when the pump is stopped."""
    import time
    sched, ols, docs, push, taken, _stop = _paced_sched(
        monkeypatch, 10.0, 0.0)
    sched.queue.flush_deadline_s = 10.0
    sched._pump_stop = threading.Event()
    entered, inner = threading.Event(), sched._sit_out

    def sit_out(pause_s):
        assert pause_s == pytest.approx(70.0)
        entered.set()
        inner(pause_s)
    monkeypatch.setattr(sched, "_sit_out", sit_out)
    push()
    sched.start_pump(interval_s=0.01)
    pump = sched._pump_thread
    assert entered.wait(30) and pump.is_alive()
    t0 = time.monotonic()
    sched.stop_pump(drain=False)
    assert not pump.is_alive() and time.monotonic() - t0 < 1.5
    assert _kinds(sched)[1] == 1        # the root closed: a row


def test_a_pump_without_mesh_windows_never_pauses(monkeypatch):
    """No `mesh_window`: the loop hands out or runs its batches as
    before, and the pump thread writes no `sched.pause`."""
    sched, ols, docs, push, taken, stop = _paced_sched(
        monkeypatch, 0.020, 0.0, mesh_window=False)
    assert not sched.mesh_window
    push()
    sched.start_pump()
    pump = sched._pump_thread
    pump.join(timeout=30)
    assert not pump.is_alive()
    assert stop.waits == [0.025] * 3 and taken == []
    kinds, pauses = _kinds(sched)
    assert pauses == 0 and kinds.get("paced", 0) == 0
    for d in docs:
        assert sched.text(d) == ols[d].checkout_tip().snapshot()
