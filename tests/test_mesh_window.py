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
    window families, and the --mesh-window CLI flag.

Runs on the CPU-simulated mesh (conftest pins JAX_PLATFORMS=cpu and an
8-device virtual host platform).
"""

import random

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
