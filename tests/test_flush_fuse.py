"""Fused vmapped bucket flush (tpu/flush_fuse.py + serve/ wiring).

Covers the ISSUE-5 tentpole surface: kernel-level parity of the fused
replay against the host oracle on randomized mixed-size buckets, the
poisoned-length (-1) contract propagating through `sync_docs` into an
evict + host fallback, the per-shard flush worker pool genuinely
overlapping flush windows across shards (no process-global sync-lock
serialization), and the fencing recheck still running INSIDE the
worker. CPU-simulated devices via conftest's virtual 8-device mesh.
"""

import random
import threading
import time

import pytest

from diamond_types_tpu.serve.admission import PendingMerge
from diamond_types_tpu.serve.bank import SessionBank
from diamond_types_tpu.serve.metrics import ServeMetrics
from diamond_types_tpu.serve.scheduler import MergeScheduler
from diamond_types_tpu.text.oplog import OpLog
from diamond_types_tpu.tpu import flush_fuse as ff

pytestmark = [pytest.mark.fused, pytest.mark.serve]

FUSED_OPTS = {"cap": 256, "max_ins": 4}


def _mk_oplog(doc_id: str) -> OpLog:
    ol = OpLog()
    ol.doc_id = doc_id
    return ol


def _random_edits(ol: OpLog, rng: random.Random, n: int,
                  agent: str = "a") -> None:
    """Mixed-size edits, including ops longer than max_ins (forcing the
    planner's chunk split) and deletes."""
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


def _items(doc_ids):
    return [PendingMerge(d, 1, 0.0) for d in doc_ids]


def _bank_text(bank, doc_id):
    """A resident session's text: the device row through the bank's
    fetch, a host session's own."""
    sess = bank.sessions[doc_id]
    return sess.text() if bank.engine == "host" \
        else bank.read_row(sess)[0]


# ---- kernel-level parity -------------------------------------------------

def test_fused_replay_parity_randomized_mixed_buckets():
    """Fused whole-bucket replay == host checkout on randomized
    mixed-size docs, including concurrent two-agent histories."""
    rng = random.Random(11)
    ols = [_mk_oplog(f"d{i}") for i in range(5)]
    for i, ol in enumerate(ols):
        _random_edits(ol, rng, 2 + i)
    sess = [ff.FusedDocSession(ol, **FUSED_OPTS) for ol in ols]
    for rnd in range(3):
        for i, ol in enumerate(ols):
            _random_edits(ol, rng, 1 + (i + rnd) % 3)
            if rnd == 1:
                # a concurrent branch from an old frontier — lands as
                # host-transformed positional ops
                b = ol.get_or_create_agent_id("b")
                ol.add_insert_at(b, [], 0, "Z" * (i + 1))
        plans = [s.plan_tail() for s in sess]
        fits = [p.fits(s.cap) for p, s in zip(plans, sess)]
        assert all(fits)
        ok, _dev = ff.fused_replay(sess, plans)
        assert all(ok)
        for s, ol in zip(sess, ols):
            assert s.text() == ol.checkout_tip().snapshot()


def test_fused_fn_per_doc_poison():
    """A bounded-shift contract violation poisons only ITS doc's
    length; bucket neighbors keep a valid result."""
    import jax.numpy as jnp
    import numpy as np
    fn = ff._fused_fn(2, 1, 2, 8)
    docs = jnp.zeros((2, 8), jnp.int32)
    lens = jnp.zeros((2,), jnp.int32)
    pos = jnp.zeros((2, 1), jnp.int32)
    dlen = jnp.zeros((2, 1), jnp.int32)
    # doc 0 violates (ilen 3 > max_ins 2); doc 1 inserts legally
    ilen = jnp.asarray([[3], [2]], jnp.int32)
    chars = jnp.full((2, 1, 2), ord("x"), jnp.int32)
    _out, out_lens = fn(docs, lens, pos, dlen, ilen, chars)
    got = np.asarray(out_lens)
    assert got[0] == -1 and got[1] == 2


def test_capacity_overflow_grows_then_converges():
    """Since PR 36 an overflowing tail grows the session on the device
    (tests/test_session_growth.py); it is not rebuilt from the host."""
    ol = _mk_oplog("grow")
    a = ol.get_or_create_agent_id("a")
    ol.add_insert(a, 0, "seed")
    sess = ff.FusedDocSession(ol, **FUSED_OPTS)
    r0 = sess.resyncs
    ol.add_insert(a, 0, "y" * 600)     # tail overflows cap=256
    sess.sync()
    sess.sync()
    assert sess.resyncs == r0 and sess.cap == 1024
    assert sess.text() == ol.checkout_tip().snapshot()


# ---- bank-level: fused vs per-doc vs host --------------------------------

def test_sync_docs_three_engine_parity():
    """The same randomized bucket through the fused replay
    (`sync_docs`), the per-doc path below it (`sync_doc`, one replay a
    document) and a host bank — all three parity with the oplog
    authority."""
    docs = [f"p{i}" for i in range(4)]

    def run(engine, per_doc=False):
        ols = {d: _mk_oplog(d) for d in docs}
        # fresh rng per engine so all three see identical histories
        r = random.Random(77)
        bank = SessionBank(0, engine=engine, fused_opts=FUSED_OPTS,
                           metrics=ServeMetrics(1, 4, 64))

        def flush():
            if per_doc:
                return [bank.sync_doc(d, ols[d]) for d in docs]
            return bank.sync_docs(_items(docs), ols.__getitem__)

        for d in docs:
            _random_edits(ols[d], r, 3)
        flush()
        for d in docs:
            _random_edits(ols[d], r, 2)
        res = flush()
        return {d: _bank_text(bank, d) for d in docs}, ols, res, bank

    fused_txt, fols, fres, fbank = run("device")
    perdoc_txt, pols, pres, pbank = run("device", per_doc=True)
    host_txt, hols, _hres, _ = run("host")
    for d in docs:
        want = fols[d].checkout_tip().snapshot()
        assert fused_txt[d] == want
        assert perdoc_txt[d] == pols[d].checkout_tip().snapshot()
        assert host_txt[d] == hols[d].checkout_tip().snapshot()
        # identical seeds -> identical content across engines
        assert fused_txt[d] == perdoc_txt[d] == host_txt[d]
    # the second flush had 4 resident sessions with fresh tails: the
    # fused path must actually have fired, in ONE device call
    assert fres["fused_calls"] == 1 and fres["fused_docs"] == 4
    m = fbank.metrics.snapshot()
    assert m["fused"]["device_calls"] >= 1
    assert m["fused"]["occupancy"] > 1
    # the per-doc path answered from the device too, a replay a doc
    assert all(r["engine"] == "device" for r in pres)
    pm = pbank.metrics.snapshot()
    assert pm["fused"]["device_calls"] == 0
    assert pm["totals"]["reads_from_device"] == len(docs)


def test_poisoned_lens_propagates_to_host_fallback(monkeypatch):
    """A fused result whose length comes back poisoned/mismatched must
    evict the session and serve the doc from the host engine — the
    `lens == -1` contract propagating through sync_docs."""
    docs = ["x0", "x1"]
    ols = {d: _mk_oplog(d) for d in docs}
    rng = random.Random(9)
    for d in docs:
        _random_edits(ols[d], rng, 3)
    metrics = ServeMetrics(1, 4, 64)
    bank = SessionBank(0, engine="device", fused_opts=FUSED_OPTS,
                       metrics=metrics)
    bank.sync_docs(_items(docs), ols.__getitem__)   # builds
    for d in docs:
        _random_edits(ols[d], rng, 2)

    real_plan = ff.FusedDocSession.plan_tail

    def bad_plan(self):
        plan = real_plan(self)
        if self.oplog.doc_id == "x0" and plan.n_ops:
            # a delete longer than max_ins reaching the kernel: the
            # device poisons this doc's length to -1
            plan.dlen[0] = self.max_ins + 1
        return plan

    monkeypatch.setattr(ff.FusedDocSession, "plan_tail", bad_plan)
    res = bank.sync_docs(_items(docs), ols.__getitem__)
    monkeypatch.undo()
    assert res["fused_calls"] == 1
    assert "x0" not in bank.sessions          # evicted
    snap = metrics.snapshot()
    assert snap["totals"]["host_fallbacks"] == 1
    # the healthy neighbour's row holds its document
    assert _bank_text(bank, "x1") == ols["x1"].checkout_tip().snapshot()


def test_bank_fused_rung_failure_propagates(monkeypatch):
    """A `fused_replay` that raises (compiler, runtime) is no data
    fault: counted (`device_errors`), recorded as rung "fused" with its
    text, and raised — nothing replays the bucket on a quieter path.
    The sessions it left behind are brought to the tip by the reads
    that come once the device answers again (`reads_from_device`)."""
    from diamond_types_tpu.obs import Observability
    ols = {}
    sched = MergeScheduler(1, resolve=lambda d: ols[d], engine="device",
                           fused_opts=FUSED_OPTS, flush_docs=8,
                           flush_deadline_s=10.0, flush_workers=False)
    sched.attach_obs(Observability())
    rng = random.Random(31)
    docs = [f"d{i}" for i in range(4)]
    per_doc = []
    real_sync = ff.FusedDocSession.sync
    monkeypatch.setattr(
        ff.FusedDocSession, "sync",
        lambda self: per_doc.append(self.oplog.doc_id) or real_sync(self))
    for rnd in range(3):
        for d in docs:
            if rnd == 0:
                ols[d] = _mk_oplog(d)
            _random_edits(ols[d], rng, 2)
            assert sched.submit(d, n_ops=2)["accepted"]
        if rnd == 2:
            def boom(sessions, plans):
                raise RuntimeError("injected fused failure")
            # sync_docs re-resolves the module attribute a call
            monkeypatch.setattr(ff, "fused_replay", boom)
            with pytest.raises(RuntimeError, match="injected fused"):
                sched.pump(force=True)
        else:
            sched.pump(force=True)
    monkeypatch.undo()
    assert per_doc == []
    m = sched.metrics_json()
    assert m["totals"]["device_errors"] == 1
    assert m["totals"]["host_fallbacks"] == 0
    assert m["fused"]["device_calls"] == 1      # round 1's, not round 2's
    ev = [e for e in sched.obs.recorder.dump()
          if e["kind"] == "device_error"]
    assert [e["rung"] for e in ev] == ["fused"]
    assert "injected fused failure" in ev[0]["error"]
    for d in docs:
        assert sched.text(d) == ols[d].checkout_tip().snapshot()
    m = sched.metrics_json()
    assert m["totals"]["reads_from_device"] == len(docs)
    assert m["totals"]["reads_from_host"] == 0


# ---- scheduler-level: workers, concurrency, fencing ----------------------

def _two_shard_docs(sched, n=2):
    """Doc ids rendezvous-routed to shards 0 and 1, n per shard."""
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


def test_two_shard_concurrent_flush_windows():
    """The worker pool + per-device locks must let two shards' flush
    windows OVERLAP: each shard's worker blocks on a shared barrier
    inside sync_docs, which only releases when both are inside their
    flush simultaneously. A process-global sync lock (the pre-fusion
    design) would deadlock the barrier."""
    ols = {}
    sched = MergeScheduler(2, resolve=lambda d: ols[d],
                           engine="device", fused_opts=FUSED_OPTS,
                           flush_docs=2, flush_deadline_s=10.0,
                           flush_workers=True)
    by_shard = _two_shard_docs(sched)
    rng = random.Random(3)
    for shard_docs in by_shard.values():
        for d in shard_docs:
            ols[d] = _mk_oplog(d)
            _random_edits(ols[d], rng, 2)

    barrier = threading.Barrier(2, timeout=10)
    overlapped = []
    orig = SessionBank.sync_docs

    def synced_sync_docs(self, items, resolve, **kw):
        try:
            barrier.wait()
            overlapped.append(self.shard_id)
        except threading.BrokenBarrierError:   # pragma: no cover
            pass
        return orig(self, items, resolve, **kw)

    SessionBank.sync_docs = synced_sync_docs
    try:
        for shard_docs in by_shard.values():
            for d in shard_docs:
                assert sched.submit(d, n_ops=1)["accepted"]
        sched.pump(force=True)
        sched.drain()
    finally:
        SessionBank.sync_docs = orig
        sched.stop_workers()
    assert sorted(overlapped) == [0, 1], overlapped
    assert not barrier.broken
    for d, ol in ols.items():
        assert sched.text(d) == ol.checkout_tip().snapshot()


def test_fencing_recheck_runs_inside_worker():
    """Work admitted under a lease epoch the host no longer holds must
    be dropped BY THE WORKER at flush time, not merged."""
    ols = {}
    sched = MergeScheduler(1, resolve=lambda d: ols[d],
                           engine="device", fused_opts=FUSED_OPTS,
                           flush_docs=8, flush_deadline_s=10.0,
                           flush_workers=True)
    epoch = {"n": 1}
    sched.epoch_of = lambda d: epoch["n"]
    d = "fenced-doc"
    ols[d] = _mk_oplog(d)
    a = ols[d].get_or_create_agent_id("a")
    ols[d].add_insert(a, 0, "hello")
    assert sched.submit(d, n_ops=1)["accepted"]
    epoch["n"] = 2        # the lease moved between admit and flush
    sched.pump(force=True)
    sched.drain()
    sched.stop_workers()
    m = sched.metrics_json()
    assert m["totals"]["fenced"] == 1
    assert m["totals"]["syncs"] == 0      # never merged
    assert d not in sched.banks[0].sessions


def test_scheduler_fused_end_to_end_counters():
    """Two pump rounds through one shard: round 1 builds, round 2 must
    fold the whole bucket into one fused device call, with the
    occupancy histogram and devprof attribution populated."""
    from diamond_types_tpu.obs.devprof import PROFILER
    ols = {}
    sched = MergeScheduler(1, resolve=lambda d: ols[d],
                           engine="device", fused_opts=FUSED_OPTS,
                           flush_docs=8, flush_deadline_s=10.0,
                           flush_workers=False)
    docs = [f"e{i}" for i in range(3)]
    rng = random.Random(1)
    PROFILER.reset()
    PROFILER.enabled = True
    try:
        for rnd in range(2):
            for d in docs:
                if rnd == 0:
                    ols[d] = _mk_oplog(d)
                _random_edits(ols[d], rng, 2)
                assert sched.submit(d, n_ops=1)["accepted"]
            sched.pump(force=True)
        m = sched.metrics_json()
        assert m["version"] == ServeMetrics.SCHEMA_VERSION
        assert m["fused"]["device_calls"] >= 1
        assert m["fused"]["occupancy"] > 1
        assert m["fused"]["occupancy_hist"]
        dp = PROFILER.snapshot()
        assert dp["fused"]["device_calls"] == \
            m["fused"]["device_calls"]
        assert dp["fused"]["docs"] == m["fused"]["docs"]
        assert "fused" in dp["jit_cache"]
    finally:
        PROFILER.enabled = False
    for d in docs:
        assert sched.text(d) == ols[d].checkout_tip().snapshot()


# ---- warmup + jit cache --------------------------------------------------

def test_warmup_populates_fused_jit_cache():
    from diamond_types_tpu.obs.devprof import PROFILER
    PROFILER.reset()
    PROFILER.enabled = True
    try:
        # tiny dedicated shape class so this test owns its cache keys
        n = ff.warmup_fused_cache(flush_docs=2, cap=64, max_ins=2,
                                  shape_classes=(1,))
        assert n == 2        # batches {1, 2} x one op class
        snap1 = PROFILER.snapshot()["jit_cache"]["fused"]
        # a second warmup over the same shapes is all hits
        ff.warmup_fused_cache(flush_docs=2, cap=64, max_ins=2,
                              shape_classes=(1,))
        snap2 = PROFILER.snapshot()["jit_cache"]["fused"]
        assert snap2["hits"] >= snap1["hits"] + 2
        assert snap2["misses"] == snap1["misses"]
    finally:
        PROFILER.enabled = False


def test_bank_background_warmup_thread_joins():
    bank = SessionBank(0, engine="device",
                       fused_opts={"cap": 64, "max_ins": 2},
                       warmup=True, flush_docs=2)
    bank.join_warmup()
    assert bank._warmup_thread is not None
    assert not bank._warmup_thread.is_alive()


# ---- rows into the batch and out of it, one program each way --------------
#
# A `fused_replay` call is three device programs whatever the batch:
# `jit_dt_stack_rows`, `jit_dt_fused_replay`, `jit_dt_unstack_rows`
# (`flush_fuse._row_programs`). Counts and bytes on the CPU, never a
# time.

ROW_BATCHES = (1, 3, 8)
ROW_PROGRAMS = ["jit(dt_fused_replay)", "jit(dt_stack_rows)",
                "jit(dt_unstack_rows)"]


@pytest.fixture
def cold_programs(monkeypatch):
    """No jitted program of this module yet and no class noted warm,
    whatever the process ran before: a first call of a class is the
    first one to compile it."""
    from diamond_types_tpu.tpu import steer
    monkeypatch.setattr(ff, "_fused_jit_cache", {})
    monkeypatch.setattr(ff, "_row_fns", None)
    monkeypatch.setattr(steer, "STEER", steer.ShapeSteer())


def _row_fleet(n: int, cap: int, tag: str):
    """`n` sessions of one capacity class over short typed documents."""
    ols = [_mk_oplog(f"{tag}{i}") for i in range(n)]
    for i, ol in enumerate(ols):
        ol.add_insert(ol.get_or_create_agent_id("a"), 0, f"doc {i}: ")
    return ols, [ff.FusedDocSession(ol, cap=cap, max_ins=4) for ol in ols]


def _type(ols, text="ab") -> None:
    """One plan row a document: every call pads to the same class
    (two scan steps, the smallest)."""
    for ol in ols:
        ol.add_insert(ol.get_or_create_agent_id("a"), 2, text)


def _replay_logged(sess, caplog):
    """`fused_replay` of every session's tail: (ok, the modules JAX
    compiled for it)."""
    import jax

    plans = [s.plan_tail() for s in sess]
    caplog.clear()
    with caplog.at_level("WARNING", logger="jax._src.interpreters.pxla"), \
            jax.log_compiles():
        ok, _dev = ff.fused_replay(sess, plans)
    return ok, sorted(r.args[0] for r in caplog.records
                      if r.msg.startswith("Compiling %s with global"))


@pytest.mark.parametrize("b", ROW_BATCHES)
def test_a_call_is_three_programs_whatever_the_batch(b, caplog,
                                                     cold_programs):
    """A first call of a class compiles the three programs and nothing
    else (no eager program a row), a second compiles none although its
    rows now come out of `dt_unstack_rows` (and one straight from a
    build) where the first call's all came from builds; every session
    reads as the host checks it out."""
    cap = 1024
    ols, sess = _row_fleet(b + 1, cap, f"three{b}-")
    _type(ols)
    ok, compiled = _replay_logged(sess[:b], caplog)
    assert ok == [True] * b
    assert compiled == ROW_PROGRAMS
    # the spare session takes the last one's lane: the same class
    _type(ols)
    again = sess[:b - 1] + sess[b:]
    ok, compiled = _replay_logged(again, caplog)
    assert ok == [True] * b and compiled == []
    for s, ol in zip(again, ols[:b - 1] + ols[b:]):
        assert s.text() == ol.checkout_tip().snapshot()
        assert s.docs.shape == (cap,) and s.lens.shape == ()


@pytest.mark.parametrize("b", ROW_BATCHES)
def test_a_poisoned_lane_keeps_its_old_row(b):
    """The stack donates nothing: a lane that fails the length fence
    is not committed and its session still owns a live row, the text
    it had, and replays the same tail the next time."""
    import numpy as np
    ols, sess = _row_fleet(b, 512, f"poison{b}-")
    _type(ols)
    assert all(ff.fused_replay(sess, [s.plan_tail() for s in sess])[0])
    before = [s.text() for s in sess]
    _type(ols, "wxyz")
    plans = [s.plan_tail() for s in sess]
    bad = b // 2
    plans[bad].ilen = plans[bad].ilen + 4       # over `max_ins`: -1
    ok, _dev = ff.fused_replay(sess, plans)
    assert ok == [i != bad for i in range(b)]
    assert sess[bad].text() == before[bad]
    assert int(np.asarray(sess[bad].lens)) == len(before[bad])
    assert sess[bad].synced_to < len(ols[bad])
    assert ff.fused_replay([sess[bad]], [sess[bad].plan_tail()])[0] == [True]
    for s, ol in zip(sess, ols):
        assert s.text() == ol.checkout_tip().snapshot()


@pytest.mark.parametrize("b", ROW_BATCHES)
def test_rows_are_buffers_of_their_own(b):
    """A session committed in one call and absent from the next still
    reads right after that call donated its batch: `dt_unstack_rows`
    hands every row and length out as its own buffer."""
    ols, sess = _row_fleet(b + 1, 512, f"own{b}-")
    _type(ols)
    assert all(ff.fused_replay(sess[:b], [s.plan_tail()
                                          for s in sess[:b]])[0])
    rows = {s.docs.unsafe_buffer_pointer() for s in sess}
    lens = {s.lens.unsafe_buffer_pointer() for s in sess}
    assert len(rows) == len(lens) == b + 1
    absent, absent_text = sess[b - 1], sess[b - 1].text()
    _type(ols)
    again = sess[:b - 1] + sess[b:]
    for _ in range(2):      # the second call's batch is built of the first's rows
        assert all(ff.fused_replay(again, [s.plan_tail()
                                           for s in again])[0])
        _type(ols[:b - 1] + ols[b:])
    assert absent.text() == absent_text
    assert all(ff.fused_replay([absent], [absent.plan_tail()])[0])
    assert absent.text() == ols[b - 1].checkout_tip().snapshot()


def test_the_warm_up_compiles_the_row_programs_a_flush_meets(
        caplog, cold_programs):
    """`warmup_fused_cache` runs a class's batch through the two row
    programs, on rows as a build leaves them: the first real flush of
    a warmed class compiles nothing."""
    cap = 2048
    ff.warmup_fused_cache(flush_docs=2, cap=cap, max_ins=4,
                          shape_classes=(1,))
    for b in (1, 2):
        ols, sess = _row_fleet(b, cap, f"warm{b}-")
        _type(ols)
        ok, compiled = _replay_logged(sess, caplog)
        assert ok == [True] * b and compiled == []


# ---- prom rendering of the fused block -----------------------------------

def test_prom_renders_fused_block():
    from diamond_types_tpu.obs.prom import render_metrics
    m = ServeMetrics(1, 4, 64)
    m.record_fused(0, 3)
    m.record_fused(0, 3)
    text = render_metrics({"serve": m.snapshot()})
    assert "dt_serve_fused_occupancy 3.0" in text
    assert 'dt_serve_fused_flush_total{docs="3"} 2' in text
    assert "dt_serve_fused_calls_total 2" in text
    assert "dt_serve_fused_docs_total 6" in text
    # one TYPE line per family, no duplicates
    lines = [ln for ln in text.splitlines() if ln.startswith("# TYPE")]
    assert len(lines) == len(set(lines))


# ---- CLI flags -----------------------------------------------------------

def test_cli_serve_bench_fused_flags_smoke(capsys):
    """--workers/--no-workers, --parity, --steady-rounds all parse and
    the dry-run smoke passes parity; the switches that selected the
    retired flush paths are refused by the parser."""
    from diamond_types_tpu.tools.cli import main
    rc = main(["serve-bench", "--dry-run",
               "--no-workers", "--parity", "--steady-rounds", "0"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "parity OK" in out
    assert "fused calls" in out
    for flag in ("--no-fused", "--device-plan", "--pallas"):
        with pytest.raises(SystemExit):
            main(["serve-bench", "--dry-run", flag])
    capsys.readouterr()


# ---- the plan walk on the oplog's native mirror ----------------------------
#
# `plan_tail` transforms a session's tail on the oplog's `NativeContext`
# (kept current by appending); the Python `TransformedOps` walk is its
# oracle: the same session planned again under DT_TPU_NO_NATIVE=1 must
# give the same plan, field for field.

def _plans_equal(a: ff.TailPlan, b: ff.TailPlan) -> None:
    for f in ("pos", "dlen", "ilen", "chars"):
        x, y = getattr(a, f), getattr(b, f)
        assert x.dtype == y.dtype and x.shape == y.shape, f
        assert (x == y).all(), f
    for f in ("n_ops", "new_len", "max_len", "frontier", "synced_to"):
        assert getattr(a, f) == getattr(b, f), f
        assert type(getattr(a, f)) is type(getattr(b, f)), f


def _plan_both_ways(sess, monkeypatch) -> ff.TailPlan:
    """The native plan, checked against the Python walk's; both are
    pure reads of the session."""
    before = (sess.frontier, sess.synced_to, sess.doc_len)
    native = sess.plan_tail()
    with monkeypatch.context() as m:
        m.setenv("DT_TPU_NO_NATIVE", "1")
        oracle = sess.plan_tail()
    assert (sess.frontier, sess.synced_to, sess.doc_len) == before
    _plans_equal(native, oracle)
    return native


def _replay_rows(model: list, plan: ff.TailPlan) -> None:
    """What the device does with a plan, on a list of code points."""
    for p, d, il, ch in zip(plan.pos.tolist(), plan.dlen.tolist(),
                            plan.ilen.tolist(), plan.chars):
        del model[p:p + d]
        model[p:p] = ch[:il].tolist()


def _commit(sess, plan, model: list) -> None:
    """Adopt `plan` as a flush would, the device's part played by
    `_replay_rows` (a plan walk reads the bookkeeping, never the row)."""
    _replay_rows(model, plan)
    sess.commit(sess.docs, sess.lens, plan)
    assert sess.doc_len == len(model)


class _Paper:
    """The `b4-papers` shape at a tenth of the size: a typed document,
    two `corpus.Typist` writers a region each, from their own heads
    (they never see each other), the warm rounds' scatters first."""

    def __init__(self, seed: int, n_ops: int = 6000) -> None:
        import numpy as np
        from bench import corpus
        self.ol = ol = _mk_oplog("paper")
        pos, nd, ni, chars = corpus.doc_columns(seed, 0, n_ops)
        ol.apply_local_patch_columns(ol.get_or_create_agent_id("seed"),
                                     pos, nd, ni, chars.decode())
        self.rng = np.random.default_rng([seed, 99])
        self.plain = corpus.PlainDoc(
            "paper", corpus.doc_text(seed, 0, n_ops), 2,
            [corpus.Typist(np.random.default_rng([seed, 1, w]), {})
             for w in range(2)])
        self.heads = [list(ol.version), list(ol.version)]

    def push(self, w: int, ops) -> None:
        """What the server's edit handler does with a push."""
        ol = self.ol
        agent = ol.get_or_create_agent_id(f"writer{w}")
        f = self.heads[w]
        for op in ops:
            if op["kind"] == "ins":
                f = [ol.add_insert_at(agent, f, op["pos"], op["text"])]
            else:
                f = [ol.add_delete_at(agent, f, op["start"], op["end"],
                                      None)]
        self.heads[w] = f
        self.plain.acknowledge(w, ops, f)

    def warm_round(self, n: int) -> None:
        for w in (0, 1):
            self.push(w, self.plain.scatter(self.rng, w, n))

    def type(self, w: int, n: int = 8) -> None:
        self.push(w, self.plain.next_push(w, n))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_native_plan_equals_the_python_walk_papers_shape(seed, monkeypatch):
    from diamond_types_tpu.obs.phases import PhaseTable
    doc = _Paper(seed)
    sess = ff.FusedDocSession(doc.ol, cap=1 << 14)
    model = list(doc.plain.text())
    assert sess.doc_len == len(model)
    table = PhaseTable()
    walks = 0
    with table.phase("sched.flush"):
        for n in (2, 16, 64):
            doc.warm_round(n)
            _commit(sess, _plan_both_ways(sess, monkeypatch), model)
            walks += 1
        for i in range(40):
            doc.type(i % 2)
            if i % 7 == 3:
                continue                # two pushes in one tail
            plan = _plan_both_ways(sess, monkeypatch)
            walks += 1
            if i % 5 == 4:
                continue                # a plan dropped un-committed
            _commit(sess, plan, model)
        _commit(sess, _plan_both_ways(sess, monkeypatch), model)
        walks += 1
        # nothing pending: no walk, nothing counted
        assert sess.plan_tail().n_ops == 0
    assert bytes(model) == bytes(doc.plain.text())
    counts = table.snapshot()["phases"]["plan.tail"]["counts"]
    assert counts["xf_native"] == counts["xf_python"] == walks
    assert counts["mirror_rebuilt"] == 0
    assert 0 < counts["mirror_appended"] <= walks


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_native_plan_equals_the_python_walk_writers_who_merge(seed,
                                                              monkeypatch):
    """Writers who pull each other's heads, inserts longer than
    `max_ins`, deletes of text the other writer already deleted."""
    rng = random.Random(seed)
    ol = _mk_oplog("merging")
    _random_edits(ol, rng, 12, agent="seed")
    sess = ff.FusedDocSession(ol, **FUSED_OPTS)
    model = [ord(c) for c in ol.checkout_tip().snapshot()]
    agents = [ol.get_or_create_agent_id(n) for n in ("a", "b", "c")]
    heads = [list(ol.version) for _ in agents]
    for i in range(60):
        w = rng.randrange(3)
        text = ol.checkout(heads[w]).snapshot()
        for _ in range(rng.randint(1, 3)):
            if text and rng.random() < 0.4:
                p = rng.randrange(len(text))
                e = min(p + rng.randint(1, 9), len(text))
                heads[w] = [ol.add_delete_at(agents[w], heads[w], p, e,
                                             None)]
                text = text[:p] + text[e:]
            else:
                p = rng.randint(0, len(text))
                s = "".join(rng.choice("abcdefgh")
                            for _ in range(rng.randint(1, 11)))
                heads[w] = [ol.add_insert_at(agents[w], heads[w], p, s)]
                text = text[:p] + s + text[p:]
        if rng.random() < 0.35:
            o = rng.choice([x for x in range(3) if x != w])
            heads[w] = list(ol.cg.graph.version_union(heads[w], heads[o]))
        if i % 3 == 2:
            plan = _plan_both_ways(sess, monkeypatch)
            if rng.random() < 0.8:      # else dropped un-committed
                _commit(sess, plan, model)
    _commit(sess, _plan_both_ways(sess, monkeypatch), model)
    assert "".join(map(chr, model)) == ol.checkout_tip().snapshot()


def test_64_way_concurrent_merge_planned_on_the_native_mirror(monkeypatch):
    """64 agents insert concurrently from the same frontier: one
    `plan_tail` on the native mirror resolves the whole sibling order
    (the Python walk agrees, field for field), one `fused_replay`
    applies it, and the device row is the host oracle's text."""
    from diamond_types_tpu.obs.phases import PhaseTable
    ol = _mk_oplog("wide")
    a0 = ol.get_or_create_agent_id("seed")
    ol.add_insert(a0, 0, "base ")
    sess = ff.FusedDocSession(ol, cap=1024, max_ins=4)
    base = list(ol.version)
    for k in range(64):
        ag = ol.get_or_create_agent_id(f"w{k}")
        ol.add_insert_at(ag, base, 0, f"[{k:02d}]")
    table = PhaseTable()
    with table.phase("sched.flush"):
        plan = _plan_both_ways(sess, monkeypatch)
    counts = table.snapshot()["phases"]["plan.tail"]["counts"]
    assert counts["xf_native"] == counts["xf_python"] == 1
    assert plan.n_ops == 64 and plan.fits(sess.cap)
    ok, _dev = ff.fused_replay([sess], [plan])
    assert ok == [True]
    want = ol.checkout_tip().snapshot()
    assert len(want) == 5 + 64 * 4 and sess.text() == want
    assert sess.synced_to == len(ol) and sess.plan_tail().n_ops == 0


def test_a_walk_costs_the_push_not_the_document(monkeypatch):
    """Counts, since a CPU gives counts and not times: over 200
    alternating pushes the native path constructs no Python tracker,
    the mirror is built whole once (at the session's checkout) and
    every walk hands the loaders a push's worth of entries."""
    from diamond_types_tpu.listmerge import transform
    from diamond_types_tpu.native.core import get_native_ctx
    from diamond_types_tpu.obs.phases import PhaseTable

    def no_tracker(*_a, **_k):
        raise AssertionError("a Python Tracker on the native path")
    monkeypatch.setattr(transform, "Tracker", no_tracker)
    doc = _Paper(4)
    sess = ff.FusedDocSession(doc.ol, cap=1 << 14)
    ctx = get_native_ctx(doc.ol)
    assert (ctx.appended, ctx.rebuilt) == (0, 1)
    whole = ctx.last_sent
    assert whole > len(doc.ol.ops.runs) > 500
    table = PhaseTable()
    with table.phase("sched.flush"):
        for n in (2, 16):
            doc.warm_round(n)
            sess.commit(sess.docs, sess.lens, sess.plan_tail())
        for i in range(200):
            doc.type(i % 2)
            plan = sess.plan_tail()
            # 8 keystrokes: at most 8 op runs and 8 characters, a graph
            # entry, an agent run, and the last entry held of each again
            assert ctx.last_sent <= 8 + 8 + 1 + 1 + 3
            sess.commit(sess.docs, sess.lens, plan)
    assert sess.doc_len == len(doc.plain.text())
    assert (ctx.appended, ctx.rebuilt) == (202, 1)
    counts = table.snapshot()["phases"]["plan.tail"]["counts"]
    # since PR 36 also the plan rows the walks made; keystrokes make no
    # block row
    assert counts.pop("rows") >= 202 and counts.pop("block_rows") == 0
    assert counts == {"xf_native": 202, "mirror_appended": 202,
                      "mirror_rebuilt": 0, "mirror_busy_waits": 0}
