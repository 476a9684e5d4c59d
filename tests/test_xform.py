"""Device-resident tail transform (tpu/xform.py) + the Pallas replay rung.

Covers the ISSUE-13 tentpole surface: randomized mixed-bucket parity of
the device-planned transform against the host tracker walk (byte-
identical final text), a 64-way concurrent merge resolved on device, the
log-prefix-frontier contract proven by the DAG reachability kernel,
per-doc poison isolation on the device-plan rung, the five-rung fallback
ladder (pallas -> mesh -> fused -> per-doc -> host) surviving injected
rung failures with parity intact, warmup coverage for the xform/pallas
jit families, and the --device-plan / --pallas CLI flags. CPU-simulated
devices via conftest's virtual 8-device mesh; Pallas kernels run in
interpret mode off-TPU.
"""

import random

import numpy as np
import pytest

from diamond_types_tpu.obs import Observability
from diamond_types_tpu.serve.metrics import ServeMetrics
from diamond_types_tpu.serve.scheduler import MergeScheduler
from diamond_types_tpu.text.oplog import OpLog
from diamond_types_tpu.tpu import flush_fuse as ff
from diamond_types_tpu.tpu import xform as xfm

pytestmark = [pytest.mark.fused, pytest.mark.serve]

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
    kw.setdefault("fused", True)
    kw.setdefault("fused_opts", FUSED_OPTS)
    kw.setdefault("flush_docs", 8)
    kw.setdefault("flush_deadline_s", 10.0)
    kw.setdefault("flush_workers", False)
    return MergeScheduler(n_shards, resolve=lambda d: ols[d], **kw)


# ---- randomized mixed-bucket parity ---------------------------------------

def test_device_plan_parity_randomized_mixed_buckets(monkeypatch):
    """plan_tails_device == host tracker walk, byte-for-byte, on
    randomized mixed-size buckets with concurrent branches every round.
    DT_XFORM_VALIDATE=1 additionally proves the log-prefix-frontier
    threshold with the device reachability kernel on every extract."""
    monkeypatch.setenv("DT_XFORM_VALIDATE", "1")
    rng = random.Random(13)
    ols = [_mk_oplog(f"d{i}") for i in range(5)]
    for i, ol in enumerate(ols):
        _random_edits(ol, rng, 2 + i)
    sess = [ff.FusedDocSession(ol, **FUSED_OPTS) for ol in ols]
    total_dev = 0
    for rnd in range(4):
        for i, ol in enumerate(ols):
            _random_edits(ol, rng, 1 + (i + rnd) % 3)
            # a concurrent branch forked at the root: a genuine
            # conflict zone for the device resolver every round
            b = ol.get_or_create_agent_id("b")
            ol.add_insert_at(b, [], 0, "Z" * (1 + (i + rnd) % 2))
        plans, stats = xfm.plan_tails_device(sess)
        assert len(plans) == len(sess)
        assert all(p is not None for p in plans)
        total_dev += stats["device_docs"]
        fits = [p.fits(s.cap) for p, s in zip(plans, sess)]
        assert all(fits)
        ok, _dev = ff.fused_replay(sess, plans)
        assert all(ok)
        for s, ol in zip(sess, ols):
            assert s.text() == ol.checkout_tip().snapshot()
    # the device rung did the planning, not the host fallback
    assert total_dev >= len(sess)


def test_64_way_concurrent_merge_device_planned():
    """64 agents insert concurrently from the same frontier; the device
    transform resolves the full Fugue order in one dispatch and the
    replayed text matches the host oracle."""
    ol = _mk_oplog("wide")
    a0 = ol.get_or_create_agent_id("seed")
    ol.add_insert(a0, 0, "base ")
    sess = ff.FusedDocSession(ol, cap=1024, max_ins=4)
    base = list(ol.version)
    for k in range(64):
        ag = ol.get_or_create_agent_id(f"w{k}")
        ol.add_insert_at(ag, base, 0, f"[{k:02d}]")
    plans, stats = xfm.plan_tails_device([sess])
    assert stats["device_docs"] == 1 and stats["fallbacks"] == 0
    assert plans[0].fits(sess.cap)
    ok, _dev = ff.fused_replay([sess], plans)
    assert all(ok)
    assert sess.text() == ol.checkout_tip().snapshot()


def test_validate_prefix_frontier_threshold():
    """The contract old-visibility rests on: `lv < synced_to` iff the
    session frontier contains lv — proven by the scatter-max DAG
    reachability kernel, and violated by an off-by-one threshold."""
    ol = _mk_oplog("v")
    a = ol.get_or_create_agent_id("a")
    ol.add_insert(a, 0, "hello")
    sess = ff.FusedDocSession(ol, **FUSED_OPTS)
    b = ol.get_or_create_agent_id("b")
    ol.add_insert_at(b, [], 0, "XY")          # concurrent tail
    assert xfm.validate_prefix_frontier(ol, sess.frontier, sess.synced_to)
    assert not xfm.validate_prefix_frontier(ol, sess.frontier,
                                            sess.synced_to - 1)
    empty = _mk_oplog("e")
    assert xfm.validate_prefix_frontier(empty, (), 0)


# ---- per-doc poison isolation ---------------------------------------------

def test_per_doc_poison_isolation_on_device_plan_rung():
    """A contract violation in one device-planned doc poisons only ITS
    row: bucket neighbors commit and stay byte-correct."""
    rng = random.Random(23)
    ols = [_mk_oplog(f"p{i}") for i in range(3)]
    for ol in ols:
        a = ol.get_or_create_agent_id("a")
        ol.add_insert(a, 0, "seed ")
    sess = [ff.FusedDocSession(ol, **FUSED_OPTS) for ol in ols]
    for ol in ols:
        _random_edits(ol, rng, 2)
        b = ol.get_or_create_agent_id("b")
        ol.add_insert_at(b, [], 0, "Q")
    plans, stats = xfm.plan_tails_device(sess)
    assert stats["device_docs"] == 3
    assert plans[1].n_ops > 0
    plans[1].ilen[0] = FUSED_OPTS["max_ins"] + 1   # violates the contract
    ok, _dev = ff.fused_replay(sess, plans)
    assert ok == [True, False, True]
    for i in (0, 2):
        assert sess[i].text() == ols[i].checkout_tip().snapshot()


# ---- the fallback ladder under injected faults ----------------------------

def test_bank_pallas_rung_failure_propagates(monkeypatch):
    """Injected pallas_fused_replay failure: the rung that was asked for
    is never answered by the XLA kernel under its name. The exception is
    counted (`device_errors`), recorded with its text and raised; the
    docs stay byte-correct through the host oracle, and the read says
    so (`reads_from_host`)."""
    ols = {}
    sched = _mk_sched(ols, 1, device_plan=True, pallas=True)
    sched.attach_obs(Observability())
    assert sched.banks[0].pallas
    rng = random.Random(31)
    docs = [f"d{i}" for i in range(4)]
    for rnd in range(3):
        for d in docs:
            if rnd == 0:
                ols[d] = _mk_oplog(d)
            _random_edits(ols[d], rng, 2)
            assert sched.submit(d, n_ops=2)["accepted"]
        if rnd == 2:
            def boom(sessions, plans):
                raise RuntimeError("injected pallas failure")
            monkeypatch.setattr(ff, "pallas_fused_replay", boom)
            with pytest.raises(RuntimeError, match="injected pallas"):
                sched.pump(force=True)
        else:
            sched.pump(force=True)
    monkeypatch.undo()
    m = sched.metrics_json()
    assert m["totals"]["device_errors"] == 1
    assert m["totals"]["host_fallbacks"] == 0
    ev = [e for e in sched.obs.recorder.dump()
          if e["kind"] == "device_error"]
    assert ev[0]["rung"] == "pallas"
    assert "injected pallas failure" in ev[0]["error"]
    for d in docs:
        assert sched.text(d) == ols[d].checkout_tip().snapshot()
    m = sched.metrics_json()
    assert m["totals"]["reads_from_host"] == len(docs)
    assert m["totals"]["reads_from_device"] == 0


@pytest.mark.parametrize("rung", ["pallas", "mesh"])
def test_window_rung_failure_propagates(monkeypatch, rung):
    """Mesh flush window whose replay raises (the Pallas program on a
    one-device window, the mesh program otherwise): counted on the
    failing class's shard, recorded, raised once the window is wound
    up — no quieter rung replays the window, and the host oracle keeps
    every doc byte-correct."""
    from diamond_types_tpu.parallel import mesh as pm
    ols = {}
    sched = _mk_sched(ols, 1, mesh_window=True, device_plan=True,
                      pallas=(rung == "pallas"))
    sched.attach_obs(Observability())
    rng = random.Random(37)
    docs = [f"d{i}" for i in range(4)]
    for rnd in range(3):
        for d in docs:
            if rnd == 0:
                ols[d] = _mk_oplog(d)
            _random_edits(ols[d], rng, 2)
            assert sched.submit(d, n_ops=2)["accepted"]
        if rnd == 2:
            def boom(*a, **k):
                raise RuntimeError("injected rung failure")
            # both call-time imports re-resolve these module attrs
            monkeypatch.setattr(ff, "pallas_fused_replay", boom)
            monkeypatch.setattr(pm, "mesh_fused_replay", boom)
            with pytest.raises(RuntimeError, match="injected rung"):
                sched.pump(force=True)
        else:
            sched.pump(force=True)
    monkeypatch.undo()
    m = sched.metrics_json()
    # the failed window is accounted, with no dispatch to its name
    assert m["window"]["windows"] == 3
    assert m["window"]["device_windows"] == 1
    assert m["totals"]["device_errors"] == 1
    ev = [e for e in sched.obs.recorder.dump()
          if e["kind"] == "device_error"]
    assert [e["rung"] for e in ev] == [rung]
    for d in docs:
        assert sched.text(d) == ols[d].checkout_tip().snapshot()


def test_device_plan_guard_trip_host_fallback(monkeypatch):
    """An extract whose device resolution fails (injected) is re-planned
    by the host tracker walk per doc — counted as a transform fallback,
    with parity intact (the per-doc host rung of the transform ladder)."""
    rng = random.Random(41)
    ols = [_mk_oplog(f"g{i}") for i in range(3)]
    for ol in ols:
        _random_edits(ol, rng, 3)
    sess = [ff.FusedDocSession(ol, **FUSED_OPTS) for ol in ols]
    for ol in ols:
        _random_edits(ol, rng, 2)
        b = ol.get_or_create_agent_id("b")
        ol.add_insert_at(b, [], 0, "W")
    monkeypatch.setattr(xfm, "resolve_positions",
                        lambda exts, pallas=False: [None] * len(exts))
    plans, stats = xfm.plan_tails_device(sess)
    monkeypatch.undo()
    assert stats["fallbacks"] == 3 and stats["device_docs"] == 0
    assert all(p is not None for p in plans)
    ok, _dev = ff.fused_replay(sess, plans)
    assert all(ok)
    for s, ol in zip(sess, ols):
        assert s.text() == ol.checkout_tip().snapshot()


# ---- warmup coverage ------------------------------------------------------

def test_warmup_precompiles_xform_and_pallas_classes():
    """warmup_fused_cache(xform_classes=..., pallas=True) compiles the
    transform dispatch and the Pallas replay rung; a second warmup over
    the same shapes is ALL cache hits (zero new misses)."""
    from diamond_types_tpu.obs.devprof import PROFILER
    PROFILER.reset()
    PROFILER.enabled = True
    try:
        n = ff.warmup_fused_cache(flush_docs=2, cap=64, max_ins=2,
                                  shape_classes=(1,), xform_classes=(1,),
                                  pallas=True)
        # batches {1, 2} x one shape class, for fused + xform + pallas
        assert n == 6
        snap1 = PROFILER.snapshot()["jit_cache"]
        assert snap1["xform"]["misses"] == 2
        assert snap1["pallas"]["misses"] == 2
        ff.warmup_fused_cache(flush_docs=2, cap=64, max_ins=2,
                              shape_classes=(1,), xform_classes=(1,),
                              pallas=True)
        snap2 = PROFILER.snapshot()["jit_cache"]
        for fam in ("fused", "xform", "pallas"):
            assert snap2[fam]["misses"] == snap1[fam]["misses"], fam
            assert snap2[fam]["hits"] >= snap1[fam]["hits"] + 2, fam
    finally:
        PROFILER.enabled = False


# ---- Pallas kernels (interpret mode off-TPU) ------------------------------

@pytest.mark.pallas
def test_xform_positions_pallas_parity():
    """The gather-free position-resolution kernel == the jnp cumsum
    formulation across lane-boundary sizes (Mosaic's ~128-lane gather
    cap is why the kernel exists)."""
    from diamond_types_tpu.tpu.pallas_kernels import xform_positions_pallas
    import jax.numpy as jnp
    rng = np.random.default_rng(7)
    for n in (1, 5, 127, 128, 513):
        nv = rng.integers(0, 6, n).astype(np.int32)
        ov = rng.integers(0, 6, n).astype(np.int32)
        pos, new_len, peak = xform_positions_pallas(
            jnp.asarray(nv), jnp.asarray(ov), interpret=True)
        cum = np.cumsum(nv)
        assert (np.asarray(pos)[:n] == (cum - nv)).all(), n
        assert int(new_len) == int(nv.sum()), n
        want_peak = max(0, int(np.max(np.cumsum(
            nv.astype(np.int64) - ov))))
        assert int(peak) == want_peak, n


@pytest.mark.pallas
def test_pallas_fused_replay_parity():
    """The ladder's top rung == host checkout on randomized concurrent
    buckets (step kernel in interpret mode on the CPU backend)."""
    rng = random.Random(43)
    ols = [_mk_oplog(f"pl{i}") for i in range(3)]
    for i, ol in enumerate(ols):
        _random_edits(ol, rng, 2 + i)
    sess = [ff.FusedDocSession(ol, **FUSED_OPTS) for ol in ols]
    for rnd in range(2):
        for i, ol in enumerate(ols):
            _random_edits(ol, rng, 1 + (i + rnd) % 2)
            b = ol.get_or_create_agent_id("b")
            ol.add_insert_at(b, [], 0, "Y" * (i + 1))
        plans = [s.plan_tail() for s in sess]
        ok, _dev = ff.pallas_fused_replay(sess, plans)
        assert all(ok)
        for s, ol in zip(sess, ols):
            assert s.text() == ol.checkout_tip().snapshot()


@pytest.mark.pallas
def test_pallas_xform_end_to_end(monkeypatch):
    """`pallas=True` routes the transform's position scans through the
    Pallas kernel; the device-planned replay stays byte-identical."""
    from diamond_types_tpu.tpu import pallas_kernels as pk
    calls = []
    real = pk.xform_positions_pallas
    monkeypatch.setattr(pk, "xform_positions_pallas",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    xfm._xform_jit_cache.clear()
    ol = _mk_oplog("pe")
    a = ol.get_or_create_agent_id("a")
    ol.add_insert(a, 0, "root ")
    sess = ff.FusedDocSession(ol, **FUSED_OPTS)
    base = list(ol.version)
    for k in range(5):
        ag = ol.get_or_create_agent_id(f"c{k}")
        ol.add_insert_at(ag, base, 0, f"<{k}>")
    plans, stats = xfm.plan_tails_device([sess], pallas=True)
    assert stats["device_docs"] == 1 and calls
    ok, _dev = ff.fused_replay([sess], plans)
    assert all(ok)
    assert sess.text() == ol.checkout_tip().snapshot()


# ---- metrics + prom -------------------------------------------------------

def test_metrics_transform_block_and_version():
    m = ServeMetrics(2, 4, 64)
    m.record_transform(0, device_docs=3, host_docs=1, fallbacks=1,
                       batches=1)
    m.bump(0, "device_errors")
    s = m.snapshot()
    assert s["version"] == 14
    t = s["transform"]
    assert t["device_docs"] == 3 and t["host_docs"] == 1
    assert t["fallbacks"] == 1 and t["batches"] == 1
    assert t["device_ratio"] == 0.6          # 3 / (3 + 1 + 1)
    assert s["totals"]["device_errors"] == 1
    assert "pallas_fallbacks" not in s["totals"]


def test_prom_zero_fills_xform_and_pallas_jit_families():
    """A devprof snapshot that never touched the xform/pallas caches
    still renders their jit families at 0 — dashboards keyed on the
    label set survive a host-plan-only deployment."""
    from diamond_types_tpu.obs.prom import render_metrics
    text = render_metrics({"obs": {"devprof": {
        "jit_cache": {"fused": {"hits": 3, "misses": 1}}}}})
    assert 'dt_devprof_jit_hits_total{cache="fused"} 3' in text
    assert 'dt_devprof_jit_hits_total{cache="xform"} 0' in text
    assert 'dt_devprof_jit_misses_total{cache="xform"} 0' in text
    assert 'dt_devprof_jit_hits_total{cache="pallas"} 0' in text


# ---- scheduler + driver + CLI ---------------------------------------------

def test_scheduler_device_plan_parity_vs_host_plan():
    """Identical concurrent edit streams through a device-plan scheduler
    and a host-plan control: every doc byte-identical, and the transform
    block shows the device rung actually engaged."""
    def mk_logs():
        logs = {}
        for i in range(6):
            ol = _mk_oplog(f"d{i}")
            a = ol.get_or_create_agent_id("seed")
            ol.add_insert(a, 0, f"doc{i}: ")
            logs[f"d{i}"] = ol
        return logs

    logs = [mk_logs() for _ in range(2)]
    scheds = [
        _mk_sched(logs[0], 2, device_plan=True, pallas=True),
        _mk_sched(logs[1], 2),
    ]
    assert scheds[0].device_plan and not scheds[1].device_plan
    rngs = [random.Random(19) for _ in range(2)]
    for rnd in range(4):
        for i in range(6):
            d = f"d{i}"
            for lg, r in zip(logs, rngs):
                _random_edits(lg[d], r, 2)
                if rnd >= 1:
                    b = lg[d].get_or_create_agent_id("b")
                    b_txt = "B" * (1 + (i + rnd) % 2)
                    lg[d].add_insert_at(b, [], 0, b_txt)
            for s in scheds:
                assert s.submit(d, n_ops=2)["accepted"]
        for s in scheds:
            s.pump(force=True)
    for i in range(6):
        d = f"d{i}"
        texts = [s.text(d) for s in scheds]
        assert texts[0] == texts[1]
        assert texts[0] == logs[0][d].checkout_tip().snapshot()
    t = scheds[0].metrics_json()["transform"]
    assert t["device_docs"] > 0
    assert t["batches"] > 0
    tc = scheds[1].metrics_json()["transform"]
    assert tc["device_docs"] == 0            # the control never engaged


def test_serve_bench_device_plan_smoke():
    """End-to-end driver run with the full ladder on: parity gate plus
    the transform block reporting device-planned docs."""
    from diamond_types_tpu.serve.driver import run_serve_bench
    report = run_serve_bench(shards=2, docs=4, txns=3, engine="device",
                             mode="concurrent", flush_docs=2,
                             max_sessions=8, steady_rounds=4,
                             device_plan=True, pallas=True,
                             warmup=False)
    assert report["parity_ok"], report["parity_mismatches"]
    assert report["config"]["device_plan"] and report["config"]["pallas"]
    t = report["transform"]
    assert t["device_docs"] > 0
    assert t["device_ratio"] > 0


def test_cli_device_plan_flags_smoke(capsys):
    """--device-plan/--pallas (and their --no- forms) parse and ride
    through the dry-run preset."""
    from diamond_types_tpu.tools.cli import main
    rc = main(["serve-bench", "--dry-run", "--device-plan", "--pallas",
               "--no-workers", "--steady-rounds", "0"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "parity OK" in out
