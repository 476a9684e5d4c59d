"""Shape steering + device-resident staging (PR 20 tentpole).

Covers, strictly above the parity fences:
  * `ShapeSteer.snap` policy — exact-warm hits, bounded-waste padding,
    forced first-sight pads, recurrence-gated compiles, the mesh batch
    multiple, and the disabled passthrough;
  * `cap_class` / `warmup_batches` — the single cap-floor source of
    truth shared by `warmup_fused_cache` and `_materialize`;
  * randomized mixed-bucket byte parity steered vs. unsteered vs. the
    host oracle across the replay paths (mesh / fused / per-doc),
    with explicit padded-window parity;
  * the warmup-then-steady pin: zero compiles and zero jit misses on
    a steered drifting tape after `warmup_fused_cache`;
  * window-arena donated-buffer reuse — the fast path engages on a
    recurring window, and a poisoned row mid-window can never leak a
    stale arena slot (ladder fallback semantics intact);
  * host->device transfer accounting split by (rung, purpose) and the
    zero-filled prom families.

Runs on the CPU-simulated mesh (conftest pins JAX_PLATFORMS=cpu and
an 8-device virtual host platform).
"""

import random

import numpy as np
import pytest

from diamond_types_tpu.obs.devprof import PROFILER
from diamond_types_tpu.parallel import arena
from diamond_types_tpu.parallel import mesh as pm
from diamond_types_tpu.text.oplog import OpLog
from diamond_types_tpu.tpu import flush_fuse as ff
from diamond_types_tpu.tpu.steer import (STEER, ShapeSteer, cap_class,
                                         warmup_batches)

pytestmark = [pytest.mark.fused, pytest.mark.serve]

FUSED_OPTS = {"cap": 256, "max_ins": 4}
MI, CAP = 4, 256


@pytest.fixture(autouse=True)
def _steer_clean():
    """Steering/arena state is process-global: start every test from a
    cold table + empty arenas and restore the default switches."""
    STEER.reset(table=True)
    STEER.enabled = True
    arena.DEVICE_STAGE.enabled = True
    arena.reset_arenas()
    yield
    STEER.reset(table=True)
    STEER.enabled = True
    arena.DEVICE_STAGE.enabled = True
    arena.reset_arenas()
    PROFILER.enabled = False
    PROFILER.reset()


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
            end = min(pos + rng.randint(1, 6), cur)
            ol.add_delete_without_content(a, pos, end)
        else:
            pos = rng.randint(0, cur)
            s = "".join(rng.choice("abcdef") for _ in
                        range(rng.randint(1, 5)))
            ol.add_insert(a, pos, s)


# ---- snap policy ---------------------------------------------------------

def test_snap_exact_warm_hit():
    s = ShapeSteer()
    s.note_warm("fused", MI, CAP, 2, 8)
    assert s.snap("fused", 2, 8, MI, CAP) == (2, 8)
    snap = s.snapshot()
    assert snap["hits"] == 1 and snap["compiles"] == 0
    assert snap["hit_rate"] == 1.0


def test_snap_pads_to_cheapest_inbound_class():
    s = ShapeSteer()
    s.note_warm("fused", MI, CAP, 4, 16)   # 64 cells
    s.note_warm("fused", MI, CAP, 8, 64)   # 512 cells
    # floor (2, 8) = 16 cells: both classes cover it, (4, 16) is the
    # cheapest and sits inside max_waste (64 <= 4 * 16)
    assert s.snap("fused", 2, 8, MI, CAP) == (4, 16)
    assert s.snapshot()["padded"] == 1


def test_snap_waste_bound_forced_pad_then_compile():
    s = ShapeSteer()
    s.note_warm("fused", MI, CAP, 16, 64)   # 1024 cells
    # floor (1, 2) = 2 cells: the only warm neighbor blows max_waste
    # (1024 > 4 * 2). First sight borrows it anyway — padding waste
    # beats a request-path compile for a one-off shape...
    assert s.snap("fused", 1, 2, MI, CAP) == (16, 64)
    snap = s.snapshot()
    assert snap["forced_pads"] == 1 and snap["compiles"] == 0
    # ...but a RECURRING shape earns its own class
    assert s.snap("fused", 1, 2, MI, CAP) == (1, 2)
    assert s.snapshot()["compiles"] == 1
    # once the compile lands in the real cache, note_warm makes it hit
    s.note_warm("fused", MI, CAP, 1, 2)
    assert s.snap("fused", 1, 2, MI, CAP) == (1, 2)
    assert s.snapshot()["hits"] == 1


def test_snap_no_candidate_compiles_immediately():
    s = ShapeSteer()
    s.note_warm("fused", MI, CAP, 2, 8)
    # bw=2 < bp0=4: no warm class covers the batch — exact class, no
    # recurrence wait (there is nothing to borrow)
    assert s.snap("fused", 4, 8, MI, CAP) == (4, 8)
    assert s.snapshot()["compiles"] == 1


def test_snap_respects_mesh_batch_multiple():
    s = ShapeSteer()
    s.note_warm("mesh", MI, CAP, 2, 32)    # not divisible by 4
    s.note_warm("mesh", MI, CAP, 4, 8)
    assert s.snap("mesh", 2, 8, MI, CAP, multiple=4) == (4, 8)


def test_snap_keys_isolate_cache_mi_cap():
    s = ShapeSteer()
    s.note_warm("fused", MI, CAP, 4, 8)
    # other cache / other cap: the warm class must not cross-match
    assert s.snap("mesh", 4, 8, MI, CAP) == (4, 8)
    assert s.snap("fused", 4, 8, MI, 512) == (4, 8)
    assert s.snapshot()["compiles"] == 2


def test_snap_disabled_is_passthrough():
    s = ShapeSteer(enabled=False)
    s.note_warm("fused", MI, CAP, 8, 8)
    assert s.snap("fused", 2, 2, MI, CAP) == (2, 2)
    assert s.snapshot()["lookups"] == 0


def test_reset_counts_vs_table():
    s = ShapeSteer()
    s.note_warm("fused", MI, CAP, 2, 8)
    s.snap("fused", 2, 8, MI, CAP)
    s.reset()
    assert s.snapshot()["lookups"] == 0
    assert s.snapshot()["warm_classes"] == {"fused": 1}
    s.reset(table=True)
    assert s.snapshot()["warm_classes"] == {}


# ---- cap-floor agreement (the warmup drift fix) --------------------------

def test_cap_class_floor_and_pow2():
    assert cap_class(1) == 256
    assert cap_class(256) == 256
    assert cap_class(300) == 512
    assert cap_class(5000) == 8192


def test_warmup_batches_enumeration():
    assert warmup_batches(1) == [1]
    assert warmup_batches(8) == [1, 2, 4, 8]
    assert warmup_batches(6) == [1, 2, 4, 8]


def test_session_materializes_on_cap_class():
    """A fresh session lands exactly on `cap_class` — the class warmup
    enumerates — so warmed kernels are the kernels flushes hit."""
    ol = _mk_oplog("d0")
    a = ol.get_or_create_agent_id("a")
    ol.add_insert(a, 0, "x" * 200)
    s = ff.FusedDocSession(ol, **FUSED_OPTS)
    assert s.cap == cap_class(int(200 * s.headroom))
    assert s.cap == cap_class(s.cap)


# ---- steered byte parity across the rungs --------------------------------

def _replay(rung, mesh, sess, plans):
    if rung == "mesh":
        ok, _dev, _bp, _staged = pm.mesh_fused_replay(mesh, sess, plans)
        return ok
    ok, _dev = ff.fused_replay(sess, plans)
    return ok


@pytest.mark.parametrize("rung", ["fused", "mesh"])
def test_steered_vs_unsteered_vs_host_randomized_parity(rung):
    """Randomized mixed buckets re-windowed across rounds: the steered
    arm, the unsteered arm, and the host oracle stay byte-identical on
    every rung. Steering only changes the PADDED shape dispatched —
    inert pad rows by construction — so parity must be exact."""
    mesh = pm.serve_mesh(4) if rung == "mesh" else None
    rng_s = random.Random(23)
    rng_u = random.Random(23)
    ols_s = [_mk_oplog(f"d{i}") for i in range(5)]
    ols_u = [_mk_oplog(f"d{i}") for i in range(5)]
    for i, (a, b) in enumerate(zip(ols_s, ols_u)):
        _random_edits(a, rng_s, 2 + i)
        _random_edits(b, rng_u, 2 + i)
    sess_s = [ff.FusedDocSession(ol, **FUSED_OPTS) for ol in ols_s]
    sess_u = [ff.FusedDocSession(ol, **FUSED_OPTS) for ol in ols_u]
    for rnd in range(3):
        for i, (a, b) in enumerate(zip(ols_s, ols_u)):
            _random_edits(a, rng_s, 1 + (i + rnd) % 3)
            _random_edits(b, rng_u, 1 + (i + rnd) % 3)
        # drifting window width: rounds dispatch 5 then 3 then 5 docs
        k = 3 if rnd == 1 else 5
        STEER.enabled = True
        ok = _replay(rung, mesh, sess_s[:k],
                     [s.plan_tail() for s in sess_s[:k]])
        assert all(ok)
        STEER.enabled = False
        ok = _replay(rung, mesh, sess_u[:k],
                     [s.plan_tail() for s in sess_u[:k]])
        assert all(ok)
        for s, u, ol in zip(sess_s[:k], sess_u[:k], ols_s[:k]):
            want = ol.checkout_tip().snapshot()
            assert s.text() == want
            assert u.text() == want
    assert STEER.snapshot()["lookups"] >= 3


def test_perdoc_and_host_rungs_unaffected_by_steering():
    """The per-doc rung (batch 1, `sync()`) and the host oracle below
    it ride the same steer table: parity pinned with the table warm."""
    STEER.note_warm("fused", MI, CAP, 8, 8)
    rng = random.Random(5)
    ol = _mk_oplog("d0")
    _random_edits(ol, rng, 4)
    s = ff.FusedDocSession(ol, **FUSED_OPTS)
    for _ in range(3):
        _random_edits(ol, rng, 2)
        s.sync()
        assert s.text() == ol.checkout_tip().snapshot()


def test_explicitly_padded_window_byte_parity():
    """Force the pad-up path: a strictly larger in-bound warm class
    absorbs the window and the result is still byte-identical."""
    STEER.note_warm("fused", MI, CAP, 8, 4)    # 32 cells, in-bound
    rng = random.Random(9)
    ols = [_mk_oplog(f"d{i}") for i in range(3)]
    for ol in ols:
        _random_edits(ol, rng, 2)
    sess = [ff.FusedDocSession(ol, **FUSED_OPTS) for ol in ols]
    for ol in ols:
        _random_edits(ol, rng, 1)
    plans = [s.plan_tail() for s in sess]
    ok, _dev = ff.fused_replay(sess, plans)
    assert all(ok)
    assert STEER.snapshot()["padded"] >= 1
    for s, ol in zip(sess, ols):
        assert s.text() == ol.checkout_tip().snapshot()


# ---- warmup-then-steady: the zero-compiles pin ---------------------------

def test_warmup_then_steady_zero_compiles():
    """After `warmup_fused_cache`, a steered steady-state tape whose
    floors drift inside the warmed envelope triggers ZERO jit-cache
    misses and ZERO steer compiles — every window lands on a warm
    class, the acceptance pin behind the >= 90% hit-rate claim."""
    ff.warmup_fused_cache(flush_docs=4, cap=CAP, max_ins=MI,
                          mesh_shards=2)
    mesh = pm.serve_mesh(2)
    STEER.reset()                      # counters only; table stays warm
    PROFILER.reset()
    PROFILER.enabled = True
    rng = random.Random(31)
    ols = [_mk_oplog(f"d{i}") for i in range(4)]
    for ol in ols:
        _random_edits(ol, rng, 2)
    sess = [ff.FusedDocSession(ol, **FUSED_OPTS) for ol in ols]
    for rnd in range(6):
        k = 1 + (rnd % 4)              # drifting window width 1..4
        for ol in ols[:k]:
            _random_edits(ol, rng, 1 + rnd % 2)
        plans = [s.plan_tail() for s in sess[:k]]
        if rnd % 2:
            ok, _d, _bp, _st = pm.mesh_fused_replay(mesh, sess[:k],
                                                    plans)
        else:
            ok, _d = ff.fused_replay(sess[:k], plans)
        assert all(ok)
        for s, ol in zip(sess[:k], ols[:k]):
            assert s.text() == ol.checkout_tip().snapshot()
    snap = STEER.snapshot()
    assert snap["compiles"] == 0, snap
    assert snap["hit_rate"] == 1.0, snap
    jit = PROFILER.snapshot()["jit_cache"]
    for cache in ("fused", "mesh"):
        assert jit.get(cache, {}).get("misses", 0) == 0, jit


# ---- window arena: donated-buffer reuse ----------------------------------

def _spy_acquire(monkeypatch):
    hits = []
    orig = arena.acquire

    def spy(*a, **k):
        r = orig(*a, **k)
        hits.append(r is not None)
        return r

    monkeypatch.setattr(arena, "acquire", spy)
    return hits


def test_arena_fast_path_engages_on_recurring_window(monkeypatch):
    """Window k's donated outputs become window k+1's inputs when the
    same session list recurs in the same shape class — and parity
    against the host oracle holds through the handoff."""
    hits = _spy_acquire(monkeypatch)
    mesh = pm.serve_mesh(2)
    rng = random.Random(41)
    ols = [_mk_oplog(f"d{i}") for i in range(4)]
    for ol in ols:
        _random_edits(ol, rng, 2)
    sess = [ff.FusedDocSession(ol, **FUSED_OPTS) for ol in ols]
    for rnd in range(3):
        for ol in ols:
            _random_edits(ol, rng, 2)
        plans = [s.plan_tail() for s in sess]
        ok, _d, _bp, _st = pm.mesh_fused_replay(mesh, sess, plans)
        assert all(ok)
        for s, ol in zip(sess, ols):
            assert s.text() == ol.checkout_tip().snapshot()
    # first window gathers (nothing parked), every recurrence reuses
    assert hits == [False, True, True]
    st = arena.arena_stats()
    assert st["arenas"] == 1 and st["generations"] == 3


def test_arena_poisoned_row_cannot_leak_stale_slot(monkeypatch):
    """Ladder-fallback mid-window: a row that fails the adopt_results
    length fence is left untagged, so the NEXT window's fast path
    misses and rebuilds from the sessions' own rows — the poisoned
    slot's stale bytes are unreachable by construction."""
    hits = _spy_acquire(monkeypatch)
    mesh = pm.serve_mesh(2)
    rng = random.Random(43)
    ols = [_mk_oplog(f"d{i}") for i in range(4)]
    for ol in ols:
        _random_edits(ol, rng, 2)
    sess = [ff.FusedDocSession(ol, **FUSED_OPTS) for ol in ols]
    # window 1: clean — arena parked, all rows tagged
    for ol in ols:
        _random_edits(ol, rng, 2)
    ok, _d, _bp, _st = pm.mesh_fused_replay(
        mesh, sess, [s.plan_tail() for s in sess])
    assert all(ok)
    # window 2: doc 2's plan projection is tampered -> its returned
    # length fails the fence -> NOT committed, NOT re-tagged
    for ol in ols:
        _random_edits(ol, rng, 2)
    pre_text = sess[2].text()       # state BEFORE window 2's commit
    plans = [s.plan_tail() for s in sess]
    plans[2].new_len += 1
    ok, _d, _bp, _st = pm.mesh_fused_replay(mesh, sess, plans)
    assert ok == [True, True, False, True]
    assert sess[2].text() == pre_text          # kept pre-window state
    assert getattr(sess[2], "_arena_tag", None) is None
    assert getattr(sess[0], "_arena_tag", None) is not None
    # window 3: untainted plans. The fast path MUST miss (doc 2's tag
    # is gone) and the gather path replays doc 2's full pending tail
    for ol in ols:
        _random_edits(ol, rng, 1)
    ok, _d, _bp, _st = pm.mesh_fused_replay(
        mesh, sess, [s.plan_tail() for s in sess])
    assert all(ok)
    assert hits == [False, True, False]
    for s, ol in zip(sess, ols):
        assert s.text() == ol.checkout_tip().snapshot()


def test_session_mutation_clears_arena_tag():
    """Any out-of-window rebuild (`_materialize`) invalidates the
    session's arena slot — the fast path can never replay over it."""
    mesh = pm.serve_mesh(2)
    rng = random.Random(47)
    ols = [_mk_oplog(f"d{i}") for i in range(2)]
    for ol in ols:
        _random_edits(ol, rng, 2)
    sess = [ff.FusedDocSession(ol, **FUSED_OPTS) for ol in ols]
    ok, _d, _bp, _st = pm.mesh_fused_replay(
        mesh, sess, [s.plan_tail() for s in sess])
    assert all(ok)
    assert getattr(sess[0], "_arena_tag", None) is not None
    # both rows live on the first device: its block, slots 0 and 1
    assert [s._arena_tag[2] for s in sess] == [0, 1] and _bp == 4
    sess[0]._materialize()
    assert sess[0]._arena_tag is None
    assert arena.acquire(mesh, sess[0].cap, MI, sess, 4, [0, 1]) is None


def test_device_stage_off_is_host_control_arm(monkeypatch):
    """`DEVICE_STAGE` disabled: the arena never engages and every
    resident state byte is re-staged through host numpy (the A/B
    control) — with byte parity unchanged."""
    hits = _spy_acquire(monkeypatch)
    arena.DEVICE_STAGE.enabled = False
    mesh = pm.serve_mesh(2)
    rng = random.Random(53)
    ols = [_mk_oplog(f"d{i}") for i in range(3)]
    for ol in ols:
        _random_edits(ol, rng, 2)
    sess = [ff.FusedDocSession(ol, **FUSED_OPTS) for ol in ols]
    staged = []
    for rnd in range(2):
        for ol in ols:
            _random_edits(ol, rng, 2)
        ok, _d, bp, st = pm.mesh_fused_replay(
            mesh, sess, [s.plan_tail() for s in sess])
        assert all(ok)
        staged.append((bp, st))
        for s, ol in zip(sess, ols):
            assert s.text() == ol.checkout_tip().snapshot()
    assert hits == []                   # fast path never consulted
    assert arena.arena_stats()["arenas"] == 0
    # control staging pays the full [bp, cap] state each window
    for bp, st in staged:
        assert st > bp * CAP * 4


# ---- transfer accounting: the (rung, purpose) split ----------------------

def test_transfer_accounting_split_by_rung_and_purpose():
    PROFILER.reset()
    PROFILER.enabled = True
    mesh = pm.serve_mesh(2)
    rng = random.Random(59)
    ols = [_mk_oplog(f"d{i}") for i in range(3)]
    for ol in ols:
        _random_edits(ol, rng, 2)
    sess = [ff.FusedDocSession(ol, **FUSED_OPTS) for ol in ols]
    detail = PROFILER.snapshot()["transfer_detail"]
    assert detail["session.stage"]["transfers"] == 3   # materialize
    # device-resident staging: the mesh window pays PLAN bytes only
    for ol in ols:
        _random_edits(ol, rng, 1)
    ok, _d, _bp, staged = pm.mesh_fused_replay(
        mesh, sess, [s.plan_tail() for s in sess])
    assert all(ok)
    detail = PROFILER.snapshot()["transfer_detail"]
    assert detail["mesh.plan"]["bytes"] == staged
    assert "mesh.stage" not in detail
    # control arm: state bytes appear under mesh.stage and dominate
    arena.DEVICE_STAGE.enabled = False
    plan_before = detail["mesh.plan"]["bytes"]
    for ol in ols:
        _random_edits(ol, rng, 1)
    ok, _d, bp, staged = pm.mesh_fused_replay(
        mesh, sess, [s.plan_tail() for s in sess])
    assert all(ok)
    detail = PROFILER.snapshot()["transfer_detail"]
    assert detail["mesh.stage"]["bytes"] == bp * CAP * 4 + bp * 4
    assert staged == detail["mesh.stage"]["bytes"] \
        + (detail["mesh.plan"]["bytes"] - plan_before)
    # per-shard rungs tag their plan uploads too
    arena.DEVICE_STAGE.enabled = True
    for ol in ols:
        _random_edits(ol, rng, 1)
    ok, _d = ff.fused_replay(sess, [s.plan_tail() for s in sess])
    assert all(ok)
    assert "fused.plan" in PROFILER.snapshot()["transfer_detail"]


def test_warmup_transfers_tagged_and_staged_reduction():
    """Mesh warmup uploads are purpose="warmup" (kept out of the
    steady-state staging claim), and the device arm's per-window
    staging is <= half the host control arm's on the same window."""
    PROFILER.reset()
    PROFILER.enabled = True
    ff.warmup_fused_cache(flush_docs=2, cap=CAP, max_ins=MI,
                          mesh_shards=2)
    detail = PROFILER.snapshot()["transfer_detail"]
    assert detail["mesh.warmup"]["bytes"] > 0
    mesh = pm.serve_mesh(2)
    rng = random.Random(61)

    def _window(device_stage):
        arena.DEVICE_STAGE.enabled = device_stage
        arena.reset_arenas()
        ols = [_mk_oplog(f"d{i}") for i in range(3)]
        for ol in ols:
            _random_edits(ol, rng, 2)
        sess = [ff.FusedDocSession(ol, **FUSED_OPTS) for ol in ols]
        for ol in ols:
            _random_edits(ol, rng, 1)
        ok, _d, _bp, staged = pm.mesh_fused_replay(
            mesh, sess, [s.plan_tail() for s in sess])
        assert all(ok)
        return staged

    staged_dev = _window(True)
    staged_host = _window(False)
    assert staged_dev <= staged_host / 2, (staged_dev, staged_host)


def test_prom_families_zero_filled():
    """The staging + hit-rate prom families exist from the first
    scrape (zero-filled), not only after the first window."""
    from diamond_types_tpu.obs.prom import render_metrics
    from diamond_types_tpu.serve.metrics import ServeMetrics
    m = ServeMetrics(2, 4, 64)
    m.record_window(1, 2, 2)            # no staged bytes yet
    text = render_metrics({"serve": m.snapshot(),
                           "obs": {"devprof": {"jit_cache": {}}}})
    assert "dt_serve_window_transfer_bytes_total 0" in text
    assert "dt_serve_window_staged_bytes_per_window 0.0" in text
    assert 'dt_devprof_jit_hit_rate{cache="mesh"} 0.0' in text
    m.record_window(1, 2, 2, staged_bytes=4096)
    text = render_metrics({
        "serve": m.snapshot(),
        "obs": {"devprof": {
            "jit_cache": {"mesh": {"hits": 3, "misses": 1}},
            "transfer_detail": {"mesh.plan": {"transfers": 2,
                                              "bytes": 512}}}}})
    assert "dt_serve_window_transfer_bytes_total 4096" in text
    assert 'dt_devprof_jit_hit_rate{cache="mesh"} 0.75' in text
    assert ('dt_devprof_transfer_detail_bytes_total'
            '{purpose="plan",rung="mesh"} 512') in text


def test_scorecard_serve_block_bands_and_missing_skip():
    """The serve.* bands gate when both cards carry the block and are
    skipped (never gate) against a host-engine card without it."""
    from diamond_types_tpu.obs.scorecard import (build_scorecard,
                                                 diff_scorecards)

    def _card(serve):
        return build_scorecard(
            scenario={"name": "t"}, wall_s=1.0, virtual_s=0.0,
            totals={"ops": 10}, latency_p99_s={"flush": 0.01},
            slo={"slo_ok": True}, ok=True, serve=serve)

    old = _card({"jit_cache_hit_rate": 0.95,
                 "staged_bytes_per_window": 4000.0,
                 "device_calls_per_window": 1.0})
    good = _card({"jit_cache_hit_rate": 0.97,
                  "staged_bytes_per_window": 3500.0,
                  "device_calls_per_window": 1.0})
    bad = _card({"jit_cache_hit_rate": 0.60,
                 "staged_bytes_per_window": 4000.0,
                 "device_calls_per_window": 1.0})
    assert diff_scorecards(old, good)["ok"]
    d = diff_scorecards(old, bad)
    assert not d["ok"]
    assert "serve.jit_cache_hit_rate" in d["regressions"]
    hostcard = _card(None)
    d = diff_scorecards(hostcard, good)
    assert d["ok"]
    assert "serve.jit_cache_hit_rate" in d["skipped"]
