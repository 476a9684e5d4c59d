"""Device-resident incremental sessions (tpu/zone_session.py) — the
merge-per-edit realtime pattern, parity-fuzzed against the tracker
engine after every sync (reference hot path: src/list/merge.rs:63-96).
"""

import random

import pytest

from conftest import reference_path
from diamond_types_tpu import OpLog
from diamond_types_tpu.tpu.zone_session import DeviceZoneSession

from test_zone import random_edit


@pytest.mark.parametrize("seed", range(10))
def test_session_realtime_fuzz(seed):
    """2-3 peers edit from their own heads; the session folds each batch
    incrementally and must match a fresh checkout every time."""
    rng = random.Random(8800 + seed)
    ol = OpLog()
    agents = [ol.get_or_create_agent_id(n) for n in ("ann", "bo", "cy")]
    heads = {a: ([], "") for a in agents}
    # seed history so the session starts non-trivially
    v, c = heads[agents[0]]
    for _ in range(5):
        v, c = random_edit(rng, ol, agents[0], v, c)
    for a in agents:
        heads[a] = (v, c)
    sess = DeviceZoneSession(ol, max_chars=32)
    assert sess.text() == ol.checkout_tip().snapshot()

    for step in range(30):
        a = agents[rng.randrange(len(agents))]
        v, c = heads[a]
        v, c = random_edit(rng, ol, a, v, c)
        heads[a] = (v, c)
        if rng.random() < 0.4:     # peers sync up sometimes
            merged = ol.checkout_tip()
            for a2 in agents:
                if rng.random() < 0.5:
                    heads[a2] = (list(merged.version), merged.snapshot())
        sess.sync()
        assert sess.text() == ol.checkout_tip().snapshot(), \
            f"seed {seed} diverged at step {step}"


def test_session_incremental_not_resyncing():
    """Sequential same-agent edits must stay on the incremental path
    (no resync after warm-up)."""
    ol = OpLog()
    a = ol.get_or_create_agent_id("solo")
    v = [ol.add_insert_at(a, [], 0, "hello world, this is a doc. ")]
    sess = DeviceZoneSession(ol)
    base_resyncs = sess.resyncs
    for i in range(10):
        v = [ol.add_insert_at(a, v, 5 + i, f"x{i}")]
        sess.sync()
    assert sess.resyncs == base_resyncs, "sequential edits caused resyncs"
    assert sess.text() == ol.checkout_tip().snapshot()


def test_session_two_agent_no_resync_after_warmup():
    """The friendsforever shape: two agents interleaving, each editing
    from its own head with periodic merges — after the first build the
    incremental path must handle everything (agent heads are pinned)."""
    rng = random.Random(4242)
    ol = OpLog()
    a1 = ol.get_or_create_agent_id("p1")
    a2 = ol.get_or_create_agent_id("p2")
    v = [ol.add_insert_at(a1, [], 0, "shared base text ")]
    h = {a1: (v, "shared base text "), a2: (v, "shared base text ")}
    for _ in range(6):
        for a in (a1, a2):
            vv, cc = h[a]
            vv, cc = random_edit(rng, ol, a, vv, cc)
            h[a] = (vv, cc)
    sess = DeviceZoneSession(ol, max_chars=64)
    base = sess.resyncs
    for step in range(20):
        a = (a1, a2)[step % 2]
        vv, cc = h[a]
        vv, cc = random_edit(rng, ol, a, vv, cc)
        h[a] = (vv, cc)
        if step % 5 == 4:
            m = ol.checkout_tip()
            h[a1] = h[a2] = (list(m.version), m.snapshot())
        sess.sync()
        assert sess.text() == ol.checkout_tip().snapshot()
    assert sess.resyncs == base, "realtime pattern fell off the " \
        "incremental path"


def test_session_capacity_growth_resync():
    """Slot-capacity overflow resyncs transparently."""
    ol = OpLog()
    a = ol.get_or_create_agent_id("big")
    v = [ol.add_insert_at(a, [], 0, "tiny")]
    sess = DeviceZoneSession(ol)
    v = [ol.add_insert_at(a, v, 2, "y" * (sess.W_cap + 10))]
    sess.sync()
    assert sess.text() == ol.checkout_tip().snapshot()


def test_session_root_anchored_op():
    """A concurrent op with parents=[] (root insert) must resync, not
    crash (regression: IndexError on empty source rows)."""
    ol = OpLog()
    a = ol.get_or_create_agent_id("a")
    b = ol.get_or_create_agent_id("b")
    ol.add_insert_at(a, [], 0, "first doc")
    sess = DeviceZoneSession(ol)
    ol.add_insert_at(b, [], 0, "root-concurrent")
    sess.sync()
    assert sess.text() == ol.checkout_tip().snapshot()


def test_session_late_agent_resync():
    """Registering a NEW agent shifts existing name ranks; the session
    must rebuild instead of mixing key epochs (regression: tie-breaks
    diverging from the host engine)."""
    ol = OpLog()
    a = ol.get_or_create_agent_id("mm")
    v = [ol.add_insert_at(a, [], 0, "base ")]
    sess = DeviceZoneSession(ol)
    # 'aa' sorts BEFORE 'mm': every existing rank shifts
    b = ol.get_or_create_agent_id("aa")
    z = ol.get_or_create_agent_id("zz")
    ol.add_insert_at(b, v, 2, "B")
    ol.add_insert_at(z, v, 2, "Z")
    ol.add_insert_at(a, v, 2, "M")
    sess.sync()
    assert sess.text() == ol.checkout_tip().snapshot()


def test_session_sliced_resync_matches_whole_tape(monkeypatch):
    """A resync executed as bounded-length slices (DT_SESSION_SLICE — the
    tpu default via auto_slice_steps, added because a grown session's
    whole-tape rebuild would cross the ~60 s per-program kill bound the
    v5e runtime of 2026-07-31 enforced) is bit-identical to the whole-tape rebuild:
    same text, same incremental behavior afterwards."""
    rng = random.Random(9100)
    ol = OpLog()
    agents = [ol.get_or_create_agent_id(n) for n in ("ann", "bo")]
    v, c = [], ""
    for _ in range(12):
        v, c = random_edit(rng, ol, agents[0], v, c)
    heads = {a: (v, c) for a in agents}
    for step in range(20):
        a = agents[step % 2]
        hv, hc = heads[a]
        heads[a] = random_edit(rng, ol, a, hv, hc)

    monkeypatch.setenv("DT_SESSION_SLICE", "7")   # uneven boundaries
    sess = DeviceZoneSession(ol)
    assert sess.text() == ol.checkout_tip().snapshot()
    # incremental continuation on top of a sliced rebuild
    for step in range(10):
        a = agents[step % 2]
        hv, hc = heads[a]
        heads[a] = random_edit(rng, ol, a, hv, hc)
        sess.sync()
        assert sess.text() == ol.checkout_tip().snapshot()
