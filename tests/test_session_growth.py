"""A resident session grows to the next capacity class on the device.

Block edits (`bench/configs/a2-sources.json`: one push in seven a diff
hunk) outgrow the capacity class a session was built at. Since PR 36 a
tail that overflows is answered by `FusedDocSession.make_room`: the
resident row copied into a zeroed row of the smallest class that holds
the plan's peak, by one small program a (cap, cap2) pair, with no host
checkout and no upload; `_materialize` stays for builds and where the
copy itself fails. Pinned here, on the CPU (counts and bytes, never a time):
the device row against a plain `bytearray` reference that holds no CRDT
(`bench/corpus.py`), the fused batch against the per-doc ladder, the
class landed on, the fallback, the slot budget, a mesh window, the copy
programs compiled ahead and the counts a reader takes.
"""

import hashlib

import numpy as np
import pytest

from bench import corpus
from bench.run import device_text
from diamond_types_tpu.obs.phases import PhaseTable
from diamond_types_tpu.serve.admission import PendingMerge
from diamond_types_tpu.serve.bank import SessionBank
from diamond_types_tpu.serve.metrics import ServeMetrics
from diamond_types_tpu.serve.scheduler import MergeScheduler
from diamond_types_tpu.text.oplog import OpLog
from diamond_types_tpu.tpu import flush_fuse as ff
from diamond_types_tpu.tpu.steer import cap_class

pytestmark = [pytest.mark.fused, pytest.mark.serve]

FUSED_OPTS = {"cap": 256, "max_ins": 16}
HUNK = {"paste_every": 1, "paste_chars": [64, 160]}


def _mk_oplog(doc_id: str) -> OpLog:
    ol = OpLog()
    ol.doc_id = doc_id
    return ol


class _Source:
    """The `a2-sources` shape at a hundredth of the size: a typed file,
    `writers` writers a region each behind guard characters, from their
    own heads (they never see each other); a push is one hunk (one
    insert of 64-160 characters) or the writer's next 8 keystrokes."""

    def __init__(self, seed: int, writers: int, n_ops: int = 1500,
                 doc_id: str = "src") -> None:
        self.ol = ol = _mk_oplog(doc_id)
        pos, nd, ni, chars = corpus.doc_columns(seed, 0, n_ops)
        ol.apply_local_patch_columns(ol.get_or_create_agent_id("seed"),
                                     pos, nd, ni, chars.decode())
        self.hunkers = [corpus.Typist(np.random.default_rng([seed, 1, w]),
                                      HUNK) for w in range(writers)]
        self.typers = [corpus.Typist(np.random.default_rng([seed, 2, w]),
                                     {}) for w in range(writers)]
        self.plain = corpus.PlainDoc(
            doc_id, corpus.doc_text(seed, 0, n_ops), writers, self.hunkers)
        self.heads = [list(ol.version) for _ in range(writers)]
        self.turn = 0

    def __len__(self) -> int:
        return len(self.plain.text())

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

    def _next(self, typists) -> None:
        w = self.turn % len(self.heads)
        self.turn += 1
        self.plain.typists = typists
        self.push(w, self.plain.next_push(w, 8))

    def hunk(self) -> None:
        self._next(self.hunkers)

    def type(self) -> None:
        self._next(self.typers)

    def hunks_until(self, length: int) -> None:
        while len(self) <= length:
            self.hunk()

    def text(self) -> str:
        return self.plain.text().decode("ascii")


def _row_text(sess) -> str:
    """The session's text as the benchmark reads it (`bench/run.py`):
    the whole row fetched and cut on the host at the device's own
    length, which has to be the host's projection."""
    assert sess.docs.shape == (sess.cap,)
    return device_text(sess)


def _counts(table: PhaseTable, name: str) -> dict:
    return table.snapshot()["phases"].get(name, {}).get("counts", {})


# ---- the device row against the plain reference --------------------------

@pytest.mark.parametrize("writers", [1, 2, 4])
@pytest.mark.parametrize("scenario", ["one_class", "two_classes",
                                      "mid_tail"])
def test_grown_session_equals_the_plain_reference(writers, scenario):
    """One sync replays a tail of hunks that crosses one class, two
    classes in one step, or has typing (inserts and backspaces) before
    and after the hunks that cross: one growth on the device each, no
    rebuild, the row equal to the reference byte for byte."""
    src = _Source(7 + writers, writers)
    sess = ff.FusedDocSession(src.ol, **FUSED_OPTS)
    cap0 = sess.cap
    assert cap0 == cap_class(2 * len(src)) == 2048
    if scenario == "mid_tail":
        for _ in range(2 * writers):
            src.type()
    src.hunks_until({"one_class": cap0, "two_classes": 2 * cap0,
                     "mid_tail": cap0}[scenario])
    if scenario == "mid_tail":
        for _ in range(2 * writers):
            src.type()
    want_cap = cap_class(len(src))
    assert want_cap == (4 * cap0 if scenario == "two_classes"
                        else 2 * cap0)
    table = PhaseTable()
    with table.phase("sched.flush"):
        steps = sess.sync()
    assert steps > 0 and sess.synced_to == len(src.ol)
    assert (sess.cap, sess.resyncs) == (want_cap, 0)
    assert _row_text(sess) == sess.text() == src.text()
    assert _counts(table, "bank.grow") == {
        "grown": 1, "grow_slots": want_cap - cap0}
    # and it goes on from there: the next hunks fit and grow nothing
    for _ in range(writers):
        src.hunk()
    with table.phase("sched.flush"):
        sess.sync()
    assert sess.cap == want_cap and _row_text(sess) == src.text()
    assert _counts(table, "bank.grow")["grown"] == 1


# ---- the fused batch against the per-doc ladder --------------------------

def _fleet(seed: int):
    """Two files of class 2^11 and one of 2^12, four writers each."""
    return {d: _Source(seed + i, 4, n_ops=n, doc_id=d)
            for i, (d, n) in enumerate((("s0", 1500), ("s1", 1500),
                                        ("l0", 3600)))}


def _items(docs):
    return [PendingMerge(d, 1, 0.0) for d in docs]


def test_fused_batch_of_two_classes_one_growing_equals_the_ladder():
    """`s0` outgrows 2^11 in the batch that also replays `s1` (2^11)
    and `l0` (2^12): it grows between the plan's two holds, joins `l0`'s group
    and the batch is two fused calls, none serial; a second bank that
    takes the same files one at a time (`sync_doc`) ends in the same
    rows, both equal to the reference."""
    fused, ladder = _fleet(31), _fleet(31)
    metrics = ServeMetrics(1, 8, 64)
    bank = SessionBank(0, max_sessions=8, fused_opts=FUSED_OPTS,
                       metrics=metrics)
    step = SessionBank(0, max_sessions=8, fused_opts=FUSED_OPTS)
    for fleet, b in ((fused, bank), (ladder, step)):
        b.sync_docs(_items(fleet), lambda d, f=fleet: f[d].ol)
        assert [b.sessions[d].cap for d in fleet] == [2048, 2048, 4096]
        fleet["s0"].hunks_until(2048)
        for d in ("s1", "l0"):
            for _ in range(4):
                fleet[d].hunk()
    table = PhaseTable()
    with table.phase("sched.flush"):
        out = bank.sync_docs(_items(fused), lambda d: fused[d].ol)
    assert out["fused_calls"] == 2 and out["fused_docs"] == 3
    assert out["fallback_docs"] == 0
    assert _counts(table, "bank.grow") == {"grown": 1, "grow_slots": 2048}
    for d in ladder:
        step.sync_doc(d, ladder[d].ol)
    for d in fused:
        a, b = bank.sessions[d], step.sessions[d]
        assert a.cap == b.cap == (2048 if d == "s1" else 4096)
        assert _row_text(a) == _row_text(b) == fused[d].text() \
            == ladder[d].text()
        assert a.resyncs == b.resyncs == 0
    totals = metrics.snapshot()["totals"]
    assert totals["host_fallbacks"] == totals["resyncs"] == 0


def test_a_grown_session_joins_its_new_class_through_the_row_programs():
    """The row a growth leaves (`jit_dt_grow`'s output) goes into the
    batch of its new class by `dt_stack_rows` beside a row that was
    there all along, and comes back out of `dt_unstack_rows` at the new
    capacity."""
    fleet = _fleet(41)
    bank = SessionBank(0, max_sessions=8, fused_opts=FUSED_OPTS)
    bank.sync_docs(_items(fleet), lambda d: fleet[d].ol)
    fleet["s0"].hunks_until(2048)
    for d in ("s1", "l0"):
        fleet[d].hunk()
    table = PhaseTable()
    with table.phase("sched.flush"):
        out = bank.sync_docs(_items(fleet), lambda d: fleet[d].ol)
    assert out["fused_calls"] == 2 and out["fallback_docs"] == 0
    assert _counts(table, "bank.grow")["grown"] == 1
    grown = bank.sessions["s0"]
    assert grown.cap == 4096 and grown.resyncs == 0
    for d, src in fleet.items():
        sess = bank.sessions[d]
        assert sess.docs.shape == (sess.cap,) and sess.lens.shape == ()
        assert _row_text(sess) == src.text()


@pytest.mark.mesh
def test_the_per_shard_rung_leaves_rows_on_the_banks_chip():
    """Four shards, a device each, no mesh window: a bank's fused call
    runs its two row programs on the bank's chip, so a committed row
    and its length lie there after a first flush (rows from builds) and
    after a second (rows out of `dt_unstack_rows`)."""
    from diamond_types_tpu.obs import Observability
    fleet = {f"p{i}": _Source(90 + i, 2, doc_id=f"p{i}") for i in range(12)}
    sched = MergeScheduler(4, resolve=lambda d: fleet[d].ol,
                           engine="device", fused_opts=FUSED_OPTS,
                           flush_docs=8, flush_deadline_s=10.0,
                           flush_workers=False, place_on_devices=True)
    sched.attach_obs(Observability())
    assert len({bank.device for bank in sched.banks}) == 4
    for rnd in range(3):
        for d, src in fleet.items():
            if rnd:
                src.type()
            assert sched.submit(d, n_ops=1)["accepted"]
        sched.pump(force=True)
        sched.drain()
        for bank in sched.banks:
            for d, sess in bank.sessions.items():
                assert sess.docs.devices() == {bank.device}, (rnd, d)
                assert sess.lens.devices() == {bank.device}, (rnd, d)
                assert _row_text(sess) == fleet[d].text()
    ph = sched.metrics_json()["phases"]["phases"]
    assert ph["replay"]["count"] >= 2       # rounds 1 and 2 both replayed
    totals = sched.metrics_json()["totals"]
    assert totals["host_fallbacks"] == totals["device_errors"] == 0
    sched.stop_workers()


# ---- no checkout, no upload ----------------------------------------------

@pytest.mark.parametrize("through", ["grow", "sync"])
def test_growth_makes_no_checkout_and_uploads_nothing(through, monkeypatch):
    """The growth path never asks the host for the document
    (`checkout_tip`, a spy) and moves no resident byte host to device
    (`note_transfer`): a sync uploads its plan's rows and nothing
    else."""
    from diamond_types_tpu.obs import devprof
    src = _Source(3, 2)
    sess = ff.FusedDocSession(src.ol, **FUSED_OPTS)
    src.hunks_until(sess.cap)
    want = src.ol.checkout_tip().snapshot()
    calls, moved = [], []
    monkeypatch.setattr(OpLog, "checkout_tip",
                        lambda self: calls.append("checkout_tip"))
    monkeypatch.setattr(OpLog, "checkout",
                        lambda self, *a: calls.append("checkout"))
    monkeypatch.setattr(
        devprof, "note_transfer",
        lambda n, rung="", purpose="": moved.append((rung, purpose)))
    if through == "grow":
        before = (sess.doc_len, sess.frontier, sess.synced_to,
                  int(np.asarray(sess.lens)))
        row = np.asarray(sess.docs)
        sess.grow(8192)
        assert (sess.doc_len, sess.frontier, sess.synced_to,
                int(np.asarray(sess.lens))) == before
        grown = np.asarray(sess.docs)
        assert sess.cap == len(grown) == 8192
        assert (grown[:len(row)] == row).all() and not grown[len(row):].any()
        assert moved == []
    else:
        sess.sync()
        assert sess.cap == 4096 and moved == [("fused", "plan")]
    assert calls == [] and sess.resyncs == 0
    monkeypatch.undo()
    if through == "sync":
        assert _row_text(sess) == want == src.text()


# ---- the class landed on -------------------------------------------------

@pytest.mark.parametrize("peak,want", [(257, 512), (512, 512), (513, 1024),
                                       (1025, 2048), (5000, 8192)])
def test_the_class_landed_on_is_the_smallest_that_fits(peak, want):
    """`headroom` sizes a build, not a growth: a plan whose peak is
    `peak` lands on `cap_class(peak)`, however many classes up."""
    ol = _mk_oplog("fit")
    a = ol.get_or_create_agent_id("a")
    ol.add_insert(a, 0, "x" * 100)
    sess = ff.FusedDocSession(ol, cap=256, max_ins=16, headroom=2.0)
    assert sess.cap == 256
    ol.add_insert(a, 50, "y" * (peak - 100))
    plan = sess.plan_tail()
    assert plan.max_len == peak and not plan.fits(sess.cap)
    assert sess.make_room(plan) is True
    assert sess.cap == want and plan.fits(sess.cap)
    ok, _dev = ff.fused_replay([sess], [plan])
    assert ok == [True] and _row_text(sess) == ol.checkout_tip().snapshot()


# ---- where the copy fails, and a fence failure after it ------------------

def _failing_grow_fn(cap, cap2):
    import jax

    def fn(_row):
        raise jax.errors.JaxRuntimeError(
            f"RESOURCE_EXHAUSTED: injected, {cap} -> {cap2}")
    return fn


def test_a_failing_growth_ends_in_materialize_and_is_counted(monkeypatch):
    src = _Source(5, 2)
    sess = ff.FusedDocSession(src.ol, **FUSED_OPTS)
    src.hunks_until(sess.cap)
    monkeypatch.setattr(ff, "_grow_fn", _failing_grow_fn)
    table = PhaseTable()
    with table.phase("sched.flush"):
        assert sess.sync() == 0     # rebuilt at the tip: nothing replayed
    # a rebuild is a build: `headroom` sizes it
    assert sess.resyncs == 1 and sess.cap == cap_class(2 * len(src))
    assert sess.synced_to == len(src.ol) and _row_text(sess) == src.text()
    assert _counts(table, "bank.grow") == {
        "grow_rebuilt": 1, "grow_slots": sess.cap - 2048}


def test_a_failing_growth_in_a_fused_batch_takes_the_per_doc_path(
        monkeypatch):
    """The rebuilt session is at the tip: it leaves the fused batch for
    `sync_doc`, which counts the resync; the others are replayed."""
    fleet = _fleet(43)
    metrics = ServeMetrics(1, 8, 64)
    bank = SessionBank(0, max_sessions=8, fused_opts=FUSED_OPTS,
                       metrics=metrics)
    bank.sync_docs(_items(fleet), lambda d: fleet[d].ol)
    fleet["s0"].hunks_until(2048)
    for d in ("s1", "l0"):
        fleet[d].hunk()
    monkeypatch.setattr(ff, "_grow_fn", _failing_grow_fn)
    table = PhaseTable()
    with table.phase("sched.flush"):
        out = bank.sync_docs(_items(fleet), lambda d: fleet[d].ol)
    assert out["fused_docs"] == 2 and out["fallback_docs"] == 1
    assert _counts(table, "bank.grow")["grow_rebuilt"] == 1
    assert "grown" not in _counts(table, "bank.grow")
    totals = metrics.snapshot()["totals"]
    assert totals["resyncs"] == 1 and totals["host_fallbacks"] == 0
    for d, src in fleet.items():
        assert _row_text(bank.sessions[d]) == src.text()


def test_a_fence_failure_after_a_growth_ends_in_materialize(monkeypatch):
    """The grown row's replay comes back poisoned: the session is
    evicted to the host oracle as before, and the next flush builds it
    anew from a host checkout at its own class."""
    src = _Source(9, 2)
    metrics = ServeMetrics(1, 8, 64)
    bank = SessionBank(0, max_sessions=8, fused_opts=FUSED_OPTS,
                       metrics=metrics)
    bank.sync_doc("src", src.ol)
    src.hunks_until(2048)
    real = ff.adopt_results
    monkeypatch.setattr(
        ff, "adopt_results",
        lambda sessions, plans, docs, lens, got: real(
            sessions, plans, docs, lens, np.full_like(got, -1)))
    out = bank.sync_doc("src", src.ol)
    assert out["engine"] == "host" and "src" not in bank.sessions
    monkeypatch.undo()
    src.hunk()
    assert bank.sync_doc("src", src.ol)["engine"] == "device"
    sess = bank.sessions["src"]
    assert sess.resyncs == 0 and sess.cap == cap_class(2 * len(src))
    assert _row_text(sess) == src.text()
    assert metrics.snapshot()["totals"]["host_fallbacks"] == 1


# ---- the slot budget -----------------------------------------------------

@pytest.mark.parametrize("path", ["fused", "per_doc"])
def test_the_slot_budget_evicts_another_never_the_one_that_grew(path):
    """Three files of 2^11 fill a bank of 3 x 2^11 slots; the one that
    grows to 2^12 keeps its place and the least recently used of the
    others goes."""
    fleet = {d: _Source(50 + i, 2, doc_id=d)
             for i, d in enumerate(("a", "b", "c"))}
    metrics = ServeMetrics(1, 8, 64)
    bank = SessionBank(0, max_sessions=8, max_slots=3 * 2048,
                       fused_opts=FUSED_OPTS, metrics=metrics)
    bank.sync_docs(_items(fleet), lambda d: fleet[d].ol)
    assert bank.footprint_slots() == 3 * 2048
    fleet["b"].hunks_until(2048)
    fleet["c"].hunk()
    if path == "fused":
        bank.sync_docs(_items(["b", "c"]), lambda d: fleet[d].ol)
    else:
        bank.sync_doc("b", fleet["b"].ol)
    assert list(bank.sessions) == (["b", "c"] if path == "fused"
                                   else ["c", "b"])
    assert bank.sessions["b"].cap == 4096
    assert bank.footprint_slots() == 4096 + 2048 <= bank.max_slots
    assert _row_text(bank.sessions["b"]) == fleet["b"].text()
    totals = metrics.snapshot()["totals"]
    assert totals["evictions"] == 1 and totals["host_fallbacks"] == 0


def test_room_is_made_before_the_copy_not_after(monkeypatch):
    """While the old row and the new one both live, the bank is inside
    its slot budget: the victim goes before the copy is dispatched."""
    fleet = {d: _Source(50 + i, 2, doc_id=d)
             for i, d in enumerate(("a", "b", "c"))}
    bank = SessionBank(0, max_sessions=8, max_slots=3 * 2048,
                       fused_opts=FUSED_OPTS)
    bank.sync_docs(_items(fleet), lambda d: fleet[d].ol)
    fleet["b"].hunks_until(2048)
    fleet["c"].hunk()
    seen = []
    grow = ff.FusedDocSession.grow

    def spy(self, cap2):
        seen.append(bank.footprint_slots() + cap2 - self.cap)
        grow(self, cap2)
    monkeypatch.setattr(ff.FusedDocSession, "grow", spy)
    bank.sync_docs(_items(["b", "c"]), lambda d: fleet[d].ol)
    assert seen == [2 * 2048 + 2048] and seen[0] <= bank.max_slots
    assert bank.sessions["b"].cap == 4096


# ---- a mesh window -------------------------------------------------------

@pytest.mark.mesh
def test_a_mesh_window_with_a_growing_session():
    """Four shards, a device each, mesh flush windows: the session that
    outgrows its class goes to its new class's dispatch with no serial
    document, its old arena tag is gone and the new class's arena tags
    it; every committed row equals the reference and lies on its bank's
    chip."""
    from diamond_types_tpu.obs import Observability
    from diamond_types_tpu.parallel import arena
    from diamond_types_tpu.tpu.steer import STEER
    STEER.reset(table=True)
    arena.reset_arenas()
    fleet = {f"m{i}": _Source(70 + i, 2, doc_id=f"m{i}") for i in range(6)}
    sched = MergeScheduler(4, resolve=lambda d: fleet[d].ol,
                           engine="device", fused_opts=FUSED_OPTS,
                           flush_docs=8, flush_deadline_s=10.0,
                           flush_workers=False, mesh_window=True,
                           place_on_devices=True)
    sched.attach_obs(Observability())

    def window():
        for d in fleet:
            assert sched.submit(d, n_ops=1)["accepted"]
        sched.pump(force=True)

    window()                        # builds
    for src in fleet.values():
        src.hunk()
    window()                        # every row committed and tagged
    sessions = {}
    for bank in sched.banks:
        sessions.update(bank.sessions)
    grower = sessions["m2"]
    old_tag = grower._arena_tag
    assert old_tag is not None and grower.cap == 2048
    fleet["m2"].hunks_until(2048)
    for d in ("m0", "m4"):
        fleet[d].hunk()
    window()
    assert grower.cap == 4096 and grower.resyncs == 0
    assert grower._arena_tag is not None \
        and grower._arena_tag[0] is not old_tag[0]
    ph = sched.metrics_json()["phases"]["phases"]
    assert ph["bank.grow"]["counts"] == {"grown": 1, "grow_slots": 2048}
    assert ph["sched.flush"]["counts"]["window_serial_docs"] == 0
    by_cap = {k: v for k, v in ph["mesh.replay"]["counts"].items()
              if k.endswith(".docs")}
    assert by_cap["cap.4096.docs"] == 1 and by_cap["cap.2048.docs"] >= 8
    for bank in sched.banks:
        for d, sess in bank.sessions.items():
            assert _row_text(sess) == fleet[d].text()
            assert sess.docs.devices() == {bank.device}
    totals = sched.metrics_json()["totals"]
    assert totals["host_fallbacks"] == totals["device_errors"] == 0
    sched.stop_workers()
    STEER.reset(table=True)
    arena.reset_arenas()


# ---- the copy programs are compiled ahead --------------------------------

@pytest.mark.parametrize("committed", [False, True])
def test_the_copy_program_exists_after_the_first_build_of_a_class(
        committed):
    """A bank compiles the copy from a class to the next one up at the
    first session it builds of that class, for a row as a build leaves
    it and for one committed to its chip (as a mesh window hands rows
    back): a later growth of either compiles nothing."""
    import jax

    from diamond_types_tpu.tpu.runtime import COMPILE_STATS
    src = _Source(11, 1, n_ops=9000)
    bank = SessionBank(0, max_sessions=2, fused_opts=FUSED_OPTS)
    bank.sync_doc("src", src.ol)        # `first_touch` counts compiles
    sess = bank.sessions["src"]
    cap = sess.cap
    assert cap == 8192 and (cap, 2 * cap) in ff._grow_fns
    if committed:
        sess.docs = jax.device_put(sess.docs, sess.docs.device)
    jax.block_until_ready(sess.docs)
    before = COMPILE_STATS.snapshot()
    sess.grow(2 * cap)
    jax.block_until_ready(sess.docs)
    assert COMPILE_STATS.delta(COMPILE_STATS.snapshot(),
                               before)["compiles"] == 0
    assert sess.docs.committed is committed
    assert _row_text(sess) == src.text()


def test_copies_between_the_classes_present_are_compiled_at_the_builds(
        monkeypatch):
    """The first build of a class compiles the copy to the next class
    up and between it and every class the bank built before, on the
    bank's chip: a growth of two classes in one step into a class that
    is present compiles nothing, and a second file of a class warms
    nothing again."""
    import jax

    from diamond_types_tpu.tpu.runtime import COMPILE_STATS
    small = _Source(21, 1, n_ops=1500, doc_id="small")      # 2^11
    large = _Source(22, 1, n_ops=9000, doc_id="large")      # 2^13
    again = _Source(23, 1, n_ops=1500, doc_id="again")      # 2^11
    fleet = {"small": small, "large": large, "again": again}
    warmed = []
    warm = ff.warm_grow

    def spy(cap, cap2):
        warmed.append((cap, cap2))
        warm(cap, cap2)
    monkeypatch.setattr(ff, "warm_grow", spy)
    bank = SessionBank(0, max_sessions=4, fused_opts=FUSED_OPTS)
    bank.sync_docs(_items(fleet), lambda d: fleet[d].ol)
    caps = sorted(s.cap for s in bank.sessions.values())
    assert caps == [2048, 2048, 8192]
    assert sorted(warmed) == [(2048, 4096), (2048, 8192), (8192, 16384)]
    small.hunks_until(4096)             # past 2^12: two classes up
    large.hunk()
    jax.block_until_ready([s.docs for s in bank.sessions.values()])
    before = COMPILE_STATS.snapshot()
    sess = bank.sessions["small"]
    plan = sess.plan_tail()
    assert cap_class(plan.max_len) == 8192
    with bank._on_device():
        assert sess.make_room(plan)
    assert COMPILE_STATS.delta(COMPILE_STATS.snapshot(),
                               before)["compiles"] == 0
    assert sess.cap == 8192
    bank.sync_docs(_items(fleet), lambda d: fleet[d].ol)
    assert _row_text(sess) == small.text()
    assert len(warmed) == 3


# ---- the counts a reader takes -------------------------------------------

def test_the_counts_read_what_a_hand_counted_plan_says():
    """One insert of 100 characters (7 rows of 16), one of 10 (one
    row), a delete of 40 (3 rows): 11 rows, 10 of them block rows; the
    replay of 11 rows is padded to 16 scan steps; the growth 256 ->
    512 gains 256 slots."""
    ol = _mk_oplog("count")
    a = ol.get_or_create_agent_id("a")
    ol.add_insert(a, 0, "x" * 200)
    sess = ff.FusedDocSession(ol, cap=256, max_ins=16, headroom=1.0)
    assert sess.cap == 256
    ol.add_insert(a, 20, "y" * 100)
    ol.add_insert(a, 5, "z" * 10)
    ol.add_delete_without_content(a, 150, 190)
    table = PhaseTable()
    with table.phase("sched.flush"):
        assert sess.sync() == 11
    plan = _counts(table, "plan.tail")
    assert (plan["rows"], plan["block_rows"]) == (11, 10)
    assert _counts(table, "replay") == {"scan_steps": 16}
    assert _counts(table, "bank.grow") == {"grown": 1, "grow_slots": 256}
    assert sess.cap == 512 and _row_text(sess) == ol.checkout_tip().snapshot()
    # keystrokes make rows and no block row
    ol.add_insert(a, 0, "k")
    with table.phase("sched.flush"):
        sess.sync()
    plan = _counts(table, "plan.tail")
    assert (plan["rows"], plan["block_rows"]) == (12, 10)


# ---- the rows of a block are the parent's --------------------------------

def _digest(plan) -> str:
    h = hashlib.sha256()
    for f in (plan.pos, plan.dlen, plan.ilen, plan.chars):
        h.update(np.ascontiguousarray(f).tobytes())
    return h.hexdigest()


def _pinned_session():
    ol = _mk_oplog("pin")
    a = ol.get_or_create_agent_id("a")
    ol.add_insert(a, 0, "".join(chr(97 + (i * 7) % 26) for i in range(400)))
    return ol, a, ff.FusedDocSession(ol, cap=1024, max_ins=16)


INSERT_2048 = "".join(chr(65 + (i * 11 + i // 16) % 26) for i in range(2048))


def test_plan_rows_of_a_2048_character_insert_are_the_parents():
    """Pinned from the parent commit (f16ccac): `_plan_tail`'s row loop
    is measured by PR 36, not rewritten, and `max_ins` stays 16."""
    ol, a, sess = _pinned_session()
    ol.add_insert(a, 37, INSERT_2048)
    p = sess.plan_tail()
    assert (p.n_ops, p.new_len, p.max_len) == (128, 2448, 2448)
    assert p.pos.tolist() == [37 + 16 * i for i in range(128)]
    assert set(p.ilen.tolist()) == {16} and set(p.dlen.tolist()) == {0}
    assert p.chars[0].tolist() == [65, 76, 87, 72, 83, 68, 79, 90, 75, 86,
                                   71, 82, 67, 78, 89, 74]
    assert p.chars[-1].tolist() == [80, 65, 76, 87, 72, 83, 68, 79, 90, 75,
                                    86, 71, 82, 67, 78, 89]
    assert _digest(p) == ("43cbcbfdab8c9f83c4d4b07a7a8cdb50"
                          "ed24bf8bd9e87c2128e2f2796e184df1")


def test_plan_rows_of_a_300_character_delete_are_the_parents():
    ol, a, sess = _pinned_session()
    ol.add_insert(a, 37, INSERT_2048)
    sess.commit(sess.docs, sess.lens, sess.plan_tail())
    ol.add_delete_without_content(a, 100, 400)
    p = sess.plan_tail()
    assert (p.n_ops, p.new_len, p.max_len) == (19, 2148, 2448)
    assert p.pos.tolist() == [100] * 19
    assert p.dlen.tolist() == [16] * 18 + [12]
    assert set(p.ilen.tolist()) == {0} and not p.chars.any()
    assert _digest(p) == ("44481e67ea01e6a42026797fde9e36ab"
                          "3ffa5f540d76f79c5dc68863f52a3b03")
