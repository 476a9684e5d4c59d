"""The oplog's native mirror (`NativeContext`) follows the oplog by
appending. Over seeded histories a context kept from the first op on
must answer exactly as one built whole on the same oplog: the same
snapshot and patch bytes (and the Python writer's), the same transform
columns, the same checkout. A column that does not continue what the
mirror holds is built whole again, and that is counted.

    python -m pytest tests/test_native_mirror.py -q -p no:cacheprovider
"""

import random

import pytest

from diamond_types_tpu import OpLog
from diamond_types_tpu.encoding.decode import decode_into
from diamond_types_tpu.encoding.encode import (ENCODE_FULL, ENCODE_PATCH,
                                               encode_oplog)
from diamond_types_tpu.native import native_available
from diamond_types_tpu.native.core import NativeContext, get_native_ctx

from test_fuzz import random_edit

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="native core not built")

ROUNDS = 36


def type_run(rng, ol, agent, version, content):
    """A typist's run at a cursor, then some backspaces: op by op, so
    that the oplog extends its last op run, agent run and graph entry
    in place."""
    pos = rng.randint(0, len(content))
    for _ in range(rng.randint(1, 5)):
        ch = rng.choice("abcdefgh \n")
        version = [ol.add_insert_at(agent, version, pos, ch)]
        content = content[:pos] + ch + content[pos:]
        pos += 1
    for _ in range(rng.randint(0, 3)):
        if pos == 0:
            break
        version = [ol.add_delete_at(agent, version, pos - 1, pos, None)]
        content = content[:pos - 1] + content[pos:]
        pos -= 1
    return version, content


def edit(rng, ol, agent, version, content):
    if rng.random() < 0.5:
        return type_run(rng, ol, agent, version, content)
    return random_edit(rng, ol, agent, version, content)


class History:
    """One oplog and the writers' branches on it; `step` appends."""

    def __init__(self, shape: str, seed: int) -> None:
        self.rng = rng = random.Random(f"{shape}:{seed}")
        self.shape = shape
        self.ol = OpLog()
        self.ol.doc_id = "mirror"
        n = 1 if shape == "linear" else rng.randint(2, 4)
        self.agents = [self.ol.get_or_create_agent_id(f"w{i}")
                       for i in range(n)]
        # a branch a writer: (version, content)
        self.branches = [([], "") for _ in range(n)]
        if shape == "remote":
            self.peer = OpLog()
            self.peer_agent = self.peer.get_or_create_agent_id("peer")
            self.peer_state = ([], "")
            self.peer_sent = []

    def step(self, i: int) -> None:
        rng, ol = self.rng, self.ol
        if i == ROUNDS // 2:            # a new agent mid-way
            self.agents.append(ol.get_or_create_agent_id("late"))
            w = rng.randrange(len(self.branches))
            self.branches.append(self.branches[w])
        if self.shape == "remote" and rng.random() < 0.4:
            return self._remote_patch()
        w = rng.randrange(len(self.branches))
        v, c = self.branches[w]
        for _ in range(rng.randint(1, 3)):
            v, c = edit(rng, ol, self.agents[w], v, c)
        self.branches[w] = (v, c)
        if self.shape in ("merging", "remote") and rng.random() < 0.3 \
                and len(self.branches) > 1:
            # this writer pulls another's head: they see each other
            o = rng.choice([x for x in range(len(self.branches)) if x != w])
            mv = ol.cg.graph.version_union(v, self.branches[o][0])
            self.branches[w] = (list(mv), ol.checkout(mv).snapshot())

    def _remote_patch(self) -> None:
        """The peer types on its own log; what it has made since its
        last patch arrives as a v1 patch and is merged by `decode_into`."""
        rng, peer = self.rng, self.peer
        v, c = self.peer_state
        for _ in range(rng.randint(1, 3)):
            v, c = edit(rng, peer, self.peer_agent, v, c)
        self.peer_state = (v, c)
        patch = encode_oplog(peer, ENCODE_PATCH, from_version=self.peer_sent)
        self.peer_sent = list(peer.version)
        decode_into(self.ol, patch)


def columns(ctx, frm, to):
    lv, ln, kind, fwd, pos, frontier = ctx.transform(frm, to)
    return (lv.tolist(), ln.tolist(), kind.tolist(), fwd.tolist(),
            pos.tolist(), list(frontier))


def assert_same_answers(h: History, kept: NativeContext, monkeypatch,
                        earlier) -> None:
    ol = h.ol
    fresh = NativeContext(ol)
    tip = ol.version
    # (b) the transform, from nothing, from every writer's head and from
    # an earlier tip
    for frm in [[], earlier] + [v for v, _c in h.branches]:
        assert columns(kept, frm, tip) == columns(fresh, frm, tip)
    # (c) the checkout
    text, frontier = kept.merge_to_string("", [], tip)
    assert (text, frontier) == fresh.merge_to_string("", [], tip)
    # (a) snapshot and patch bytes: kept == fresh == the Python writer
    full = kept.encode_full(ol.doc_id, None, True, True)
    patch = kept.encode_patch(ol.doc_id, None, True, False, earlier) \
        if earlier else None
    assert full is not None
    assert full == fresh.encode_full(ol.doc_id, None, True, True)
    if earlier:
        assert patch == fresh.encode_patch(ol.doc_id, None, True, False,
                                           earlier)
    with monkeypatch.context() as m:
        m.setenv("DT_TPU_NO_NATIVE", "1")
        assert full == encode_oplog(ol, ENCODE_FULL)
        assert text == ol.checkout_tip().snapshot()
        if earlier:
            from diamond_types_tpu.encoding.encode import EncodeOptions
            assert patch == encode_oplog(
                ol, EncodeOptions(store_start_branch_content=False,
                                  compress_content=False),
                from_version=earlier)


SHAPES = ("linear", "own_heads", "merging", "remote")


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("shape", SHAPES)
def test_a_mirror_kept_by_appending_answers_as_a_fresh_one(shape, seed,
                                                          monkeypatch):
    h = History(shape, seed)
    kept = get_native_ctx(h.ol)
    kept.sync()                         # the empty oplog: the first sync
    earlier = []
    for i in range(ROUNDS):
        h.step(i)
        if i % 3 == 2:
            assert_same_answers(h, kept, monkeypatch, earlier)
            if h.rng.random() < 0.5:
                earlier = list(h.ol.version)
    assert_same_answers(h, kept, monkeypatch, earlier)
    # every sync after the first found the columns continued
    assert kept.rebuilt == 1 and kept.appended >= ROUNDS // 3


def test_an_unchanged_oplog_is_not_sent_again():
    h = History("own_heads", 0)
    for i in range(6):
        h.step(i)
    ctx = NativeContext(h.ol)
    ctx.sync()
    before = (ctx.appended, ctx.rebuilt)
    ctx.sync()
    ctx.transform([], h.ol.version)
    assert (ctx.appended, ctx.rebuilt) == before == (0, 1)


def _typed(name: str, n: int) -> OpLog:
    ol = OpLog()
    a = ol.get_or_create_agent_id(name)
    for i in range(n):
        ol.add_insert(a, i, "x")
    return ol


def _forked(n: int) -> OpLog:
    """Two writers from the root: as many columns as `_typed` has and
    more, but the first graph entry ends early."""
    ol = OpLog()
    a, b = (ol.get_or_create_agent_id(x) for x in ("a", "b"))
    va = vb = []
    for i in range(n):
        va = [ol.add_insert_at(a, va, 0, "a")]
        vb = [ol.add_insert_at(b, vb, 0, "b")]
    return ol


@pytest.mark.parametrize("other", ["shorter", "diverged"])
def test_a_column_that_does_not_continue_is_built_whole_and_counted(other):
    """The append cannot be proved safe: the oplog is shorter than what
    the mirror holds, or as long but not its continuation (the last
    entry held does not come back). The mirror is built whole again,
    the rebuild is counted, and the answers are a fresh context's."""
    ol = _typed("a", 12)
    ctx = NativeContext(ol)
    ctx.sync()
    assert (ctx.appended, ctx.rebuilt) == (0, 1)
    ol2 = _typed("b", 5) if other == "shorter" else _forked(9)
    ctx._oplog = ol2
    fresh = NativeContext(ol2)
    assert columns(ctx, [], ol2.version) == columns(fresh, [], ol2.version)
    assert ctx.encode_full(None, None, True, False) \
        == fresh.encode_full(None, None, True, False)
    assert (ctx.appended, ctx.rebuilt) == (0, 2)
    # and it follows the new oplog by appending from there
    ol2.add_insert(0, 0, "tail")
    assert columns(ctx, [], ol2.version) \
        == columns(NativeContext(ol2), [], ol2.version)
    assert (ctx.appended, ctx.rebuilt) == (1, 2)


# ---- which calls keep the interpreter ------------------------------------

class _Named:
    """A library handle that notes the functions looked up on it."""

    def __init__(self, lib) -> None:
        self.lib, self.calls = lib, []

    def __getattr__(self, name):
        self.calls.append(name)
        return getattr(self.lib, name)


TAIL_LOADERS = ["dt_load_graph_tail", "dt_load_agent_runs_tail",
                "dt_load_ops_tail", "dt_load_ins_arena_tail"]


@pytest.mark.parametrize("build", ["tail", "whole"])
def test_a_plan_walk_lets_the_interpreter_go_once(build):
    """A walk as `plan_tail` makes it (sync, transform, release): where
    the mirror follows by appending, only the transform itself goes
    through the handle that lets the interpreter go (`ct.CDLL`); the
    tail's loaders, the copies out of the result and the release go
    through the one that keeps it (`ct.PyDLL`): one hand-off a walk
    under the store's lock, not eight. A whole build, milliseconds on a
    long document, lets it go."""
    import ctypes

    from diamond_types_tpu.native import core
    assert isinstance(core._lib_kept, ctypes.PyDLL)
    assert not isinstance(core._lib, ctypes.PyDLL)
    ol = _typed("a", 12)
    ctx = NativeContext(ol)
    if build == "tail":
        ctx.sync()
        ol.add_insert(0, 0, "tail")
    ctx._lib, ctx._kept = _Named(ctx._lib), _Named(ctx._kept)
    got = columns(ctx, [], ol.version)
    ctx.release_tracker()
    fetch = ["dt_get_out", "dt_get_out_frontier", "dt_release_tracker"]
    if build == "tail":
        assert ctx._lib.calls == ["dt_transform"]
        assert ctx._kept.calls == TAIL_LOADERS + fetch
        assert (ctx.appended, ctx.rebuilt) == (1, 1)
    else:
        assert ctx._lib.calls == ["dt_ctx_free", "dt_ctx_new",
                                  "dt_add_agent"] + TAIL_LOADERS \
            + ["dt_transform"]
        assert ctx._kept.calls == fetch
        assert (ctx.appended, ctx.rebuilt) == (0, 1)
    assert got == columns(NativeContext(ol), [], ol.version)
