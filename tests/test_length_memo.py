"""`OpLog.length_at` / `remember_length`: a document's length at a
frontier, remembered because an append-only log never changes it.
Seeded random histories of concurrent agents are the property test:
whatever the memo answers, a full checkout answers the same."""

from __future__ import annotations

import random

import pytest

from diamond_types_tpu.text.oplog import LEN_MEMO_SIZE, OpLog

ALPHABET = "abc xyz\né中\U0001F600\U0001F3F4\U00010348"


class _Counted:
    """Counts the full checkouts an oplog pays."""

    def __init__(self, monkeypatch) -> None:
        self.n = 0
        real = OpLog.checkout

        def checkout(ol, frontier):
            self.n += 1
            return real(ol, frontier)

        monkeypatch.setattr(OpLog, "checkout", checkout)
        self.real = real


class _History:
    """`n_agents` writers, each typing from their own head and now and
    then pulling a peer's; every length the writers use comes from
    `length_at`, every length they leave goes to `remember_length` (the
    edit handler's arithmetic), and each is checked against a checkout."""

    def __init__(self, seed: int, n_agents: int, real_checkout) -> None:
        self.rng = random.Random(seed)
        self.ol = OpLog()
        self.truth = lambda f: len(real_checkout(self.ol, f))
        self.agents = [self.ol.get_or_create_agent_id(f"agent{i}")
                       for i in range(n_agents)]
        self.heads = [[] for _ in self.agents]

    def step(self) -> None:
        rng, ol = self.rng, self.ol
        i = rng.randrange(len(self.agents))
        if rng.random() < 0.25:         # pull a peer: two heads, or one
            j = rng.randrange(len(self.agents))
            both = sorted(set(self.heads[i]) | set(self.heads[j]))
            self.heads[i] = list(ol.cg.graph.find_dominators(both))
        f = self.heads[i]
        n = ol.length_at(f)
        assert n == self.truth(f)
        if n and rng.random() < 0.35:
            start = rng.randrange(n)
            end = rng.randint(start + 1, min(n, start + 4))
            lv = ol.add_delete_at(self.agents[i], f, start, end, None)
            n -= end - start
        else:
            text = "".join(rng.choice(ALPHABET)
                           for _ in range(rng.randint(1, 5)))
            lv = ol.add_insert_at(self.agents[i], f, rng.randint(0, n), text)
            n += len(text)
        self.heads[i] = [lv]
        ol.remember_length([lv], n)
        assert n == self.truth([lv])

    def frontiers(self) -> list:
        """One head, several heads (minimal and not), none, and versions
        in the middle of the log."""
        ol, rng = self.ol, self.rng
        every = sorted({lv for h in self.heads for lv in h})
        out = [list(h) for h in self.heads]
        out.append(list(ol.cg.graph.find_dominators(every)))
        out.append(every)                       # may not be minimal
        out.append(list(ol.version))
        out.append([])
        out += [[rng.randrange(len(ol))] for _ in range(3)]
        return out


@pytest.mark.parametrize("n_agents", [2, 3])
@pytest.mark.parametrize("seed", [1, 2, 3, 2 ** 31 + 11])
def test_length_at_equals_a_checkout_on_a_hit_and_on_a_miss(
        seed, n_agents, monkeypatch):
    counted = _Counted(monkeypatch)
    h = _History(seed, n_agents, counted.real)
    for _ in range(60):
        h.step()
    ol = h.ol
    asked = h.frontiers()
    assert any(len(f) > 1 for f in asked)
    for f in asked:
        want = h.truth(f)
        known = ol.length_known(f)
        before = counted.n
        assert ol.length_at(f) == want
        assert counted.n == before + (0 if known else 1)    # miss: one
        before = counted.n
        assert ol.length_known(f)
        assert ol.length_at(list(reversed(f))) == want      # hit: none
        assert counted.n == before
        assert len(ol._len_memo) <= LEN_MEMO_SIZE
    # further appends change no remembered length: the log only grows.
    # First around the memo, so that what it holds stays and must hit
    remembered = dict(ol._len_memo)
    assert len(remembered) == LEN_MEMO_SIZE
    for i, agent in enumerate(h.agents):
        h.heads[i] = [ol.add_insert_at(agent, h.heads[i], 0,
                                       "zz\U0001F600")]
    before = counted.n
    for key, n in remembered.items():
        assert ol.length_at(key) == n == h.truth(key)
    assert counted.n == before and ol._len_memo == remembered
    # then through it, until every entry has been replaced
    for _ in range(40):
        h.step()
    assert not set(remembered) & set(ol._len_memo)
    for f in asked + [list(k) for k in remembered] + h.frontiers():
        assert ol.length_at(f) == h.truth(f)
    assert len(ol._len_memo) == LEN_MEMO_SIZE


def test_the_memo_keeps_its_size_and_evicts_the_oldest_first():
    ol = OpLog()
    a = ol.get_or_create_agent_id("a")
    ol.add_insert_at(a, [], 0, "x" * (LEN_MEMO_SIZE + 4))
    for lv in range(LEN_MEMO_SIZE):
        ol.remember_length([lv], lv + 1)
    assert list(ol._len_memo) == [(lv,) for lv in range(LEN_MEMO_SIZE)]
    # a hit and a second remembering of the same version refresh nothing
    assert ol.length_at([0]) == 1
    ol.remember_length([0], 1)
    assert list(ol._len_memo)[0] == (0,)
    # one more: the oldest goes, whether remembered or checked out
    ol.remember_length([LEN_MEMO_SIZE], LEN_MEMO_SIZE + 1)
    assert not ol.length_known([0]) and ol.length_known([1])
    assert ol.length_at([LEN_MEMO_SIZE + 2]) == LEN_MEMO_SIZE + 3    # a miss
    assert not ol.length_known([1]) and ol.length_known([2])
    assert len(ol._len_memo) == LEN_MEMO_SIZE
    assert list(ol._len_memo)[-2:] == [(LEN_MEMO_SIZE,),
                                       (LEN_MEMO_SIZE + 2,)]
    # an evicted version is a miss again, and as exact as before
    assert ol.length_at([0]) == 1 and ol.length_known([0])


def test_a_frontier_is_one_key_in_any_order_and_a_new_oplog_knows_nothing():
    ol = OpLog()
    assert not ol.length_known([])
    assert ol.length_at([]) == 0 and ol.length_known(())
    a = ol.get_or_create_agent_id("a")
    b = ol.get_or_create_agent_id("b")
    la = ol.add_insert_at(a, [], 0, "aa\U0001F600")
    lb = ol.add_insert_at(b, [], 0, "b")
    assert ol.length_at([lb, la]) == 4
    assert ol.length_known([la, lb]) and ol.length_known((lb, la))
    assert list(ol._len_memo) == [(), (la, lb)]
    # loaded from bytes it is another object: nothing carried over
    from diamond_types_tpu.encoding.decode import load_oplog
    from diamond_types_tpu.encoding.encode import ENCODE_FULL, encode_oplog
    again = load_oplog(encode_oplog(ol, ENCODE_FULL))
    assert again._len_memo == {}
    assert again.length_at(again.version) == 4
