"""Seeded documents, the keystrokes writers push and the plain reference.

`typing_trace`, `plain_replay` and `apply_plain` are copied from
`chip_smoke.py`, so that the yardstick does not move when the smoke
does. A push is the next keystrokes of the same typing (`Typist`), not
the smoke's burst of edits at random positions: the cited corpora are
keystroke traces, runs at a cursor, and the oplog merges such runs.
Nothing here imports `jax` or `diamond_types_tpu`: the load generator
holds the plain reference, and a reference takes nothing the program
has made.

A document is a bytearray. A document with several writers is cut into
one region per writer, and each writer types only inside its own region
from its own head (it never sees the others' edits). A guard character
on each side of an inner boundary is never edited, so no two writers
ever insert into the same gap, and the CRDT's merge of the concurrent
branches is the plain concatenation of the regions: checkable without a
CRDT in the reference.
"""

from __future__ import annotations

import numpy as np

ALPHABET = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz    etaoin\n",
                         dtype=np.uint8)
# net characters a typing trace leaves per operation (mean run 14,
# 42.5 % backspaced): 259,778 ops leave ~104.9k chars as in the source
CHARS_PER_OP = 0.4035


def typing_trace(rng, n_ops: int, mean_run: int = 14,
                 p_back: float = 0.425):
    """`n_ops` single-keystroke ops of run-based typing: jump the cursor,
    type a run, backspace part of it. Returns the columnar patch arrays
    `apply_local_patch_columns` takes plus the run table the plain
    reference replays."""
    n_runs = int(n_ops / (mean_run * (1 + p_back)) * 1.25) + 64
    k = rng.geometric(1.0 / mean_run, size=n_runs).astype(np.int64)
    j = rng.binomial(k, p_back).astype(np.int64)
    cum = np.cumsum(k + j)
    last = int(np.searchsorted(cum, n_ops))
    k, j = k[:last + 1].copy(), j[:last + 1].copy()
    over = int(cum[last] - n_ops)
    dj = min(over, int(j[-1]))
    j[-1] -= dj
    k[-1] -= over - dj
    if k[-1] == 0:
        k, j = k[:-1], j[:-1]
    net = k - j
    cur = np.floor(rng.random(len(k)) * (np.cumsum(net) - net + 1)) \
        .astype(np.int64)
    per = k + j
    run = np.repeat(np.arange(len(k)), per)
    off = np.arange(len(run)) - np.repeat(np.cumsum(per) - per, per)
    is_ins = off < k[run]
    pos = np.where(is_ins, cur[run] + off, cur[run] + 2 * k[run] - 1 - off)
    chars = ALPHABET[rng.integers(0, len(ALPHABET), size=int(k.sum()))] \
        .tobytes()
    return (pos, (~is_ins).astype(np.int64), is_ins.astype(np.int64),
            chars, (cur, k, net))


def plain_replay(model: bytearray, chars: bytes, runs) -> None:
    """The plain reference: the same typing applied to a bytearray, one
    splice per run (what survives a run's backspaces is its prefix)."""
    cur, k, net = runs
    for c, o, keep in zip(cur.tolist(), (np.cumsum(k) - k).tolist(),
                          net.tolist()):
        model[c:c] = chars[o:o + keep]


class Typist:
    """One writer's keystrokes, as `typing_trace` models them but one
    at a time: jump the cursor, type a run (geometric, mean
    `mean_run`), backspace a binomial share `p_back` of it, jump again.
    A push is the next `n` keystrokes, so a run carries over from one
    push to the next and what the server's oplog sees is runs at a
    cursor, which it merges. `paste_every` makes every so-manyth push
    one insert of `paste_chars` [lo, hi] characters instead.

    Positions are in the writer's own editable text of `length`
    characters; the caller shifts them into the writer's view."""

    def __init__(self, rng, shape: dict) -> None:
        self.rng = rng
        self.mean_run = float(shape.get("mean_run", 14))
        self.p_back = float(shape.get("p_back", 0.425))
        self.paste_every = int(shape.get("paste_every", 0))
        self.paste_chars = shape.get("paste_chars")
        self.pushes = int(rng.integers(self.paste_every)) \
            if self.paste_every else 0
        self.reset()

    def reset(self) -> None:
        """Forget the run in hand (after a push whose fate is unknown,
        or a paste): the next keystroke jumps."""
        self.cur = self.to_type = self.to_erase = 0
        self.chars = b""

    def _text(self, n: int) -> bytes:
        return ALPHABET[self.rng.integers(0, len(ALPHABET), size=n)] \
            .tobytes()

    def next_push(self, length: int, n: int):
        """The JSON ops of the next push."""
        self.pushes += 1
        if self.paste_every and self.pushes % self.paste_every == 0:
            lo, hi = self.paste_chars
            self.reset()
            return [{"kind": "ins",
                     "pos": int(self.rng.random() * (length + 1)),
                     "text": self._text(int(self.rng.integers(lo, hi + 1)))
                     .decode()}]
        ops = []
        for _ in range(n):
            if not self.to_type and not self.to_erase:
                k = int(self.rng.geometric(1.0 / self.mean_run))
                self.to_type = k
                self.to_erase = int(self.rng.binomial(k, self.p_back))
                self.cur = int(self.rng.random() * (length + 1))
                self.chars = self._text(k)
            if self.to_type:
                c = self.chars[len(self.chars) - self.to_type]
                ops.append({"kind": "ins", "pos": self.cur, "text": chr(c)})
                self.to_type -= 1
                self.cur += 1
                length += 1
            else:
                ops.append({"kind": "del", "start": self.cur - 1,
                            "end": self.cur})
                self.to_erase -= 1
                self.cur -= 1
                length -= 1
        return ops


def scatter(rng, length: int, n: int):
    """`n` single-character inserts that the oplog cannot merge: each
    two or more characters before the last. The warm rounds push these,
    so that a flush of a known number of plan rows is compiled before
    the window opens (a plan has one row for each run of the oplog)."""
    slots = np.sort(rng.choice(length // 2 + 1, size=n, replace=False))
    return [{"kind": "ins", "pos": int(2 * p),
             "text": chr(ALPHABET[int(rng.integers(0, 26))])}
            for p in slots[::-1]]


def apply_plain(model: bytearray, ops, shift: int = 0) -> None:
    """Apply JSON ops to a bytearray; `shift` is subtracted from every
    position (ops are in a writer's view, the model is its region)."""
    for op in ops:
        if op["kind"] == "ins":
            p = op["pos"] - shift
            model[p:p] = op["text"].encode()
        else:
            del model[op["start"] - shift:op["end"] - shift]


def class_ops(cls: dict, seed: int, k: int) -> list:
    """Operations per document of fleet class number `k`. Every seed
    gets the same set of sizes (evenly spaced over the class's range),
    in another order, so that the seed does not change the amount of
    work."""
    n = cls["docs"]
    if "ops" in cls:
        return [int(cls["ops"])] * n
    lo, hi = cls["chars"]
    sizes = [int((lo + (hi - lo) * (i + 0.5) / n) / CHARS_PER_OP)
             for i in range(n)]
    order = np.random.default_rng([seed, 5, k]).permutation(n)
    return [sizes[i] for i in order]


def doc_rng(seed: int, index: int):
    return np.random.default_rng([seed, index, 17])


def doc_columns(seed: int, index: int, n_ops: int):
    """The document as the program is given it: patch columns."""
    pos, nd, ni, chars, _runs = typing_trace(doc_rng(seed, index), n_ops)
    return pos, nd, ni, chars


def doc_text(seed: int, index: int, n_ops: int) -> bytearray:
    """The document as the reference knows it: the same typing replayed
    on a bytearray."""
    _pos, _nd, _ni, chars, runs = typing_trace(doc_rng(seed, index), n_ops)
    model = bytearray()
    plain_replay(model, chars, runs)
    return model


class PlainDoc:
    """One document of the reference: a region per writer, each with the
    head (remote frontier) its writer types from."""

    def __init__(self, doc_id: str, text: bytearray, writers: int,
                 typists=None) -> None:
        self.id = doc_id
        self.typists = typists           # a `Typist` for each writer
        n = len(text)
        cuts = [n * r // writers for r in range(writers + 1)]
        self.regions = [bytearray(text[cuts[r]:cuts[r + 1]])
                        for r in range(writers)]
        # a writer's view is base[:start] + its region + base[end:]
        self.starts = cuts[:-1]
        self.heads = [None] * writers    # remote frontier, set at load
        self.next_writer = 0
        self.touched = False
        self.tainted = False             # a push whose fate is unknown

    def _guards(self, w: int):
        return (1 if w > 0 else 0,
                1 if w < len(self.regions) - 1 else 0)

    def _shifted(self, w: int, make):
        """Ops made against writer `w`'s editable text (its region
        inside the guards), in its own view's coordinates."""
        lo, hi = self._guards(w)
        shift = self.starts[w] + lo
        ops = make(len(self.regions[w]) - lo - hi)
        for op in ops:
            if op["kind"] == "ins":
                op["pos"] += shift
            else:
                op["start"] += shift
                op["end"] += shift
        return ops

    def next_push(self, w: int, n_ops: int):
        """Writer `w`'s next `n_ops` keystrokes."""
        return self._shifted(w, lambda n: self.typists[w].next_push(n, n_ops))

    def scatter(self, rng, w: int, n_ops: int):
        return self._shifted(w, lambda n: scatter(rng, n, n_ops))

    def acknowledge(self, w: int, ops, version) -> None:
        apply_plain(self.regions[w], ops, shift=self.starts[w])
        self.heads[w] = version
        self.touched = True

    def text(self) -> bytes:
        return b"".join(self.regions)
