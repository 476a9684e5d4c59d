"""Device zone execution — origin extraction ON the accelerator.

Lowers listmerge/zone_np.py's per-entry merge algorithm to ONE `lax.scan`
over a packed step tape. This is the round-3 flagship (VERDICT r2 missing
#1): the host's only jobs are plan compilation (plan2), entry composition
(compose.py — a piece-table pass over the op table) and text-pool
assembly; the device resolves every origin, places every concurrent
block with the YjsMod integrate rule, evolves the per-index state matrix,
and assembles the final document order. No M1/tracker transform runs
anywhere in this path (reference being replaced: the per-op origin scan +
integrate of src/listmerge/merge.rs:154-423).

Tape steps (all shapes static; scan body compiled once per size bucket):
  OP_BEGIN row        state[row] <- base visibility (prefix chars)
  OP_FORK  src dst    state[dst] <- state[src]
  OP_MAX   dst src    state[dst] <- max(state[dst], state[src])
  OP_APPLY row        one SUB-STEP of an entry: up to MB blocks, MC chars,
                      MD delete atoms. The first sub-step of each entry
                      snapshots the row (resolution must not see the
                      entry's own writes; compose coords are entry-start).

Per APPLY sub-step, fully vectorized over the W char slots:
  * visibility prefix-sum over the current order (one cumsum)
  * per block: cursor coord -> (a = rank of origin-left, b = rank of
    origin-right = first non-NotInsertedYet after a)
  * per block: the rank-space YjsMod integrate (top-row break / bottom-row
    skip / same-gap right-origin comparison with the scanning-rollback
    rule, merge.rs:154-278) as masked reductions — no data-dependent
    control flow
  * combined rank bump + order rescatter + state/metadata writes

Blocks larger than MC chars continue in later sub-steps as CONTINUATION
blocks (cursor == -2): their target is directly after the previous
chunk's last char, and their origin-right re-resolves to the same B (the
first snapshot-non-NIY after the gap — own chars are NIY in the snapshot).
"""

from __future__ import annotations

import os

from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..listmerge.compose import K_OWN
from ..listmerge.plan2 import APPLY, BEGIN, DROP, FORK, MAX
from ..listmerge.zone_np import ZonePrep, _slot_of, prepare_zone
from .merge_kernel import _pow2

OP_BEGIN, OP_FORK, OP_MAX, OP_APPLY = 0, 1, 2, 3

BIG32 = np.int32(1 << 30)


@dataclass
class ZoneTape:
    """Packed device tape + host-prepared pools for one document."""
    # per step
    op: np.ndarray         # [T] i32
    arg_a: np.ndarray      # [T] i32 (row / src)
    arg_b: np.ndarray      # [T] i32 (dst)
    snap_flag: np.ndarray  # [T] i32 1 = copy row -> snapshot first
    # per step x block
    blk_cursor: np.ndarray  # [T,MB] i32 coord; -1 pad; -2 continuation
    blk_prev: np.ndarray    # [T,MB] i32 continuation: append after slot
    blk_root: np.ndarray    # [T,MB] i32 root char slot (keys)
    blk_start: np.ndarray   # [T,MB] i32 first char index in this step
    blk_len: np.ndarray     # [T,MB] i32 char count (0 pad)
    # per step x char
    ch_slot: np.ndarray     # [T,MC] i32 (-1 pad)
    ch_ol_static: np.ndarray   # [T,MC] i32 slot; -1 doc start; -2 coord
    ch_ol_coord: np.ndarray    # [T,MC] i32 entry-start coord
    ch_orr_own: np.ndarray     # [T,MC] i32 slot or -1 (block B)
    ch_blk: np.ndarray         # [T,MC] i32 block index in step
    ch_agent: np.ndarray       # [T,MC] i32 agent name rank
    ch_seq: np.ndarray         # [T,MC] i32 agent-local seq
    # per step x delete atom
    del_kind: np.ndarray    # [T,MD] i32 -1 pad / 0 coords / 1 slot range
    del_a: np.ndarray       # [T,MD] i32
    del_b: np.ndarray       # [T,MD] i32
    # doc-level
    W: int
    plen: int
    n_idx: int
    pool: np.ndarray        # [W] i32 char codes by slot
    total_steps: int


def _origin_encoding(ch_kind, slots, anchor, c_of):
    """The per-char origin-left encoding — the ONE statement of the rule
    shared by the per-entry and whole-corpus batched column builders:
    interior chars chain to their predecessor slot, K_OWN heads anchor on
    an own slot, query heads (K_LEFTJOIN / K_ROOT) carry a cursor coord
    (-1 = doc start, -2 = resolve the coord at runtime)."""
    is_q = ch_kind >= 2
    ol_static = np.where(
        ch_kind == 0, slots - 1,
        np.where(ch_kind == K_OWN, anchor,
                 np.where(c_of == 0, -1, -2)))
    ol_coord = np.where(is_q & (c_of > 0), c_of, 0)
    return ol_static, ol_coord


def entry_columns(ce, slot_fn, agent_k, seq_k):
    """Per-char tape columns for one composed entry: (slots, ol_static,
    ol_coord, orr_own, ag, sq, root_slots)."""
    slots = slot_fn(ce.ch_lv).astype(np.int64)
    anchor = np.where(ce.ch_anchor >= 0,
                      slot_fn(np.maximum(ce.ch_anchor, 0)), -1)
    orr_own = np.where(ce.ch_orrown >= 0,
                       slot_fn(np.maximum(ce.ch_orrown, 0)), -1)
    root_slots = slot_fn(ce.blk_root_lv)
    qc = np.asarray(ce.q_cursor, dtype=np.int64) \
        if ce.q_cursor else np.zeros(1, np.int64)
    c_of = qc[np.clip(ce.ch_q, 0, None)]
    ol_static, ol_coord = _origin_encoding(np.asarray(ce.ch_kind), slots,
                                           anchor, c_of)
    if callable(agent_k):   # one call yields both key planes
        ag, sq = agent_k(ce.ch_lv)
    else:
        ag = np.asarray(agent_k)[slots]
        sq = np.asarray(seq_k)[slots]
    return slots, ol_static, ol_coord, orr_own, ag, sq, root_slots


def entry_steps(ce, slot_fn, agent_k, seq_k, MB, MC, MD, cur, next_sub,
                cols=None):
    """Append one composed entry's APPLY sub-step contents (blocks, char
    slices, delete atoms) under the shared budgets. `slot_fn` maps insert
    LVs to char slots; `cur` is the current step dict; `next_sub()`
    returns a fresh sub-step. Shared by the whole-document packer below
    and the incremental session packer (zone_session.py). `cols` are
    precomputed entry_columns (the whole-document packer batches them
    across all entries — per-entry numpy-call overhead dominated the
    pack on many-entry corpora)."""
    nc = ce.num_chars()
    if nc:
        if cols is None:
            cols = entry_columns(ce, slot_fn, agent_k, seq_k)
        slots, ol_static, ol_coord, orr_own, ag, sq, root_slots = cols
    for b in range(len(ce.blk_start) if nc else 0):
        lo = int(ce.blk_start[b])
        hi = lo + int(ce.blk_len[b])
        first = True
        pos = lo
        while pos < hi:
            if len(cur["blocks"]) >= MB or cur["n_chars"] >= MC:
                cur = next_sub()
            take = min(hi - pos, MC - cur["n_chars"])
            assert take > 0
            cursor = int(ce.q_cursor[int(ce.blk_root_q[b])]) \
                if first else -2
            cur["blocks"].append((
                cursor, -1 if first else int(slots[pos - 1]),
                int(root_slots[b]), cur["n_chars"], take))
            cur["chars"].append((len(cur["blocks"]) - 1, pos, pos + take,
                                 slots, ol_static, ol_coord, orr_own,
                                 ag, sq))
            cur["n_chars"] += take
            pos += take
            first = False
    for (c0, c1) in ce.del_base:
        if len(cur["dels"]) >= MD:
            cur = next_sub()
        cur["dels"].append((0, int(c0), int(c1)))
    for (lv0, lv1) in ce.del_own:
        if len(cur["dels"]) >= MD:
            cur = next_sub()
        s0 = int(slot_fn(np.asarray([lv0]))[0])
        cur["dels"].append((1, s0, s0 + (lv1 - lv0)))


def _batched_columns(prep):
    """entry_columns for EVERY composed entry in a few whole-corpus numpy
    passes, returned as per-entry views. Equivalent to calling
    entry_columns per entry (pinned by test_zone_kernel's corpora parity)
    but ~an order of magnitude cheaper on many-entry plans."""
    ces = prep.get_composed()
    # Batching trades per-entry numpy-call overhead for whole-corpus
    # concatenation copies: a win on many-small-entry plans (git-style
    # DAGs), a loss on few-huge-entry plans (node_nodecc's 100 entries
    # of ~4k chars) where the copies dominate and the per-entry overhead
    # was negligible. 200 entries is comfortably past the crossover.
    if len(ces) < 200:
        return {}
    cat = np.concatenate
    ch_lv = cat([np.asarray(ce.ch_lv, dtype=np.int64) if ce.num_chars()
                 else np.zeros(0, np.int64) for ce in ces])
    if not len(ch_lv):
        return {}
    as_i64 = lambda a: np.asarray(a, dtype=np.int64)  # noqa: E731
    nchars = [ce.num_chars() for ce in ces]
    z = np.zeros(0, np.int64)
    ch_kind = cat([as_i64(ce.ch_kind) if n else z
                   for ce, n in zip(ces, nchars)])
    ch_anchor = cat([as_i64(ce.ch_anchor) if n else z
                     for ce, n in zip(ces, nchars)])
    ch_orrown = cat([as_i64(ce.ch_orrown) if n else z
                     for ce, n in zip(ces, nchars)])
    # entry-local query ids -> one flat query table via per-entry offsets
    q_lens = [len(ce.q_cursor) for ce in ces]
    q_off = np.cumsum([0] + q_lens[:-1])
    flat_q = cat([as_i64(ce.q_cursor) if q else z
                  for ce, q in zip(ces, q_lens)]) if sum(q_lens) \
        else np.zeros(1, np.int64)
    ch_q = cat([np.where(as_i64(ce.ch_q) >= 0, as_i64(ce.ch_q) + off, -1)
                if n else z
                for ce, n, off in zip(ces, nchars, q_off)])
    from ..listmerge.zone_np import _slot_of
    slots = _slot_of(prep, ch_lv).astype(np.int64)
    anchor = np.where(ch_anchor >= 0,
                      _slot_of(prep, np.maximum(ch_anchor, 0)), -1)
    orr_own = np.where(ch_orrown >= 0,
                       _slot_of(prep, np.maximum(ch_orrown, 0)), -1)
    c_of = flat_q[np.clip(ch_q, 0, None)]
    ol_static, ol_coord = _origin_encoding(ch_kind, slots, anchor, c_of)
    ag = np.asarray(prep.agent_k)[slots]
    sq = np.asarray(prep.seq_k)[slots]
    nb = [len(ce.blk_root_lv) if ce.num_chars() else 0 for ce in ces]
    root_slots = _slot_of(prep, cat(
        [as_i64(ce.blk_root_lv) if n else z for ce, n in zip(ces, nb)])) \
        if sum(nb) else z
    out = {}
    c0 = b0 = 0
    for i, (ce, n, bn) in enumerate(zip(ces, nchars, nb)):
        if n:
            sl = slice(c0, c0 + n)
            out[i] = (slots[sl], ol_static[sl], ol_coord[sl],
                      orr_own[sl], ag[sl], sq[sl],
                      root_slots[b0:b0 + bn])
        c0 += n
        b0 += bn
    return out


def _pack_native(prep: ZonePrep, MB: int, MC: int, MD: int):
    """The C++ tape packer (native/dt_core.cpp dt_zone_pack; VERDICT r4
    #6 — the pure-Python pack was ~280 ms of git-makefile zone prep).
    Array-identical to the Python packer below (pinned by
    tests/test_zone_kernel.py); None when the native library is absent."""
    ctx = prep.native_ctx
    if ctx is None:
        return None
    lib = ctx._lib
    if not hasattr(lib, "dt_zone_pack"):
        return None
    with ctx.mirror_lock:   # the pack and the fetch of its steps: one use
        return _pack_native_held(prep, lib, ctx, MB, MC, MD)


def _pack_native_held(prep: ZonePrep, lib, ctx, MB: int, MC: int,
                      MD: int):
    """`_pack_native` under the mirror's lock."""
    n = len(prep.plan.entries)

    acts = prep.plan.actions
    ak = np.zeros(len(acts), np.int64)
    aa = np.zeros(len(acts), np.int64)
    ab = np.zeros(len(acts), np.int64)
    for i, act in enumerate(acts):
        ak[i] = act[0]
        aa[i] = act[1]
        ab[i] = act[2] if len(act) > 2 else 0
    ins_lv0 = np.ascontiguousarray(prep.ins_lv0, dtype=np.int64)
    ins_cum = np.ascontiguousarray(prep.ins_cum, dtype=np.int64)
    agent_k = np.ascontiguousarray(prep.agent_k, dtype=np.int64)
    seq_k = np.ascontiguousarray(prep.seq_k, dtype=np.int64)

    # fast path: the composer's output is still cached on the ctx from
    # prepare_zone's compose_plan call — pack straight from it, no
    # column round-trip. -2 = cache stale/absent -> marshal below.
    if prep.compose_serial:
        d64 = np.zeros(1, np.int64)
        d32 = np.zeros(1, np.int32)
        du8 = np.zeros(1, np.uint8)
        T = lib.dt_zone_pack(
            ctx._ptr, len(acts), ak, aa, ab, n, d64, d64, d64, du8, d64,
            d32, d64, d32, d64, d32, d32, d64, d64, d64, d64,
            len(ins_lv0), ins_lv0, ins_cum, prep.plen, agent_k, seq_k,
            MB, MC, MD, prep.compose_serial)
        if T >= 0:
            return _pack_fetch(prep, lib, ctx, int(T), MB, MC, MD)
    ces = prep.get_composed()
    as_i64 = lambda a: np.ascontiguousarray(a, dtype=np.int64)  # noqa: E731
    counts = np.zeros(n * 5, dtype=np.int64)
    for k, ce in enumerate(ces):
        counts[k * 5 + 0] = len(ce.q_cursor)
        counts[k * 5 + 1] = ce.num_chars()
        counts[k * 5 + 2] = 0 if ce.blk_start is None else len(ce.blk_start)
        counts[k * 5 + 3] = len(ce.del_base)
        counts[k * 5 + 4] = len(ce.del_own)
    z64 = np.zeros(0, np.int64)
    z32 = np.zeros(0, np.int32)
    zu8 = np.zeros(0, np.uint8)

    def cat(parts, dtype):
        parts = [p for p in parts if len(p)]
        if not parts:
            return np.zeros(1, dtype)
        return np.ascontiguousarray(np.concatenate(parts), dtype=dtype)

    flat_q = cat([as_i64(ce.q_cursor) if ce.q_cursor else z64
                  for ce in ces], np.int64)
    nc = [ce.num_chars() for ce in ces]
    ch_lv = cat([as_i64(ce.ch_lv) if m else z64
                 for ce, m in zip(ces, nc)], np.int64)
    ch_kind = cat([np.asarray(ce.ch_kind, np.uint8) if m else zu8
                   for ce, m in zip(ces, nc)], np.uint8)
    ch_anchor = cat([as_i64(ce.ch_anchor) if m else z64
                     for ce, m in zip(ces, nc)], np.int64)
    ch_q = cat([np.asarray(ce.ch_q, np.int32) if m else z32
                for ce, m in zip(ces, nc)], np.int32)
    ch_orrown = cat([as_i64(ce.ch_orrown) if m else z64
                     for ce, m in zip(ces, nc)], np.int64)
    nb = [int(counts[k * 5 + 2]) for k in range(n)]
    blk_root_q = cat([np.asarray(ce.blk_root_q, np.int32) if m else z32
                      for ce, m in zip(ces, nb)], np.int32)
    blk_root_lv = cat([as_i64(ce.blk_root_lv) if m else z64
                       for ce, m in zip(ces, nb)], np.int64)
    blk_start = cat([np.asarray(ce.blk_start, np.int32) if m else z32
                     for ce, m in zip(ces, nb)], np.int32)
    blk_len = cat([np.asarray(ce.blk_len, np.int32) if m else z32
                   for ce, m in zip(ces, nb)], np.int32)
    db0 = cat([as_i64([a for a, _ in ce.del_base]) for ce in ces], np.int64)
    db1 = cat([as_i64([b for _, b in ce.del_base]) for ce in ces], np.int64)
    do0 = cat([as_i64([a for a, _ in ce.del_own]) for ce in ces], np.int64)
    do1 = cat([as_i64([b for _, b in ce.del_own]) for ce in ces], np.int64)

    T = lib.dt_zone_pack(
        ctx._ptr, len(acts), ak, aa, ab, n, counts, flat_q, ch_lv, ch_kind,
        ch_anchor, ch_q, ch_orrown, blk_root_q, blk_root_lv, blk_start,
        blk_len, db0, db1, do0, do1, len(ins_lv0), ins_lv0, ins_cum,
        prep.plen, agent_k, seq_k, MB, MC, MD, 0)
    if T < 0:
        return None
    return _pack_fetch(prep, lib, ctx, int(T), MB, MC, MD)


def _pack_fetch(prep, lib, ctx, T: int, MB: int, MC: int, MD: int):
    Tp = max(1, int(T))
    # np.empty everywhere: dt_zone_pack_fetch writes every cell, pads
    # included (pad-initializing the ~100 MB tape in numpy was a
    # measurable share of the whole pack)
    out = ZoneTape(
        op=np.empty(Tp, np.int32), arg_a=np.empty(Tp, np.int32),
        arg_b=np.empty(Tp, np.int32), snap_flag=np.empty(Tp, np.int32),
        blk_cursor=np.empty((Tp, MB), np.int32),
        blk_prev=np.empty((Tp, MB), np.int32),
        blk_root=np.empty((Tp, MB), np.int32),
        blk_start=np.empty((Tp, MB), np.int32),
        blk_len=np.empty((Tp, MB), np.int32),
        ch_slot=np.empty((Tp, MC), np.int32),
        ch_ol_static=np.empty((Tp, MC), np.int32),
        ch_ol_coord=np.empty((Tp, MC), np.int32),
        ch_orr_own=np.empty((Tp, MC), np.int32),
        ch_blk=np.empty((Tp, MC), np.int32),
        ch_agent=np.empty((Tp, MC), np.int32),
        ch_seq=np.empty((Tp, MC), np.int32),
        del_kind=np.empty((Tp, MD), np.int32),
        del_a=np.empty((Tp, MD), np.int32),
        del_b=np.empty((Tp, MD), np.int32),
        W=prep.W, plen=prep.plen,
        n_idx=max(1, prep.plan.indexes_used),
        pool=prep.pool.astype(np.int32), total_steps=int(T))
    lib.dt_zone_pack_fetch(
        ctx._ptr, out.op, out.arg_a, out.arg_b, out.snap_flag,
        out.blk_cursor, out.blk_prev, out.blk_root, out.blk_start,
        out.blk_len, out.ch_slot, out.ch_ol_static, out.ch_ol_coord,
        out.ch_orr_own, out.ch_blk, out.ch_agent, out.ch_seq,
        out.del_kind, out.del_a, out.del_b, MB, MC, MD)
    return out


def pack_zone_tape(prep: ZonePrep, max_blocks: int = 8,
                   max_chars: int = 512, max_dels: int = 16) -> ZoneTape:
    """Flatten a prepared zone (plan + composed entries) into the tape."""
    MB, MC, MD = max_blocks, max_chars, max_dels
    if not os.environ.get("DT_TPU_NO_NATIVE"):
        native = _pack_native(prep, MB, MC, MD)
        if native is not None:
            return native
    steps: List[dict] = []
    all_cols = _batched_columns(prep)

    def new_step(op, a=0, b=0, snap=0):
        s = dict(op=op, a=a, b=b, snap=snap,
                 blocks=[], chars=[], dels=[], n_chars=0)
        steps.append(s)
        return s

    composed = prep.get_composed()
    for act in prep.plan.actions:
        kind = act[0]
        if kind == BEGIN:
            new_step(OP_BEGIN, act[1])
        elif kind == FORK:
            new_step(OP_FORK, act[1], act[2])
        elif kind == MAX:
            new_step(OP_MAX, act[2], act[1])   # a=src, b=dst
        elif kind == DROP:
            continue
        elif kind == APPLY:
            ce = composed[act[1]]
            row = act[2]
            cur = new_step(OP_APPLY, row, snap=1)

            def next_sub():
                return new_step(OP_APPLY, row, snap=0)

            def slot_fn(lvs):
                return _slot_of(prep, lvs)

            entry_steps(ce, slot_fn, prep.agent_k, prep.seq_k,
                        MB, MC, MD, cur, next_sub,
                        cols=all_cols.get(act[1]))

    return _fill_tape(steps, prep.W, prep.plen,
                      max(1, prep.plan.indexes_used),
                      prep.pool.astype(np.int32), MB, MC, MD)


def _fill_tape(steps: List[dict], W: int, plen: int, n_idx: int,
               pool: np.ndarray, MB: int, MC: int, MD: int) -> ZoneTape:
    """Materialize packed micro-step dicts into tape arrays (shared by
    the whole-document packer above and zone_session's incremental
    packer)."""
    T = max(1, len(steps))
    out = ZoneTape(
        op=np.zeros(T, np.int32), arg_a=np.zeros(T, np.int32),
        arg_b=np.zeros(T, np.int32), snap_flag=np.zeros(T, np.int32),
        blk_cursor=np.full((T, MB), -1, np.int32),
        blk_prev=np.full((T, MB), -1, np.int32),
        blk_root=np.zeros((T, MB), np.int32),
        blk_start=np.zeros((T, MB), np.int32),
        blk_len=np.zeros((T, MB), np.int32),
        ch_slot=np.full((T, MC), -1, np.int32),
        ch_ol_static=np.full((T, MC), -1, np.int32),
        ch_ol_coord=np.zeros((T, MC), np.int32),
        ch_orr_own=np.full((T, MC), -1, np.int32),
        ch_blk=np.zeros((T, MC), np.int32),
        ch_agent=np.zeros((T, MC), np.int32),
        ch_seq=np.zeros((T, MC), np.int32),
        del_kind=np.full((T, MD), -1, np.int32),
        del_a=np.zeros((T, MD), np.int32),
        del_b=np.zeros((T, MD), np.int32),
        W=W, plen=plen, n_idx=n_idx,
        pool=pool, total_steps=len(steps))
    for t, s in enumerate(steps):
        out.op[t] = s["op"]
        out.arg_a[t] = s["a"]
        out.arg_b[t] = s["b"]
        out.snap_flag[t] = s["snap"]
        for i, (cursor, prev, root, start, length) in \
                enumerate(s["blocks"]):
            out.blk_cursor[t, i] = cursor
            out.blk_prev[t, i] = prev
            out.blk_root[t, i] = root
            out.blk_start[t, i] = start
            out.blk_len[t, i] = length
        w = 0
        for (blk_i, lo, hi, slots, ol_static, ol_coord, orr_own,
             ag, sq) in s["chars"]:
            n = hi - lo
            out.ch_slot[t, w:w + n] = slots[lo:hi]
            out.ch_ol_static[t, w:w + n] = ol_static[lo:hi]
            out.ch_ol_coord[t, w:w + n] = ol_coord[lo:hi]
            out.ch_orr_own[t, w:w + n] = orr_own[lo:hi]
            out.ch_blk[t, w:w + n] = blk_i
            out.ch_agent[t, w:w + n] = ag[lo:hi]
            out.ch_seq[t, w:w + n] = sq[lo:hi]
            w += n
        for i, (k, a, b) in enumerate(s["dels"]):
            out.del_kind[t, i] = k
            out.del_a[t, i] = a
            out.del_b[t, i] = b
    return out


# ---------------------------------------------------------------------------
# device execution
# ---------------------------------------------------------------------------


def make_zone_step(W: int, plen: int, n_idx: int, MB: int, MC: int,
                  MD: int):
    """Build the scan-step function over the zone carry. The carry is
    (state, snap, rank, ord, ol_id, orr_id, ever, m, agent_k, seq_k) —
    agent/seq key planes ride in the carry and are updated from the tape,
    so an incremental caller (zone_session.py) ships only per-char deltas
    per step instead of re-uploading whole key arrays."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    idx_w = jnp.arange(W, dtype=jnp.int32)
    base_row = (idx_w < plen).astype(jnp.uint8)

    def gather_i32(arr, ix, fill):
        return jnp.where(ix >= 0, arr[jnp.clip(ix, 0, W - 1)], fill)

    def apply_step(carry, x):
        (state, snap, rank, ordv, ol_id, orr_id, ever, m,
         agent_k, seq_k) = carry
        # key planes first: the chars placed THIS step are roots/anchors
        # whose keys the integrate scan reads
        ch_ok = x["ch_slot"] >= 0
        key_ix = jnp.where(ch_ok, x["ch_slot"], W)
        agent_k = agent_k.at[key_ix].set(x["ch_agent"], mode="drop")
        seq_k = seq_k.at[key_ix].set(x["ch_seq"], mode="drop")
        row = jnp.clip(x["a"], 0, n_idx - 1)
        st_row = lax.dynamic_index_in_dim(state, row, 0, keepdims=False)
        snap = jnp.where(x["snap"] == 1, st_row, snap)

        placed_r = idx_w < m                      # rank-space mask
        ch_at = ordv                              # [W]: char slot by rank
        s_r = jnp.where(placed_r, snap[jnp.clip(ch_at, 0, W - 1)], 0)
        vis_r = (s_r == 1) & placed_r
        cum = jnp.cumsum(vis_r.astype(jnp.int32))
        nonniy_r = (s_r != 0) & placed_r

        # ---- block anchor resolution (reference: merge.rs:395-423) ----
        def resolve_block(cursor, prev_slot):
            is_cont = cursor == -2
            j = jnp.searchsorted(cum, jnp.maximum(cursor, 1),
                                 side="left").astype(jnp.int32)
            a_from_coord = jnp.where(cursor <= 0, -1, j)
            a_rank = jnp.where(
                is_cont, gather_i32(rank, prev_slot, BIG32), a_from_coord)
            ol_char = jnp.where(
                is_cont | (cursor <= 0), -1,
                ch_at[jnp.clip(a_from_coord, 0, W - 1)])
            cand = jnp.where(nonniy_r & (idx_w > a_rank), idx_w, W)
            b0 = jnp.min(cand)
            orr_char = jnp.where(b0 < m, ch_at[jnp.clip(b0, 0, W - 1)], -1)
            b_rank = jnp.minimum(b0, m)
            return a_rank, ol_char, b_rank, orr_char

        a_b, ol_b, b_b, orr_b = jax.vmap(resolve_block)(
            x["blk_cursor"], x["blk_prev"])

        # ---- YjsMod integrate (reference: merge.rs:154-278) ----
        olw = gather_i32(ol_id, ch_at, -3)
        olr_w = jnp.where(olw == -1, -1, gather_i32(rank, olw, BIG32))
        orw = gather_i32(orr_id, ch_at, -3)
        orr_r_w = jnp.where(orw == -1, BIG32,
                            gather_i32(rank, orw, BIG32))
        agent_w = gather_i32(agent_k, ch_at, 0)
        seq_w = gather_i32(seq_k, ch_at, 0)

        def integrate(a_rank, ol_char, b_rank, orr_char, cursor, root):
            is_cont = cursor == -2
            in_win = (idx_w > a_rank) & (idx_w < b_rank) & placed_r
            agent_c = gather_i32(agent_k, root, 0)
            seq_c = gather_i32(seq_k, root, 0)
            b_eff = jnp.where(orr_char < 0, BIG32, b_rank)

            top_row = in_win & (olr_w < a_rank)
            eq = in_win & (olr_w == a_rank)
            same = eq & (orw == orr_char)
            ins_here = same & ((agent_c < agent_w) |
                               ((agent_c == agent_w) & (seq_c < seq_w)))
            brk = top_row | ins_here
            jstar = jnp.min(jnp.where(brk, idx_w, b_rank))
            before = idx_w < jstar
            set_ev = eq & ~same & (orr_r_w < b_eff) & before
            reset_ev = ((eq & ~same & (orr_r_w >= b_eff)) |
                        (same & ~ins_here)) & before
            last_reset = jnp.max(jnp.where(reset_ev, idx_w, -1))
            streak = jnp.min(jnp.where(set_ev & (idx_w > last_reset),
                                       idx_w, W))
            t = jnp.where(streak < W, streak, jstar)
            return jnp.where(is_cont, a_rank + 1, t)

        t_b = jax.vmap(integrate)(a_b, ol_b, b_b, orr_b,
                                  x["blk_cursor"], x["blk_root"])
        blk_valid = x["blk_len"] > 0
        t_b = jnp.where(blk_valid, t_b, BIG32)
        L_b = jnp.where(blk_valid, x["blk_len"], 0)

        # ---- delete resolution against the snapshot, in rank space ----
        def del_mask(kind, a, b):
            return vis_r & (cum > a) & (cum <= b) & (kind == 0)

        dmask_r = jnp.any(jax.vmap(del_mask)(
            x["del_kind"], x["del_a"], x["del_b"]), axis=0)

        # ---- rank bump + placement (disjoint windows commute) ----
        bump = jnp.sum(
            jnp.where((t_b[:, None] <= rank[None, :]), L_b[:, None], 0),
            axis=0).astype(jnp.int32)
        live = rank < BIG32
        rank = jnp.where(live, rank + bump, rank)
        off_b = jnp.sum(
            jnp.where(t_b[None, :] < t_b[:, None], L_b[None, :], 0),
            axis=1).astype(jnp.int32)
        start_b = t_b + off_b
        ch_valid = x["ch_slot"] >= 0
        intra = jnp.arange(MC, dtype=jnp.int32) - \
            x["blk_start"][x["ch_blk"]]
        new_rank_ch = start_b[x["ch_blk"]] + intra
        # scatter targets: pad chars aim out of bounds and are dropped
        slot_ix = jnp.where(ch_valid, x["ch_slot"], W)
        rank = rank.at[slot_ix].set(new_rank_ch, mode="drop")
        m = m + jnp.sum(ch_valid.astype(jnp.int32))
        live = rank < BIG32
        ordv = jnp.zeros(W, jnp.int32).at[
            jnp.where(live, rank, W)].set(idx_w, mode="drop")

        # ---- origin metadata for the new chars ----
        coordq = jnp.maximum(x["ch_ol_coord"], 1)
        jq = jnp.searchsorted(cum, coordq, side="left").astype(jnp.int32)
        ol_from_coord = jnp.where(
            x["ch_ol_coord"] <= 0, -1, ch_at[jnp.clip(jq, 0, W - 1)])
        ol_ch = jnp.where(x["ch_ol_static"] == -2, ol_from_coord,
                          x["ch_ol_static"])
        orr_ch = jnp.where(x["ch_orr_own"] >= 0, x["ch_orr_own"],
                           orr_b[x["ch_blk"]])
        ol_id = ol_id.at[slot_ix].set(ol_ch, mode="drop")
        orr_id = orr_id.at[slot_ix].set(orr_ch, mode="drop")

        # ---- state writes: inserts + deletes (monotone lattice) ----
        ins_w = jnp.zeros(W, jnp.uint8).at[slot_ix].set(
            jnp.ones(MC, jnp.uint8), mode="drop")
        del_slot_ix = jnp.where(dmask_r, ch_at, W)
        del_w = jnp.zeros(W, jnp.uint8).at[del_slot_ix].set(
            jnp.full(W, 2, jnp.uint8), mode="drop")

        def slot_del(kind, a, b):
            return (kind == 1) & (idx_w >= a) & (idx_w < b)

        own_del = jnp.any(jax.vmap(slot_del)(
            x["del_kind"], x["del_a"], x["del_b"]), axis=0)
        del_w = jnp.maximum(del_w,
                            jnp.where(own_del, 2, 0).astype(jnp.uint8))
        new_row = jnp.maximum(jnp.maximum(st_row, ins_w), del_w)
        state = lax.dynamic_update_index_in_dim(state, new_row, row, 0)
        ever = jnp.maximum(ever, (del_w >= 2).astype(jnp.uint8))
        return (state, snap, rank, ordv, ol_id, orr_id, ever, m,
                agent_k, seq_k), None

    def row_step(carry, x):
        state = carry[0]
        op = x["op"]
        src = lax.dynamic_index_in_dim(
            state, jnp.clip(x["a"], 0, n_idx - 1), 0, keepdims=False)
        dst = lax.dynamic_index_in_dim(
            state, jnp.clip(x["b"], 0, n_idx - 1), 0, keepdims=False)
        new = jnp.where(op == OP_BEGIN, base_row,
                        jnp.where(op == OP_FORK, src,
                                  jnp.maximum(dst, src)))
        target = jnp.where(op == OP_BEGIN, x["a"], x["b"])
        state = lax.dynamic_update_index_in_dim(
            state, new, jnp.clip(target, 0, n_idx - 1), 0)
        return (state,) + tuple(carry[1:]), None

    def step(carry, x):
        return lax.cond(x["op"] == OP_APPLY, apply_step, row_step,
                        carry, x)

    return step


def init_zone_carry(W: int, plen: int, n_idx: int, agent_k, seq_k):
    """Fresh carry for a zone execution (prefix chars pre-placed)."""
    import jax.numpy as jnp
    idx_w = jnp.arange(W, dtype=jnp.int32)
    return (jnp.zeros((n_idx, W), jnp.uint8),          # state matrix
            jnp.zeros(W, jnp.uint8),                   # entry snapshot
            jnp.where(idx_w < plen, idx_w, BIG32),     # rank
            idx_w,                                     # ord
            jnp.where(idx_w < plen, idx_w - 1, -2),    # ol_id
            jnp.full(W, -1, jnp.int32),                # orr_id
            jnp.zeros(W, jnp.uint8),                   # ever
            jnp.int32(plen),                           # m
            jnp.asarray(agent_k, jnp.int32),
            jnp.asarray(seq_k, jnp.int32))


def _run_zone(xs, agent_k, seq_k, W: int, plen: int, n_idx: int, MB: int,
              MC: int, MD: int):
    """Jitted whole-tape execution: one lax.scan, returns (rank, ever)."""
    from jax import lax

    step = make_zone_step(W, plen, n_idx, MB, MC, MD)
    carry = init_zone_carry(W, plen, n_idx, agent_k, seq_k)
    final, _ = lax.scan(step, carry, xs)
    return final[2], final[6]


_zone_jit_cache = {}


def execute_zone_jax(tape: ZoneTape, agent_k: np.ndarray,
                     seq_k: np.ndarray):
    """Run the tape; returns (rank, ever) as numpy [W] arrays."""
    import jax
    import jax.numpy as jnp

    W, plen, n_idx = tape.W, tape.plen, tape.n_idx
    T = tape.op.shape[0]
    MB, MC, MD = (tape.blk_cursor.shape[1], tape.ch_slot.shape[1],
                  tape.del_kind.shape[1])
    key = (W, plen, n_idx, _pow2(T), MB, MC, MD)
    fn = _zone_jit_cache.get(key)
    if fn is None:
        fn = jax.jit(partial(_run_zone, W=W, plen=plen, n_idx=n_idx,
                             MB=MB, MC=MC, MD=MD))
        _zone_jit_cache[key] = fn

    xs = {k: jnp.asarray(v) for k, v in _pad_tape_xs(tape).items()}
    rank, ever = fn(xs, jnp.asarray(agent_k.astype(np.int32)),
                    jnp.asarray(seq_k.astype(np.int32)))
    return np.asarray(rank), np.asarray(ever)


_zone_batch_jit_cache = {}


def execute_zone_batch_jax(tape: ZoneTape, agent_k: np.ndarray,
                           seq_k: np.ndarray, batch: int,
                           replica_sharding=None, xs=None):
    """Batched replica execution: ONE shared tape, `batch` independent
    state evolutions (the many-docs-per-chip deployment shape — BASELINE
    config 4). seq keys are materialized per replica so every row is a
    real computation, not a broadcast the compiler can collapse.
    `replica_sharding` (a jax.sharding.NamedSharding over the replica
    axis) spreads the batch over a device mesh; jit partitions the whole
    evolution from the input placement.
    Returns (rank [B, W], ever [B, W]) as numpy arrays."""
    import jax
    import jax.numpy as jnp

    W, plen, n_idx = tape.W, tape.plen, tape.n_idx
    T = tape.op.shape[0]
    MB, MC, MD = (tape.blk_cursor.shape[1], tape.ch_slot.shape[1],
                  tape.del_kind.shape[1])
    key = (W, plen, n_idx, _pow2(T), MB, MC, MD, batch)
    fn = _zone_batch_jit_cache.get(key)
    if fn is None:
        inner = partial(_run_zone, W=W, plen=plen, n_idx=n_idx,
                        MB=MB, MC=MC, MD=MD)
        fn = jax.jit(jax.vmap(inner, in_axes=(None, None, 0)))
        _zone_batch_jit_cache[key] = fn
    if xs is None:
        xs = _pad_tape_xs(tape)
        xs = {k: jnp.asarray(v) for k, v in xs.items()}
    seq_b = jnp.asarray(
        np.broadcast_to(seq_k.astype(np.int32), (batch, W)).copy())
    if replica_sharding is not None:
        seq_b = jax.device_put(seq_b, replica_sharding)
    rank, ever = fn(xs, jnp.asarray(agent_k.astype(np.int32)), seq_b)
    return rank, ever   # DEVICE arrays: callers np.asarray (or slice) them


def _run_zone_slice(carry, xs, W: int, plen: int, n_idx: int, MB: int,
                    MC: int, MD: int):
    """One bounded-length scan segment: carry in, carry out."""
    from jax import lax

    step = make_zone_step(W, plen, n_idx, MB, MC, MD)
    final, _ = lax.scan(step, carry, xs)
    return final


def slice_tape_xs(tape: ZoneTape, slice_steps: int):
    """Cut the padded tape into device-resident scan segments of length
    `slice_steps` (pad steps are self-FORK no-ops, so over-padding the
    last segment is safe). Returns (S, [xs dicts on device])."""
    import jax.numpy as jnp

    if int(slice_steps) <= 0:
        raise ValueError(f"slice_steps must be positive, got {slice_steps}"
                         " (use the whole-tape executor to disable slicing)")
    T = tape.op.shape[0]
    S = min(int(slice_steps), _pow2(T))
    n_sl = max(1, -(-T // S))
    xs_np = _pad_tape_xs(tape, target=n_sl * S)
    return S, [{k: jnp.asarray(v[i * S:(i + 1) * S])
                for k, v in xs_np.items()} for i in range(n_sl)]


# Per-dispatch device-time budget for the sliced executor, in
# step-replica-width units (scan_steps x batch x W). Calibrated on
# 2026-07-31 against a v5e runtime that killed any single program past
# a ~60 s device-time bound ("TPU worker process crashed or
# restarted"); friendsforever at batch 8 (W 23,719) measured ~33M
# units/s there, so 3.3e8 units ~= 10 s/dispatch — a 6x margin under
# that bound. Whether a per-program bound exists on this machine is
# not measured here; the slicing stays until it is.
_SLICE_BUDGET_UNITS = 3.3e8


def auto_slice_steps(tape: "ZoneTape", batch: int) -> int:
    """Slice length that bounds one dispatch's device time: scan steps
    per dispatch shrink as the replica batch or the zone width W grow
    (per-step cost is ~linear in both — every step does W-wide vector
    updates per replica)."""
    units_per_step = max(1, int(batch)) * max(1, int(tape.W))
    steps = int(_SLICE_BUDGET_UNITS // units_per_step)
    # the budget takes precedence over the floor: a floor-clamped
    # dispatch at flagship width (git-makefile W ~560k, batch 8) was
    # measured at ~35 s with a 256 floor — inside 2x of the runtime's
    # kill bound. 64 steps keeps the worst honored shape near the
    # budget; dispatch-count growth is cheap (async enqueue, one
    # compile for all slices).
    return max(64, min(32768, steps))


_zone_slice_jit_cache = {}


def execute_zone_batch_sliced_jax(tape: ZoneTape, agent_k: np.ndarray,
                                  seq_k: np.ndarray, batch: int,
                                  slice_steps: int = 32768,
                                  xs_slices=None):
    """execute_zone_batch_jax semantics with the whole-tape scan split
    into bounded-length dispatches (carry stays device-resident between
    calls, so the only extra cost is per-slice dispatch).

    Motivation (the one on-chip session this executor has seen,
    2026-07-31): the single whole-tape scan — 524k scan steps on
    git-makefile — reproducibly killed the TPU worker of that v5e
    runtime (\"TPU worker process crashed or restarted ... kernel
    fault\") on every corpus, while short-program benches on the same
    chip ran clean. Bounding device time per dispatch keeps each
    program inside whatever execution budget a runtime enforces, and
    lets other work interleave at slice boundaries instead of queueing
    behind a minutes-long program. Whether this machine enforces such
    a budget is not measured here. Returns (rank [B, W], ever [B, W])
    as DEVICE arrays, like the whole-tape batch executor."""
    import jax
    import jax.numpy as jnp

    W, plen, n_idx = tape.W, tape.plen, tape.n_idx
    MB, MC, MD = (tape.blk_cursor.shape[1], tape.ch_slot.shape[1],
                  tape.del_kind.shape[1])
    if xs_slices is None:
        S, xs_slices = slice_tape_xs(tape, slice_steps)
    else:
        S = int(xs_slices[0]["op"].shape[0])
    key = (W, plen, n_idx, S, MB, MC, MD, batch)
    fns = _zone_slice_jit_cache.get(key)
    if fns is None:
        inner = partial(_run_zone_slice, W=W, plen=plen, n_idx=n_idx,
                        MB=MB, MC=MC, MD=MD)
        # donate the dead previous carry (zone_session._micro_fn
        # pattern): each slice updates the batched state in place
        # instead of doubling peak device memory per dispatch
        fn = jax.jit(jax.vmap(inner, in_axes=(0, None)),
                     donate_argnums=0)
        init = jax.jit(jax.vmap(
            partial(init_zone_carry, W, plen, n_idx), in_axes=(None, 0)))
        fns = (fn, init)
        _zone_slice_jit_cache[key] = fns
    fn, init = fns
    agent_j = jnp.asarray(agent_k.astype(np.int32))
    seq_b = jnp.asarray(
        np.broadcast_to(seq_k.astype(np.int32), (batch, W)).copy())
    carry = init(agent_j, seq_b)
    for xs in xs_slices:
        carry = fn(carry, xs)
    return carry[2], carry[6]


def _pad_tape_xs(tape: ZoneTape, target: Optional[int] = None) -> dict:
    T = tape.op.shape[0]
    Tp = _pow2(T) if target is None else int(target)
    assert Tp >= T

    def pad_t(a, fill=0):
        out = np.full((Tp,) + a.shape[1:], fill, a.dtype)
        out[:T] = a
        return out

    return dict(
        # pad steps are self-FORKs (state[0] <- state[0]): a padded
        # OP_BEGIN would reset row 0 to the base prefix and clobber any
        # pinned session row held there
        op=pad_t(tape.op, OP_FORK), a=pad_t(tape.arg_a),
        b=pad_t(tape.arg_b), snap=pad_t(tape.snap_flag),
        blk_cursor=pad_t(tape.blk_cursor, -1),
        blk_prev=pad_t(tape.blk_prev, -1), blk_root=pad_t(tape.blk_root),
        blk_start=pad_t(tape.blk_start), blk_len=pad_t(tape.blk_len),
        ch_slot=pad_t(tape.ch_slot, -1),
        ch_ol_static=pad_t(tape.ch_ol_static, -1),
        ch_ol_coord=pad_t(tape.ch_ol_coord),
        ch_orr_own=pad_t(tape.ch_orr_own, -1), ch_blk=pad_t(tape.ch_blk),
        ch_agent=pad_t(tape.ch_agent), ch_seq=pad_t(tape.ch_seq),
        del_kind=pad_t(tape.del_kind, -1), del_a=pad_t(tape.del_a),
        del_b=pad_t(tape.del_b))


def zone_checkout_device(oplog, from_frontier: Sequence[int] = (),
                         merge_frontier: Optional[Sequence[int]] = None,
                         prep: Optional[ZonePrep] = None,
                         tape: Optional[ZoneTape] = None):
    """Full device checkout/merge via the zone kernel. Returns
    (text, frontier). FULL runs (prep and tape computed here) record
    their throughput into the engine policy (listmerge/policy.py) — this
    is how the policy's zone rate bootstraps; callers passing precomputed
    prep/tape are NOT recorded (an execute-only rate would flatter the
    engine by the dominant compose/pack cost it skipped)."""
    import time as _time
    t0 = _time.perf_counter()
    # Record throughput into the engine policy only for FULL runs (prep
    # and tape computed here): a caller passing precomputed prep/tape
    # would otherwise feed an execute-only rate — minus the dominant
    # compose/pack cost — into merge-engine selection.
    full_run = prep is None and tape is None
    if prep is None:
        # fetch_composed=False: the native pack reads the composer's
        # output in the ctx cache; the Python-side entry columns are
        # only materialized if a fallback needs them (get_composed)
        prep = prepare_zone(oplog, from_frontier, merge_frontier,
                            fetch_composed=False)
    if not prep.plan.entries:
        txt = prep.prefix
    else:
        if tape is None:
            tape = pack_zone_tape(prep)
        rank, ever = execute_zone_jax(tape, prep.agent_k, prep.seq_k)
        order = np.argsort(rank, kind="stable")[:_count_live(rank)]
        vis = ever[order] == 0
        txt = prep.pool[order[vis]].astype(np.int32).tobytes() \
            .decode("utf-32-le")
    if full_run:
        from ..listmerge import policy as _policy
        n_before = max((int(x) for x in from_frontier), default=-1) + 1
        n_after = max((int(x) for x in prep.plan.final_frontier),
                      default=-1) + 1
        _policy.GLOBAL.record(_policy.ZONE, n_after - n_before,
                              _time.perf_counter() - t0)
    return txt, list(prep.plan.final_frontier)


def _count_live(rank: np.ndarray) -> int:
    return int((rank < int(BIG32)).sum())
