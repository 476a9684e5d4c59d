"""Device execution of fork/join merge plans (listmerge2 on TPU).

Lowers the dense state-matrix executor (listmerge/dense.py) to JAX: the
whole fork/join schedule — Begin/Fork/Max column ops plus every Apply's
journaled state writes — runs as ONE `lax.scan` over a flat step tape,
evolving the dense [n_slots, n_indexes] state matrix on device and
snapshotting requested version rows along the way.

Two device capabilities fall out of the state rows:

  * **Batched time travel** — `texts_at_versions` materializes the document
    at MANY historical versions in one vmapped device call (the reference
    can only `checkout(version)` one at a time, rebuilding a tracker per
    call — src/list/oplog.rs:32). A version's document is just
    "final order, filtered to row==1" — the CRDT convergence property
    makes every historical doc a mask over one shared linearization.
  * **Batched origin resolution** — `origin_query_jax` answers the
    position->-(origin_left, origin_right) queries of YjsMod integrate
    (reference: merge.rs:395-423) for whole batches of concurrent inserts
    with two prefix-sums and a suffix scan, replacing the M1 engine's
    per-op tree walks for wide fan-in zones (the 10k-replica north star),
    where every branch's first run queries its parent-version row.

The step tape is int32-only: slots are addressed by their rank in id-sorted
order (underwater ids are >= 1<<62 and stay host-side). Journal writes are
item-id RANGES captured at write time, which makes split inheritance
disappear: a later split only refines slots inside an already-written
range, and states are monotone (this engine never retreats), so range-max
replay over the FINAL slot table reproduces every intermediate row exactly.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..core.span import UNDERWATER_START
from ..listmerge.dense import DenseExecutor
from ..listmerge.plan2 import (APPLY, BEGIN, DROP, FORK, MAX, MergePlan2,
                               compile_plan2)
from .merge_kernel import _pow2

# Tape opcodes.
T_WRITE = 0   # a=slot_lo, b=slot_hi (id-sorted ranks), c=state, d=row
T_BEGIN = 1   # a=idx
T_FORK = 2    # a=src, b=dest
T_MAX = 3     # a=dest, b=src
T_SNAP = 4    # a=row, b=snapshot slot in the output buffer


@dataclass
class PackedTape:
    op: np.ndarray        # [T] int32
    a: np.ndarray         # [T] int32
    b: np.ndarray         # [T] int32
    c: np.ndarray         # [T] int32
    d: np.ndarray         # [T] int32
    n_slots: int
    n_idx: int
    n_snaps: int
    is_base: np.ndarray   # [n_slots] uint8, id-sorted
    sorted_ids: np.ndarray    # [n_slots] int64 slot id-range starts
    sorted_lens: np.ndarray   # [n_slots] int64 slot lengths
    perm: np.ndarray      # [n_slots] int32: document order -> sorted rank
    snap_entries: List[int]   # entry index per snapshot slot


@dataclass
class TapeSource:
    """Slot table + write journal a tape can be packed from. Two builders:
    `source_from_executor` (the Python dense executor's own tables) and
    `source_native` (C++ tracker dump + delete-target rows — no Python
    execution of the zone at all)."""
    ids: np.ndarray       # [n_slots] int64 item-id range starts
    lens: np.ndarray      # [n_slots] int64
    is_base: np.ndarray   # [n_slots] uint8 (pre-zone / underwater slots)
    order: np.ndarray     # [n_slots] doc-order permutation into the above
    n_idx: int
    journal: list         # per-APPLY list of (id_lo, id_hi, state) writes


def source_from_executor(ex: DenseExecutor) -> TapeSource:
    assert ex.journal is not None, "executor must be run with journal=True"
    n = len(ex.slots)
    return TapeSource(
        ids=np.array([s.ids for s in ex.slots], dtype=np.int64),
        lens=np.array([len(s) for s in ex.slots], dtype=np.int64),
        is_base=np.asarray(ex.is_base[:n], dtype=np.uint8),
        order=np.asarray(ex.order, dtype=np.int64),
        n_idx=ex.n_idx, journal=ex.journal)


def source_native(oplog, plan: MergePlan2, from_frontier,
                  merge_frontier) -> TapeSource:
    """Build the tape source from the C++ engine: one native transform
    gives the final item table (document order) and the delete-target rows;
    the journal is derived from the op table (inserts) and those rows
    (deletes) — delete targets are intrinsic to each op, so the M1-walk-
    recorded rows are valid for the fork/join schedule too. The native
    items are RLE-merged, so they are split at every journal-write
    boundary to restore the alignment pack_plan_tape asserts."""
    from ..listmerge.dense import DELETED, INSERTED
    from ..native.core import get_native_ctx
    from ..text.op import INS

    ctx = get_native_ctx(oplog)
    with ctx.mirror_lock:   # the transform and the dumps of its tracker
        ctx.transform([int(x) for x in from_frontier],
                      [int(x) for x in merge_frontier])
        common = ctx.zone_common()
        assert sorted(common) == sorted(plan.common), \
            "native transform and plan disagree on the conflict zone"
        ids, lens, *_rest = ctx.dump_tracker(keep_underwater=True)
        lv0, lv1, t0, t1, fwd = ctx.dump_del_rows()
        ctx.release_tracker()

    journal = []
    bounds = set()
    for en in plan.entries:
        writes = []
        for piece in oplog.ops.iter_range(en.span):
            if piece.kind == INS:
                writes.append((piece.lv, piece.lv + len(piece), INSERTED))
            else:
                a, b = piece.lv, piece.lv + len(piece)
                j = int(np.searchsorted(lv0, a, side="right")) - 1
                while a < b:
                    assert 0 <= j < len(lv0) and lv0[j] <= a < lv1[j], \
                        "delete op not covered by native del rows"
                    e = min(b, int(lv1[j]))
                    if fwd[j]:
                        tr = (int(t0[j]) + (a - int(lv0[j])),
                              int(t0[j]) + (e - int(lv0[j])))
                    else:
                        tr = (int(t1[j]) - (e - int(lv0[j])),
                              int(t1[j]) - (a - int(lv0[j])))
                    writes.append((tr[0], tr[1], DELETED))
                    a = e
                    j += 1
        for (lo, hi, _s) in writes:
            bounds.add(lo)
            bounds.add(hi)
        journal.append(writes)

    # Split the RLE-merged native items at write boundaries (doc order is
    # preserved: splits are adjacent).
    bs = np.array(sorted(bounds), dtype=np.int64)
    out_ids, out_lens = [], []
    for i in range(len(ids)):
        s, e = int(ids[i]), int(ids[i] + lens[i])
        lo = int(np.searchsorted(bs, s, side="right"))
        hi = int(np.searchsorted(bs, e, side="left"))
        prev = s
        for cut in bs[lo:hi]:
            out_ids.append(prev)
            out_lens.append(int(cut) - prev)
            prev = int(cut)
        out_ids.append(prev)
        out_lens.append(e - prev)
    oids = np.array(out_ids, dtype=np.int64)
    olens = np.array(out_lens, dtype=np.int64)
    return TapeSource(
        ids=oids, lens=olens,
        is_base=(oids >= UNDERWATER_START).astype(np.uint8),
        order=np.arange(len(oids), dtype=np.int64),
        n_idx=max(1, plan.indexes_used), journal=journal)


def pack_plan_tape(plan: MergePlan2, src, snapshot_entries: Sequence[int]
                   ) -> PackedTape:
    """Flatten a fork/join plan + a write journal into a device step tape.
    `src` is a TapeSource or a journal=True DenseExecutor."""
    if isinstance(src, DenseExecutor):
        src = source_from_executor(src)
    for e in snapshot_entries:
        if not 0 <= int(e) < len(plan.entries):
            raise IndexError(
                f"snapshot entry {e} out of range: plan has "
                f"{len(plan.entries)} conflict entries (a pure fast-forward "
                f"history has none — use oplog.checkout for those versions)")
    n_slots = len(src.ids)
    ids = src.ids
    lens = src.lens
    rank_order = np.argsort(ids, kind="stable")
    sorted_ids = ids[rank_order]
    sorted_lens = lens[rank_order]
    rank_of = np.empty(n_slots, dtype=np.int64)
    rank_of[rank_order] = np.arange(n_slots)
    ends = sorted_ids + sorted_lens

    def rank_range(lo: int, hi: int) -> Tuple[int, int]:
        a = int(np.searchsorted(sorted_ids, lo))
        b = int(np.searchsorted(sorted_ids, hi))
        assert a < b and sorted_ids[a] == lo and ends[b - 1] == hi, \
            "journal range not aligned to final slot boundaries"
        return a, b

    want = {int(e): i for i, e in enumerate(snapshot_entries)}
    op, aa, bb, cc, dd = [], [], [], [], []

    def emit(o, a=0, b=0, c=0, d=0):
        op.append(o); aa.append(a); bb.append(b); cc.append(c); dd.append(d)

    apply_i = 0
    for act in plan.actions:
        kind = act[0]
        if kind == BEGIN:
            emit(T_BEGIN, act[1])
        elif kind == FORK:
            emit(T_FORK, act[1], act[2])
        elif kind == MAX:
            emit(T_MAX, act[1], act[2])
        elif kind == DROP:
            pass
        elif kind == APPLY:
            for (lo, hi, state) in src.journal[apply_i]:
                ra, rb = rank_range(lo, hi)
                emit(T_WRITE, ra, rb, state, act[2])
            if act[1] in want:
                emit(T_SNAP, act[2], want[act[1]])
            apply_i += 1

    is_base = np.asarray(src.is_base, dtype=np.uint8)[rank_order]
    perm = rank_of[np.asarray(src.order, dtype=np.int64)].astype(np.int32)
    return PackedTape(
        op=np.array(op, dtype=np.int32), a=np.array(aa, dtype=np.int32),
        b=np.array(bb, dtype=np.int32), c=np.array(cc, dtype=np.int32),
        d=np.array(dd, dtype=np.int32), n_slots=n_slots, n_idx=src.n_idx,
        n_snaps=len(snapshot_entries), is_base=is_base,
        sorted_ids=sorted_ids, sorted_lens=sorted_lens, perm=perm,
        snap_entries=[int(e) for e in snapshot_entries])


_tape_jit_cache = {}
_materialize_jit_cache = {}


def execute_tape_jax(op, a, b, c, d, is_base, n_slots: int, n_idx: int,
                     n_snaps: int):
    """Run the packed schedule on device: one lax.scan over tape steps.
    Returns the snapshot rows [n_snaps, n_slots] uint8.

    All shapes are padded to powers of two so the compiled-executable cache
    stays O(log max_size) with real reuse across merges (same bucketing
    pattern as merge_kernel._jitted_kernel). Padding tape steps are WRITEs
    with an empty slot range; padding slots are never written and padding
    snapshot rows are sliced off before returning."""
    import jax

    ns, ni = _pow2(n_slots), _pow2(n_idx)
    nq = _pow2(max(n_snaps, 1))
    T = _pow2(max(len(op), 1))
    key = (ns, ni, nq, T)
    fn = _tape_jit_cache.get(key)
    if fn is None:
        fn = jax.jit(partial(_execute_tape, n_slots=ns, n_idx=ni,
                             n_snaps=nq))
        _tape_jit_cache[key] = fn

    def pad(x, n, fill=0):
        x = np.asarray(x)
        out = np.full(n, fill, dtype=x.dtype)
        out[:len(x)] = x
        return out

    rows = fn(pad(op, T, T_WRITE), pad(a, T), pad(b, T), pad(c, T),
              pad(d, T), pad(is_base, ns))
    return rows[:n_snaps, :n_slots]


def _execute_tape(op, a, b, c, d, is_base, n_slots: int, n_idx: int,
                  n_snaps: int):
    import jax.numpy as jnp
    from jax import lax

    S0 = jnp.zeros((n_idx, n_slots), dtype=jnp.uint8)
    rows0 = jnp.zeros((max(n_snaps, 1), n_slots), dtype=jnp.uint8)
    base_row = jnp.asarray(is_base, dtype=jnp.uint8)
    slot_ix = jnp.arange(n_slots, dtype=jnp.int32)

    def write(S, rows, t):
        _o, lo, hi, state, row = t
        mask = (slot_ix >= lo) & (slot_ix < hi)
        col = lax.dynamic_index_in_dim(S, row, 0, keepdims=False)
        col = jnp.maximum(col, jnp.where(mask, state, 0).astype(jnp.uint8))
        return lax.dynamic_update_index_in_dim(S, col, row, 0), rows

    def begin(S, rows, t):
        return lax.dynamic_update_index_in_dim(S, base_row, t[1], 0), rows

    def fork(S, rows, t):
        col = lax.dynamic_index_in_dim(S, t[1], 0, keepdims=False)
        return lax.dynamic_update_index_in_dim(S, col, t[2], 0), rows

    def fmax(S, rows, t):
        dst = lax.dynamic_index_in_dim(S, t[1], 0, keepdims=False)
        src = lax.dynamic_index_in_dim(S, t[2], 0, keepdims=False)
        return lax.dynamic_update_index_in_dim(
            S, jnp.maximum(dst, src), t[1], 0), rows

    def snap(S, rows, t):
        col = lax.dynamic_index_in_dim(S, t[1], 0, keepdims=False)
        return S, lax.dynamic_update_index_in_dim(rows, col, t[2], 0)

    def step(carry, t):
        S, rows = carry
        S, rows = lax.switch(t[0], [
            lambda args: write(*args),
            lambda args: begin(*args),
            lambda args: fork(*args),
            lambda args: fmax(*args),
            lambda args: snap(*args),
        ], (S, rows, t))
        return (S, rows), None

    tape = jnp.stack([jnp.asarray(x, dtype=jnp.int32)
                      for x in (op, a, b, c, d)], axis=1)
    (_S, rows), _ = lax.scan(step, (S0, rows0), tape)
    return rows


def snapshot_rows(oplog, from_frontier: Sequence[int],
                  merge_frontier: Optional[Sequence[int]] = None,
                  entries: Optional[Sequence[int]] = None,
                  source: str = "python"):
    """Compile + journal (host) + device-replay a merge, returning
    (plan, source, tape, rows) where rows[i] is the device-computed state
    row at snapshot entry i's version.

    source="python" runs the dense executor for the journal (also yields
    slot origins — the origin-query tests use them); source="native" gets
    the journal from one C++ transform + the delete-target rows — no
    Python zone execution, fast enough for the shipped corpora."""
    merge = list(oplog.version) if merge_frontier is None \
        else list(merge_frontier)
    plan = compile_plan2(oplog.cg.graph, list(from_frontier), merge)
    if source == "native":
        ex = source_native(oplog, plan, list(from_frontier), merge)
    elif source == "python":
        ex = DenseExecutor(plan, oplog.cg.agent_assignment, oplog.ops,
                           journal=True)
        for _ in ex.run():
            pass
    else:
        raise ValueError(f"unknown source {source!r}: use 'python' or "
                         f"'native'")
    if entries is None:
        entries = range(len(plan.entries))
    tape = pack_plan_tape(plan, ex, list(entries))
    rows = np.asarray(execute_tape_jax(
        tape.op, tape.a, tape.b, tape.c, tape.d, tape.is_base,
        n_slots=tape.n_slots, n_idx=tape.n_idx, n_snaps=tape.n_snaps))
    return plan, ex, tape, rows


def entry_frontier(graph, plan: MergePlan2, k: int) -> List[int]:
    """The version frontier reached by entry k: zone common ancestor plus
    every in-zone ancestor entry plus k itself."""
    tips = list(plan.common)
    seen = set()
    stack = [k]
    while stack:
        e = stack.pop()
        if e in seen:
            continue
        seen.add(e)
        tips.append(plan.entries[e].span[1] - 1)
        stack.extend(plan.entries[e].parents)
    return list(graph.find_dominators(tips))


# ---- batched time travel -------------------------------------------------

def texts_at_versions(oplog, entries: Sequence[int],
                      from_frontier: Sequence[int] = (),
                      source: str = "python",
                      merge_frontier: Optional[Sequence[int]] = None,
                      version_sharding=None) -> List[str]:
    """Materialize the document at many historical versions (one per
    snapshot entry) in a single vmapped device call.

    Reference equivalent: N separate `oplog.checkout(version)` calls, each
    a full tracker replay (src/list/oplog.rs:32). Here one device tape
    replay yields every version's state row, and one batched materialize
    gathers each document as a visibility mask over the shared final-order
    linearization. `version_sharding` (a jax.sharding.NamedSharding over
    the snapshot axis) spreads the materialize batch over a device mesh
    (the version axis is padded up to the mesh when needed)."""
    import jax
    import jax.numpy as jnp

    from ..text.op import INS
    from .linearize import materialize_jax
    from .merge_kernel import _arena_offsets

    plan, ex, tape, rows = snapshot_rows(oplog, from_frontier,
                                         merge_frontier=merge_frontier,
                                         entries=entries, source=source)
    base_text = oplog.checkout(plan.common).snapshot()
    plen = len(base_text)

    sid, slen = tape.sorted_ids, tape.sorted_lens
    uw = sid >= UNDERWATER_START
    uw_off = np.where(uw, sid - UNDERWATER_START, 0)
    text_len = np.where(
        uw, np.maximum(0, np.minimum(uw_off + slen, plen) - uw_off),
        slen).astype(np.int32)
    arena_str = oplog.ops._arenas[INS].get((0, oplog.ops.arena_len(INS)))
    arena = np.frombuffer((base_text + arena_str).encode("utf-32-le"),
                          dtype=np.int32)
    char_off = np.where(uw, uw_off,
                        plen + _arena_offsets(
                            oplog, np.where(uw, 0, sid))).astype(np.int32)

    vis = np.where(rows == 1, text_len[None, :], 0).astype(np.int32)
    n_real = vis.shape[0]
    cap = _pow2(max(1, int(vis.sum(axis=1).max())))
    fn = _materialize_jit_cache.get(cap)
    if fn is None:
        fn = jax.jit(jax.vmap(partial(materialize_jax, cap=cap),
                              in_axes=(None, 0, None, None)))
        _materialize_jit_cache[cap] = fn
    vis_dev = jnp.asarray(vis)
    if version_sharding is not None:
        n_mesh = int(np.prod(list(version_sharding.mesh.shape.values())))
        pad = (-n_real) % n_mesh
        if pad:
            vis_dev = jnp.concatenate(
                [vis_dev, jnp.zeros((pad, vis.shape[1]), jnp.int32)])
        vis_dev = jax.device_put(vis_dev, version_sharding)
    texts, totals = fn(jnp.asarray(tape.perm), vis_dev,
                       jnp.asarray(char_off),
                       jnp.asarray(arena if len(arena) else
                                   np.zeros(1, np.int32)))
    texts, totals = np.asarray(texts), np.asarray(totals)
    return [texts[i, :totals[i]].astype(np.int32).tobytes()
            .decode("utf-32-le") for i in range(len(tape.snap_entries))]


# ---- batched origin resolution ------------------------------------------

def origin_query_jax(row_ord, len_ord, positions):
    """Batched YjsMod origin queries against one version row.

    row_ord [n]: the version's slot states in DOCUMENT order (0/1/2).
    len_ord [n]: slot char lengths in document order (underwater clipped
                 to real text so int32 prefix sums cannot overflow).
    positions [q]: insert positions (chars) in the version's visible doc.

    Returns (ol_j, ol_off, orr_j, orr_off): document-order slot index and
    in-slot offset of origin_left (the pos-1'th visible char; ol_j == -1
    for pos == 0 / ROOT) and origin_right (the next char at or after the
    cursor whose slot is NOT NotInsertedYet; orr_j == -1 for end-of-doc) —
    the exact neighbor pair the M1 tracker extracts per insert with a tree
    descent + rightward scan (reference: merge.rs:395-423)."""
    import jax.numpy as jnp

    n = row_ord.shape[0]
    vis_len = jnp.where(row_ord == 1, len_ord, 0)
    cvis = jnp.cumsum(vis_len)

    # origin_left: slot containing visible char pos-1.
    p = positions - 1
    j = jnp.searchsorted(cvis, p, side="right").astype(jnp.int32)
    jc = jnp.clip(j, 0, n - 1)
    ol_off = (p - (cvis[jc] - vis_len[jc])).astype(jnp.int32)
    ol_j = jnp.where(positions == 0, -1, jc)

    # origin_right: cursor sits after origin_left; the next non-NIY char.
    # Within a visible slot the next char is right there; otherwise scan
    # forward to the next slot with state != NIY (suffix min over indexes).
    non_niy = row_ord != 0
    idx = jnp.arange(n, dtype=jnp.int32)
    nxt = jnp.flip(jax_lazy_cummin(jnp.flip(
        jnp.where(non_niy, idx, n), axis=0)), axis=0)
    # cursor slot/off: (jc, ol_off+1) unless past slot end or pos==0.
    in_slot = (positions != 0) & (ol_off + 1 < len_ord[jc])
    scan_from = jnp.clip(jnp.where(positions == 0, 0, jc + 1), 0, n)
    nxt_pad = jnp.concatenate([nxt, jnp.full((1,), n, dtype=nxt.dtype)])
    far_j = nxt_pad[scan_from]
    orr_j = jnp.where(in_slot, jc, far_j).astype(jnp.int32)
    orr_off = jnp.where(in_slot, ol_off + 1, 0).astype(jnp.int32)
    orr_j = jnp.where(orr_j >= n, -1, orr_j)
    return ol_j, ol_off, orr_j, orr_off


def jax_lazy_cummin(x):
    import jax.numpy as jnp
    from jax import lax
    return lax.associative_scan(jnp.minimum, x)
