"""Device-resident tail transform — `plan_tail_device` for the serve path.

`FusedDocSession.plan_tail()` resolves every pending op's merge position
with the host tracker walk (`get_xf_operations_full`): one Python step
per op, serialized under the oplog guard — the stage ROADMAP item 2
calls the cap on every occupancy win. This module is the `listmerge_tpu`
replacement: the flush bucket's op tails become columnar DAG arrays
(listmerge/columnar.py) and the concurrent-order resolution runs on
device, batched over the bucket.

Division of labor (the merge_kernel prepare/checkout split):

  host   extract_tail(sess)        [under the oplog guard]
           one native transform -> tracker item runs + delete-target
           rows -> visibility-granular splits -> Fugue tree arrays
           (parent/side/keys) + old/new visible-length columns
  device resolve_positions(...)    [outside the oplog guard]
           fugue_linearize_jax order + position/peak/length prefix
           scans, vmapped over the bucket, pow2-padded shape classes
           with a locked jit cache (devprof family "xform")

Old-visibility is a pure LV THRESHOLD: a fused session's frontier is
always the oplog version at log length `synced_to` (set together under
the oplog guard), so `lv < synced_to  <=>  op causally <= frontier` —
no per-op reachability walk needed. `validate_prefix_frontier` proves
exactly that equivalence with the scatter-max DAG reachability kernel
(tpu/graph_kernels.py); the randomized parity tests run it, and
DT_XFORM_VALIDATE=1 turns it on per extract.

The edit script is emitted in DOCUMENT order (delete old-only runs,
insert new-only runs, positions = exclusive prefix sum of new visible
lengths), which reaches the same final text as the host's causal-order
script; `plan.new_len`/`max_len` describe THIS script, so the fused
replay fences (`adopt_results` length check) apply unchanged. Every
guard — empty conflict zone, reversed insert runs, missing arena
content, the Σold_vis == doc_len fence — falls back to the host
`plan_tail()` per document, never skipping a parity fence.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Sequence, Tuple, Union

import numpy as np

from ..listmerge.columnar import (TailColumns, UnsupportedTail,
                                  agent_key_columns, arena_offset_columns,
                                  export_tail_columns, old_delete_intervals,
                                  visibility_cuts)
from .flush_fuse import TailPlan, _empty_plan
from .linearize import (UNDERWATER, build_tree_np, fugue_linearize_jax,
                        resolve_pos_keys, split_runs_at_anchors)
from .merge_kernel import _pow2

INT32_MAX = np.iinfo(np.int32).max


@dataclass
class TailExtract:
    """Host half of one doc's device plan: Fugue tree arrays + visibility
    columns, self-contained (no oplog access needed after extraction, so
    the device half runs outside the oplog guard)."""
    parent: np.ndarray     # [k] int64, parent == k -> virtual root
    side: np.ndarray       # [k] int8
    key_pos: np.ndarray    # [k] int64
    key_agent: np.ndarray  # [k] int64
    key_seq: np.ndarray    # [k] int64
    old_vis: np.ndarray    # [k] int32 chars visible at the session frontier
    new_vis: np.ndarray    # [k] int32 chars visible after the merge
    aoff: np.ndarray       # [k] int64 insert-arena char offsets
    arena: np.ndarray      # int32 char codes (whole insert arena)
    doc_len: int
    max_ins: int
    frontier: Tuple[int, ...]
    synced_to: int

    @property
    def n(self) -> int:
        return len(self.parent)


def extract_tail(sess) -> Union[TailExtract, TailPlan]:
    """Host half of plan_tail_device for one FusedDocSession. Must be
    called under the oplog guard (native transform + column reads).

    Returns a TailExtract for the device resolver, or — when the tail is
    outside the device contract — the host `plan_tail()` result directly
    (the per-doc host fallback rung of the transform ladder)."""
    ol = sess.oplog
    if sess.synced_to >= len(ol):
        return sess.plan_tail()          # empty tail: host fast path
    try:
        cols = export_tail_columns(ol, sess.frontier)
    except UnsupportedTail:
        return sess.plan_tail()
    synced_to = len(ol)
    plen = len(cols.prefix)

    cuts = visibility_cuts(cols, sess.synced_to)
    s_ids, s_len, s_ol, s_orr, s_ev = split_runs_at_anchors(
        cols.ids, cols.ln, cols.ol, cols.orr, (cols.ev,), extra_cuts=cuts)
    agent, seq = agent_key_columns(ol, s_ids)
    parent, side, ka, ks, orr_run = build_tree_np(s_ids, s_len, s_ol, s_orr,
                                                  agent, seq)
    kp = resolve_pos_keys(parent, side, ka, ks, orr_run)

    uw = s_ids >= UNDERWATER
    uw_text = np.maximum(
        0, np.minimum(s_ids + s_len, UNDERWATER + plen) - s_ids)
    text_len = np.where(uw, uw_text, s_len)
    # new visibility: merged-to-union rule, identical to prepare_doc
    new_vis = np.where(s_ev != 0, 0, text_len)
    # old visibility: inserted at-or-before the session frontier (uw
    # spine, or lv under the threshold) and not deleted by an op under
    # the threshold. Runs are cut at every delete-target boundary and at
    # each straddling row's old/new split point, so coverage at the run
    # START decides the whole run.
    d0, d1 = old_delete_intervals(cols, sess.synced_to)
    cov = (np.searchsorted(np.sort(d0), s_ids, side="right")
           - np.searchsorted(np.sort(d1), s_ids, side="right"))
    old_ins = uw | (s_ids < sess.synced_to)
    old_vis = np.where(old_ins & (cov == 0), text_len, 0)

    if int(old_vis.sum(dtype=np.int64)) != sess.doc_len:
        # the transform's parity fence: our model of the resident text
        # disagrees with the session — never guess, host-plan instead
        return sess.plan_tail()
    aoff = arena_offset_columns(ol, np.where(uw, 0, s_ids))
    ins_run = (new_vis > 0) & (old_vis == 0)
    if (aoff[ins_run] < 0).any():
        return sess.plan_tail()          # insert without stored content
    if os.environ.get("DT_XFORM_VALIDATE"):
        assert validate_prefix_frontier(ol, sess.frontier, sess.synced_to), \
            "log-prefix-frontier contract violated (device reachability)"
    return TailExtract(
        parent=parent, side=side, key_pos=kp, key_agent=ka, key_seq=ks,
        old_vis=old_vis.astype(np.int32), new_vis=new_vis.astype(np.int32),
        aoff=aoff, arena=cols.arena, doc_len=sess.doc_len,
        max_ins=sess.max_ins, frontier=cols.union, synced_to=synced_to)


# ---------------------------------------------------------------------------
# device half: batched order + position resolution
# ---------------------------------------------------------------------------

_xform_jit_cache = {}
from ..analysis.witness import make_lock as _make_lock
_xform_jit_lock = _make_lock("xform_jit", "device")


def _xform_single(parent, side, kp, ka, ks, ov, nv, pallas: bool):
    import jax.numpy as jnp

    perm = fugue_linearize_jax(parent, side, kp, ka, ks)
    nvp = nv[perm]
    ovp = ov[perm]
    if pallas:
        from .pallas_kernels import xform_positions_pallas
        pos, new_len, peak = xform_positions_pallas(nvp, ovp)
    else:
        cum = jnp.cumsum(nvp)
        pos = (cum - nvp).astype(jnp.int32)
        delta = jnp.cumsum(nvp - ovp)
        new_len = cum[-1].astype(jnp.int32)
        peak = jnp.maximum(jnp.int32(0), jnp.max(delta)).astype(jnp.int32)
    return perm.astype(jnp.int32), pos, new_len, peak


def _xform_fn(b: int, n: int, pallas: bool = False):
    """Jitted batched transform for `b` docs x `n` run slots — static
    pow2 shape classes, same O(log^2) cache discipline as `_fused_fn`.
    `pallas` (the serve bank's `pallas=True`, the one selector) routes
    the position-resolution scans through the gather-free Pallas kernel
    (batch unrolled: vmap-of-pallas_call would stack an illegal batch
    grid dim — see merge_kernel._jitted_kernel)."""
    import jax

    key = (b, n, bool(pallas))
    with _xform_jit_lock:
        fn = _xform_jit_cache.get(key)
        from ..obs.devprof import note_jit_lookup
        note_jit_lookup("xform", fn is not None)
        if fn is not None:
            return fn
        if pallas:
            import jax.numpy as jnp
            single = partial(_xform_single, pallas=True)

            def run_all(*cols):
                outs = [single(*(c[i] for c in cols))
                        for i in range(cols[0].shape[0])]
                return tuple(jnp.stack([o[j] for o in outs])
                             for j in range(4))

            fn = jax.jit(run_all)
        else:
            fn = jax.jit(jax.vmap(partial(_xform_single, pallas=False)))
        _xform_jit_cache[key] = fn
        return fn


def xform_shape_class(extracts: Sequence[TailExtract]) -> Tuple[int, int]:
    """(b, n) jit-cache class a bucket of extracts compiles to."""
    b = len(extracts)
    return (_pow2(b) if b > 1 else 1,
            _pow2(max(max(ex.n for ex in extracts), 1)))


def resolve_positions(extracts: Sequence[TailExtract],
                      pallas: bool = False
                      ) -> List[Optional[TailPlan]]:
    """Device half: resolve every extract's document order + positions in
    ONE batched dispatch, then assemble TailPlans host-side. Runs outside
    the oplog guard — extracts are self-contained.

    A doc whose device result fails the cross-check (device new_len vs
    the host visibility sum) comes back as None; the caller host-plans it
    under the oplog guard. Padding rows carry parent=root + INT32_MAX
    keys + zero visibility, so they linearize last and contribute no
    positions (the pad_docs convention)."""
    import jax.numpy as jnp

    if not extracts:
        return []
    bp, n = xform_shape_class(extracts)
    b = len(extracts)
    parent = np.full((bp, n), n, np.int32)
    side = np.ones((bp, n), np.int32)
    kp = np.full((bp, n), INT32_MAX, np.int32)
    ka = np.full((bp, n), INT32_MAX, np.int32)
    ks = np.full((bp, n), INT32_MAX, np.int32)
    ov = np.zeros((bp, n), np.int32)
    nv = np.zeros((bp, n), np.int32)
    for i, ex in enumerate(extracts):
        k = ex.n
        parent[i, :k] = np.where(ex.parent == k, n, ex.parent)
        side[i, :k] = ex.side
        kp[i, :k] = ex.key_pos
        ka[i, :k] = ex.key_agent
        ks[i, :k] = ex.key_seq
        ov[i, :k] = ex.old_vis
        nv[i, :k] = ex.new_vis
    from ..obs.devprof import note_transfer
    note_transfer(parent.nbytes * 5 + ov.nbytes + nv.nbytes)
    fn = _xform_fn(bp, n, pallas)
    perm_d, pos_d, len_d, peak_d = fn(*(jnp.asarray(x) for x in
                                        (parent, side, kp, ka, ks, ov, nv)))
    perm_d = np.asarray(perm_d)
    pos_d = np.asarray(pos_d)
    len_d = np.asarray(len_d)
    peak_d = np.asarray(peak_d)

    plans: List[Optional[TailPlan]] = []
    for i, ex in enumerate(extracts):
        try:
            plans.append(_assemble_plan(ex, perm_d[i], pos_d[i],
                                        int(len_d[i]), int(peak_d[i])))
        except AssertionError:      # the cross-check, a data fence
            plans.append(None)
    return plans


def _assemble_plan(ex: TailExtract, perm: np.ndarray, pos: np.ndarray,
                   new_len: int, peak: int) -> TailPlan:
    """Pack one doc's device-resolved order into TailPlan rows (doc-order
    edit script, ops chunked to max_ins like the host packer)."""
    if new_len != int(ex.new_vis.sum(dtype=np.int64)):
        raise AssertionError("device/host new-length disagreement")
    mi = ex.max_ins
    k = ex.n
    rows: List[Tuple[int, int, int, Optional[np.ndarray]]] = []
    for j in range(k):
        r = int(perm[j])
        ov_r = int(ex.old_vis[r])
        nv_r = int(ex.new_vis[r])
        if ov_r == nv_r:
            continue
        p = int(pos[j])
        if nv_r == 0:                      # delete the old-only run
            d = ov_r
            while d:
                step = min(d, mi)
                rows.append((p, step, 0, None))
                d -= step
        else:                              # insert the new-only run
            a = int(ex.aoff[r])
            off = 0
            while off < nv_r:
                step = min(nv_r - off, mi)
                rows.append((p + off, 0, step,
                             ex.arena[a + off:a + off + step]))
                off += step
    n_rows = len(rows)
    if n_rows == 0:
        return _empty_plan(ex.frontier, ex.synced_to, ex.doc_len, mi)
    pos_a = np.zeros(n_rows, np.int32)
    dl_a = np.zeros(n_rows, np.int32)
    il_a = np.zeros(n_rows, np.int32)
    ch_a = np.zeros((n_rows, mi), np.int32)
    for i, (p, d, il, ch) in enumerate(rows):
        pos_a[i] = p
        dl_a[i] = d
        il_a[i] = il
        if il:
            ch_a[i, :il] = ch
    return TailPlan(pos_a, dl_a, il_a, ch_a, n_rows, new_len,
                    ex.doc_len + peak, ex.frontier, ex.synced_to)


def plan_tails_device(sessions: Sequence, oplog_lock=None,
                      pallas: bool = False) -> Tuple[
        List[TailPlan], dict]:
    """plan_tail_device over a bucket: host extracts under the oplog
    guard, device resolves outside it, per-doc host fallback for guard
    trips. Returns (plans — one per session, never None — and a stats
    dict with the ServeMetrics transform-block counters)."""
    import contextlib
    guard = oplog_lock if oplog_lock is not None else contextlib.nullcontext()
    with guard:
        halves = [extract_tail(s) for s in sessions]
    extracts = [(i, h) for i, h in enumerate(halves)
                if isinstance(h, TailExtract)]
    stats = {"device_docs": 0, "host_docs": len(halves) - len(extracts),
             "fallbacks": 0, "batches": 1 if extracts else 0}
    plans: List[Optional[TailPlan]] = [
        h if isinstance(h, TailPlan) else None for h in halves]
    if extracts:
        resolved = resolve_positions([h for _, h in extracts],
                                     pallas=pallas)
        for (i, _), plan in zip(extracts, resolved):
            plans[i] = plan
    for i, plan in enumerate(plans):
        if plan is None:
            stats["fallbacks"] += 1
            with guard:
                plans[i] = sessions[i].plan_tail()
        elif isinstance(halves[i], TailExtract):
            stats["device_docs"] += 1
    return plans, stats


def validate_prefix_frontier(oplog, frontier: Sequence[int],
                             synced_to: int,
                             targets: Optional[np.ndarray] = None) -> bool:
    """Prove the log-prefix-frontier threshold with the device DAG
    reachability kernel: `lv < synced_to  <=>  frontier contains lv`,
    for every LV (or a caller-chosen sample). This is the property the
    transform's old-visibility column rests on."""
    import jax.numpy as jnp

    from .graph_kernels import frontier_contains_lv, pack_graph

    n = len(oplog)
    if n == 0:
        return int(synced_to) == 0
    packed = pack_graph(oplog.cg.graph)
    if targets is None:
        targets = np.arange(n, dtype=np.int32)
    fr = sorted(int(x) for x in frontier)
    fr_a = np.asarray(fr if fr else [-1], dtype=np.int32)
    got = np.asarray(frontier_contains_lv(packed, jnp.asarray(fr_a),
                                          jnp.asarray(targets)))
    want = np.asarray(targets) < int(synced_to)
    return bool((got == want).all())
