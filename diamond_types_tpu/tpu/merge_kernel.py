"""listmerge_tpu — the device-resident merge backend.

End-to-end document checkout with the concurrent-order resolution running
on the accelerator (reference equivalent: the whole `src/listmerge` stack).
Division of labor (BASELINE.json north star): the host extracts per-item
origins (its order-statistic tree is the right tool for positional
lookups); the device computes the global document order — the Fugue-tree
linearization that replaces YjsMod `integrate` (see tpu/linearize.py) —
plus visibility filtering and text assembly, batched over documents.

Pipeline:

  host   prepare_doc(oplog):
           native transform (origin extraction) -> tracker item table
           -> anchor-split runs -> tree arrays (parent/side/keys)
           -> char pool (fast-forward prefix text + insert arena slices)
  device checkout_device / checkout_batch_device:
           fugue_linearize_jax (sorts + pointer-jumping Euler tour)
           -> visible-length prefix sums -> gather from the char pool

Batching: documents are padded to a common run count and char capacity and
vmapped; padding runs carry parent=root, huge sort keys, and zero visible
length, so they sort to the end and contribute no text.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Sequence

import numpy as np

from .linearize import (ROOT, UNDERWATER, build_tree_np,
                        fugue_linearize_jax, materialize_jax,
                        resolve_pos_keys, split_runs_at_anchors)


@dataclass
class DeviceDoc:
    """Host-prepared dense tables for one document's device checkout."""
    parent: np.ndarray      # [n] int32, parent == n -> virtual root
    side: np.ndarray        # [n] int8, 0 left / 1 right child
    key_pos: np.ndarray     # [n] int32 sibling sort key (orr position desc)
    key_agent: np.ndarray   # [n] int32 sibling sort key (agent name rank)
    key_seq: np.ndarray     # [n] int32 sibling sort key (seq)
    vis_len: np.ndarray     # [n] int32 visible chars contributed by run
    char_off: np.ndarray    # [n] int32 first char of run in `chars`
    chars: np.ndarray       # [pool] int32 char codes (prefix + ins arena)
    total_len: int          # expected document length
    frontier: Optional[List[int]] = None  # version the checkout lands on


# The agent-rank and insert-arena columns live in listmerge/columnar.py;
# the historical names stay importable — plan_kernels and
# listmerge/zone_np.py use them.
from ..listmerge.columnar import (agent_key_columns as _agent_keys,
                                  arena_offset_columns as _arena_offsets)


def prepare_doc(oplog, from_frontier: Sequence[int] = (),
                merge_frontier: Optional[Sequence[int]] = None) -> DeviceDoc:
    """Host pass: origins + char pool for a device checkout.

    Generalizes to INCREMENTAL merge (reference: TransformedOpsIter::new
    takes any `from` frontier, merge.rs:618): the tracker covers the
    conflict zone of (from, merge), the underwater spine tiles the
    document at the zone's common ancestor, and the produced checkout is
    the document at version_union(from, merge) — which is exactly what a
    branch at `from` merging `merge` must converge to."""
    from ..native.core import get_native_ctx

    ctx = get_native_ctx(oplog)
    frm = [int(x) for x in from_frontier]
    merge = ([int(x) for x in oplog.version] if merge_frontier is None
             else [int(x) for x in merge_frontier])
    with ctx.mirror_lock:   # the transform, its tracker's dump, the prefix
        *_rest, union = ctx.transform(frm, merge)
        ids, ln, ol, orr, st, ev = ctx.dump_tracker(keep_underwater=True)
        common = ctx.zone_common()
        # The underwater id space tiles the document at the conflict
        # zone's COMMON ANCESTOR (the version the tracker's walk starts
        # from) — NOT at [min insert id - 1]: zone ops that are pure
        # deletes toggle underwater text without creating tracker items.
        # With no conflict zone at all the prefix is the whole document.
        at = union if len(ids) == 0 else common
        prefix = ctx.merge_to_string("", [], at)[0] if at else ""
        ctx.release_tracker()  # the dump above is all we needed

    if len(ids) == 0:
        # no conflict zone at all (purely linear history): the document is
        # the fast-forward result; model it as one visible pseudo-run
        arr = np.frombuffer(prefix.encode("utf-32-le"), dtype=np.int32)
        n = 1
        return DeviceDoc(
            parent=np.array([n], dtype=np.int32),
            side=np.ones(n, dtype=np.int8),
            key_pos=np.zeros(n, dtype=np.int32),
            key_agent=np.zeros(n, dtype=np.int32),
            key_seq=np.zeros(n, dtype=np.int32),
            vis_len=np.array([len(arr)], dtype=np.int32),
            char_off=np.zeros(n, dtype=np.int32),
            chars=arr if len(arr) else np.zeros(1, np.int32),
            total_len=len(arr), frontier=union)
    prefix_arr = np.frombuffer(prefix.encode("utf-32-le"), dtype=np.int32)
    plen = len(prefix_arr)

    s_ids, s_len, s_ol, s_orr, s_ev = split_runs_at_anchors(
        ids, ln, ol, orr, (ev,))
    agent, seq = _agent_keys(oplog, s_ids)
    parent, side, ka, ks, orr_run = build_tree_np(s_ids, s_len, s_ol, s_orr,
                                                  agent, seq)
    kp = resolve_pos_keys(parent, side, ka, ks, orr_run)

    uw = s_ids >= UNDERWATER
    # Final visibility: a full checkout merges EVERY op, so an item is
    # visible iff no delete op ever targeted it — the tracker's monotone
    # `ever` flag. (The post-walk `state` reflects only the LAST walked
    # piece's version: concurrent branches sit retreated, deletes from
    # other branches sit undone — wrong for the merged frontier.)
    # Underwater runs are structural anchors; only their overlap with the
    # real prefix text [UNDERWATER, UNDERWATER+plen) is document text (the
    # tracker seeds one giant placeholder span whose tail is not text).
    uw_text = np.maximum(
        0, np.minimum(s_ids + s_len, UNDERWATER + plen) - s_ids)
    vis = np.where(s_ev != 0, 0, np.where(uw, uw_text, s_len))

    from ..text.op import INS
    arena_str = oplog.ops._arenas[INS].get((0, oplog.ops.arena_len(INS)))
    arena = np.frombuffer(arena_str.encode("utf-32-le"), dtype=np.int32)
    chars = np.concatenate([prefix_arr, arena]) if plen else arena
    off = np.where(uw, s_ids - UNDERWATER,
                   plen + _arena_offsets(oplog, np.where(uw, 0, s_ids)))

    return DeviceDoc(
        parent=parent.astype(np.int32), side=side.astype(np.int8),
        key_pos=kp.astype(np.int32),
        key_agent=ka.astype(np.int32), key_seq=ks.astype(np.int32),
        vis_len=vis.astype(np.int32), char_off=off.astype(np.int32),
        chars=chars.astype(np.int32), total_len=int(vis.sum()),
        frontier=union)


def _checkout_kernel(parent, side, key_pos, key_agent, key_seq, vis_len,
                     char_off, chars, cap: int, pallas: bool = False):
    perm = fugue_linearize_jax(parent, side, key_pos, key_agent, key_seq)
    if pallas:
        from .pallas_kernels import materialize_pallas
        return materialize_pallas(perm, vis_len, char_off, chars, cap)
    return materialize_jax(perm, vis_len, char_off, chars, cap)


_kernel_cache = {}


def _pow2(x: int) -> int:
    return 1 << max(1, (int(x) - 1)).bit_length()


def _jitted_kernel(cap: int):
    """Compiled batched kernels keyed by the (power-of-two) capacity so
    growing documents reuse O(log max_len) compiled executables instead of
    recompiling per exact length. DT_TPU_PALLAS=1 selects the Pallas
    materialize stage (pallas_kernels.materialize_pallas); that path
    unrolls the batch instead of vmapping — the run-copy kernel's grid
    spans runs, and vmap-of-pallas_call would stack a batch grid dim
    whose auto-extended block specs violate Pallas TPU block rules."""
    pallas = bool(os.environ.get("DT_TPU_PALLAS"))
    key = (cap, pallas)
    fn = _kernel_cache.get(key)
    if fn is None:
        import jax
        if pallas:
            import jax.numpy as jnp
            single = partial(_checkout_kernel, cap=cap, pallas=True)

            def run_all(*cols):
                outs = [single(*(c[i] for c in cols))
                        for i in range(cols[0].shape[0])]
                return (jnp.stack([t for t, _ in outs]),
                        jnp.stack([n for _, n in outs]))

            fn = jax.jit(run_all)
        else:
            fn = jax.jit(jax.vmap(partial(_checkout_kernel, cap=cap,
                                          pallas=pallas)))
        _kernel_cache[key] = fn
    return fn


def checkout_device(oplog, doc: Optional[DeviceDoc] = None) -> str:
    """Full checkout with device-side order resolution. Returns the text."""
    if doc is None:
        doc = prepare_doc(oplog)
    return checkout_batch_device([doc])[0]


def merge_device(oplog, from_frontier: Sequence[int],
                 merge_frontier: Optional[Sequence[int]] = None):
    """Incremental device merge: the document + frontier a branch at
    `from_frontier` reaches after merging `merge_frontier` (defaults to
    the oplog tip). Returns (text, frontier) at version_union(from,
    merge) — the convergence target of Branch.merge (reference:
    src/list/merge.rs:63-96 via TransformedOpsIter::new(from, ...))."""
    doc = prepare_doc(oplog, from_frontier, merge_frontier)
    return checkout_batch_device([doc])[0], doc.frontier


def pad_docs(docs: List[DeviceDoc]):
    """Stack documents into batch arrays. Shapes are padded to the next
    power of two so repeated checkouts of growing documents hit the jit
    trace cache instead of recompiling per exact size."""
    n = _pow2(max(d.parent.shape[0] for d in docs))
    pool = _pow2(max(d.chars.shape[0] for d in docs))
    b = len(docs)
    parent = np.full((b, n), 0, dtype=np.int32)
    side = np.ones((b, n), dtype=np.int32)
    kp = np.full((b, n), np.iinfo(np.int32).max, dtype=np.int32)
    ka = np.full((b, n), np.iinfo(np.int32).max, dtype=np.int32)
    ks = np.full((b, n), np.iinfo(np.int32).max, dtype=np.int32)
    vis = np.zeros((b, n), dtype=np.int32)
    off = np.zeros((b, n), dtype=np.int32)
    chars = np.zeros((b, pool), dtype=np.int32)
    for i, d in enumerate(docs):
        k = d.parent.shape[0]
        # the kernel's virtual root is index n (padded size); remap each
        # doc's own root (k) and hang padding rows off the root with huge
        # keys so they linearize to the very end (zero visible text)
        parent[i, :] = n
        parent[i, :k] = np.where(d.parent == k, n, d.parent)
        side[i, :k] = d.side
        kp[i, :k] = d.key_pos
        ka[i, :k] = d.key_agent
        ks[i, :k] = d.key_seq
        vis[i, :k] = d.vis_len
        off[i, :k] = d.char_off
        chars[i, :d.chars.shape[0]] = d.chars
    return parent, side, kp, ka, ks, vis, off, chars


def checkout_batch_device(docs: List[DeviceDoc], cap: Optional[int] = None
                          ) -> List[str]:
    """Batched device checkout: one vmapped kernel call for all docs."""
    import jax.numpy as jnp

    parent, side, kp, ka, ks, vis, off, chars = pad_docs(docs)
    if cap is None:
        cap = _pow2(max(max(d.total_len for d in docs), 1))
    fn = _jitted_kernel(cap)
    texts, totals = fn(*(jnp.asarray(x) for x in
                         (parent, side, kp, ka, ks, vis, off, chars)))
    texts = np.asarray(texts)
    totals = np.asarray(totals)
    return [texts[i, :totals[i]].astype(np.int32).tobytes()
            .decode("utf-32-le") for i in range(len(docs))]
