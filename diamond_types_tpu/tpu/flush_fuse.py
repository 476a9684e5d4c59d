"""Fused vmapped bucket flush: many documents, ONE device call.

The serve tier batches work (shape-bucketed admission queues) but the
pre-fusion flush threw the batch away at the device boundary: each doc
in a taken bucket was synced back-to-back, so batch occupancy bought
compile reuse but zero arithmetic intensity (ROADMAP item (c)). This
module closes that gap with the `tpu/batch.py replay_batch` shape —
`lax.scan` over op index, batched over documents — continued from
RESIDENT device state instead of replayed from scratch:

  * `FusedDocSession` — a document resident on the device as a dense
    `[cap]` char-code buffer + length (the replay-kernel state). The
    pending op tail since the last sync is extracted HOST-side through
    the oplog's transformed-op stream (`get_xf_operations_full`, the
    same oracle every host engine applies), so concurrent/merged
    histories arrive as plain positional ops — the device only ever
    sees the bounded-shift linear form.
  * `plan_tail()` packs that tail into dense `(pos, dlen, ilen, chars)`
    rows, splitting long ops to `max_ins` exactly like
    `encode_trace_ops` (the bounded-shift contract that keeps the tail
    shift a static-roll select, see batch.py).
  * `fused_replay(sessions, plans)` stacks every doc in the bucket into
    `[b, n, max_ins]` arrays — `n` padded to the bucket's power-of-two
    shape class, `b` rounded to a power of two so the jit cache stays
    O(log^2) — and runs ONE jitted scan for the whole bucket. The
    resident rows go into the `[b, cap]` batch and come back out of it
    by one jitted program each way (`_row_programs`): three device
    programs a call, whatever `b`.

Contract violations (an op longer than `max_ins` reaching the kernel)
poison that DOCUMENT's length to -1 — per-doc, not per-batch, so one
bad doc falls back to the host engine without discarding its bucket
neighbors' work. `fused_replay` additionally cross-checks each
returned length against the host-side projection; any drift evicts the
session and the bank serves the doc from `oplog.checkout_tip()`.

Everything device-touching imports jax lazily: the serve tier's host
engine must never pull in a backend.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Sequence, Tuple

import numpy as np

from ..obs.phases import phase
from .merge_kernel import _pow2

DEFAULT_CAP = 1 << 10
DEFAULT_MAX_INS = 16
# shape classes the background warmer compiles ahead of the first real
# flush (ops-per-doc axis); batch classes derive from flush_docs
WARMUP_SHAPE_CLASSES = (1, 2, 4, 8)

_fused_jit_cache = {}
from ..analysis.witness import make_lock as _make_lock
_fused_jit_lock = _make_lock("fused_jit", "leaf")


class FenceFailure(RuntimeError):
    """A replay came back with a poisoned (-1) or drifting length: a
    DATA fault. The bank answers it by evicting the session to the host
    oracle. Every other exception out of a device rung is a compiler or
    runtime failure and propagates."""


def make_replay_body(mi: int):
    """The fused-tail replay body, shared by the per-shard jit
    (`_fused_fn`) and the mesh flush program
    (`parallel.mesh.mesh_flush_fn`, which wraps it in `shard_map` over
    the `docs` axis — the body is pure data parallel, so partitioning
    the batch axis needs no collectives). Per-doc poison: a
    bounded-shift violation is zeroed to a no-op and only ITS doc's
    length comes back -1, so one bad doc never corrupts batch (or, on
    the mesh path, other shards') neighbors. Rows whose incoming length
    is the -1 padding sentinel and whose ops are all zero stay at -1 —
    inert mesh padding rows survive the kernel identifiably."""
    import jax
    import jax.numpy as jnp

    from .batch import _apply_ops_batched

    # the function's name is the jitted module's in a profiler trace
    # (`jit_dt_fused_replay`), the scopes name its device operations:
    # metadata only, the operations and shapes are what they were
    def dt_fused_replay(docs, lens, pos, dlen, ilen, chars):
        with jax.named_scope("dt.replay.sanitize"):
            bad = (dlen > mi) | (ilen > mi)
            dlen = jnp.where(bad, 0, dlen)
            ilen = jnp.where(bad, 0, ilen)
            bad_doc = jnp.any(bad, axis=1)

        def step(carry, op):
            d, l, p, dl, il, c = carry + op
            with jax.named_scope("dt.replay.apply"):
                d, l = _apply_ops_batched(d, l, p, dl, il, c)
            return (d, l), None

        with jax.named_scope("dt.replay.scan"):
            ops = (jnp.swapaxes(pos, 0, 1), jnp.swapaxes(dlen, 0, 1),
                   jnp.swapaxes(ilen, 0, 1), jnp.swapaxes(chars, 0, 1))
            (docs, lens), _ = jax.lax.scan(step, (docs, lens), ops)
            return docs, jnp.where(bad_doc, -1, lens)

    return dt_fused_replay


def _fused_fn(b: int, n: int, mi: int, cap: int):
    """Jitted fused-tail replay for batch `b`, `n` ops/doc, `max_ins`
    `mi`, capacity `cap` — all static, all powers of two, so the cache
    holds O(log^2) entries no matter how buckets drift."""
    import jax

    key = (b, n, mi, cap)
    with _fused_jit_lock:
        fn = _fused_jit_cache.get(key)
        from ..obs.devprof import note_jit_lookup
        note_jit_lookup("fused", fn is not None)
        if fn is None:
            fn = jax.jit(make_replay_body(mi), donate_argnums=(0, 1))
            _fused_jit_cache[key] = fn
    # hit or miss, the class is warm from here on — tell the steer
    # table (outside the cache guard; note_warm takes its own leaf)
    from .steer import STEER
    STEER.note_warm("fused", mi, cap, b, n)
    return fn


_row_fns = None


def _row_programs():
    """The two programs that take resident `[cap]` rows into the
    replay's `[bp, cap]` batch and back, one dispatch each where eager
    `jnp.stack` / `out_docs[i]` made some four a row; `jax.jit` keeps an
    executable a `(bp, cap)`. `dt_stack_rows` donates nothing: a
    session that fails the length fence keeps its row. `dt_unstack_rows`
    returns every row and length as a buffer of its own, so the next
    call may donate its batch. Their names (`jit_dt_stack_rows`,
    `jit_dt_unstack_rows`) are what a profiler trace shows of them."""
    global _row_fns
    with _fused_jit_lock:
        if _row_fns is None:
            import jax
            import jax.numpy as jnp

            def dt_stack_rows(rows, lens):
                with jax.named_scope("dt.replay.stack"):
                    return jnp.stack(rows), jnp.stack(lens)

            def dt_unstack_rows(docs, lens):
                with jax.named_scope("dt.replay.unstack"):
                    lanes = range(docs.shape[0])
                    return (tuple(docs[i] for i in lanes),
                            tuple(lens[i] for i in lanes))

            _row_fns = (jax.jit(dt_stack_rows), jax.jit(dt_unstack_rows))
    return _row_fns


_grow_fns = {}


def _grow_fn(cap: int, cap2: int):
    """The copy program of one (cap, cap2) pair of capacity classes: a
    resident `[cap]` row into a zeroed `[cap2]` row, on the device the
    row lives on. Its name (`jit_dt_grow`) and its scope are what a
    profiler trace shows of a growth."""
    import jax

    key = (cap, cap2)
    with _fused_jit_lock:
        fn = _grow_fns.get(key)
        if fn is None:
            import jax.numpy as jnp

            def dt_grow(row):
                with jax.named_scope("dt.grow"):
                    return jnp.pad(row, (0, cap2 - cap))

            fn = _grow_fns[key] = jax.jit(dt_grow)
    return fn


def warm_grow(cap: int, cap2: int) -> None:
    """Compile the copy from capacity class `cap` to `cap2` by running
    it on an empty row, on the default device: a bank calls this when
    it builds its first session of a class, so that a session which
    outgrows its class later compiles nothing. JAX keeps one program
    for a row that is committed to its chip (how a mesh window hands
    rows back, `parallel.mesh._cut_blocks`) and one for a row that is
    not, so both are run."""
    import jax
    import jax.numpy as jnp

    fn = _grow_fn(cap, cap2)
    row = jnp.zeros((cap,), jnp.int32)
    for r in (row, jax.device_put(row, next(iter(row.devices())))):
        jax.block_until_ready(fn(r))


def warmup_fused_cache(flush_docs: int = 8, cap: int = DEFAULT_CAP,
                       max_ins: int = DEFAULT_MAX_INS,
                       shape_classes: Sequence[int] = WARMUP_SHAPE_CLASSES,
                       mesh_shards: int = 0) -> int:
    """Compile the fused kernel for every (batch, ops) shape class a
    bank configured with `flush_docs` can emit, so the first REAL flush
    hits a warm jit cache instead of eating a compile on the request
    path. Returns the number of kernels compiled. Hits/misses surface
    through the existing `devprof.jit_cache` fields (cache "fused").

    `mesh_shards > 0` additionally pre-compiles the MESH flush program
    (`parallel.mesh.mesh_flush_fn`) for every super-batch shape class a
    `mesh_shards`-shard window can assemble — B padded to the mesh per
    `pad_batch_to_mesh` — and runs the two row programs on every mesh
    device for each of those classes' blocks (`warm_block_programs`),
    so the first mesh window doesn't eat a cold compile either (cache
    "mesh"), however unevenly its rows lie over the chips."""
    import jax
    import jax.numpy as jnp

    from .steer import cap_class, warmup_batches

    # sessions materialize at steer.cap_class(len * headroom) — warm
    # the floor class a fresh session actually lands on, not the raw
    # configured cap (which may name a class no session ever uses).
    # Both the floor and the batch enumeration come from tpu/steer.py,
    # the SAME table the flush path's snap() consults, so warmup and
    # steering can never disagree on what counts as a warm class.
    cap = cap_class(cap)
    compiled = 0
    batches = warmup_batches(flush_docs)
    for b in batches:
        # the batch goes in and comes out through the two row
        # programs, on rows as a build leaves them (not committed to a
        # chip), which is how a flush meets them
        stack, unstack = _row_programs()
        rows = (jnp.zeros((cap,), jnp.int32),) * b
        row_lens = (jnp.zeros((), jnp.int32),) * b
        for ncls in shape_classes:
            n = _pow2(ncls)
            fn = _fused_fn(b, n, max_ins, cap)
            docs, lens = stack(rows, row_lens)
            z = jnp.zeros((b, n), jnp.int32)
            ch = jnp.zeros((b, n, max_ins), jnp.int32)
            jax.block_until_ready(unstack(*fn(docs, lens, z, z, z, ch)))
            compiled += 1
    if mesh_shards > 0:
        from ..parallel.mesh import (block_classes, mesh_flush_fn,
                                     pad_batch_count, serve_mesh,
                                     warm_block_programs)
        mesh = serve_mesh(mesh_shards)
        ndev = mesh.devices.size
        sh = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(mesh.axis_names[0]))
        # a window can fold up to mesh_shards * flush_docs docs; the
        # padded-B classes below are exactly what pad_batch_count can
        # emit for any b in that range (O(log) classes)
        bps = sorted({pad_batch_count(b, ndev)
                      for b in range(1, mesh_shards * flush_docs + 1)})
        from ..obs.devprof import note_transfer
        warm_block_programs(mesh.devices.flat, cap,
                            block_classes(ndev, mesh_shards * flush_docs))
        for bp in bps:
            for ncls in shape_classes:
                n = _pow2(ncls)
                fn = mesh_flush_fn(mesh, bp, n, max_ins, cap)
                docs = jax.device_put(
                    jnp.zeros((bp, cap), jnp.int32), sh)
                lens = jax.device_put(
                    jnp.full((bp,), -1, jnp.int32), sh)
                z = jax.device_put(jnp.zeros((bp, n), jnp.int32), sh)
                ch = jax.device_put(
                    jnp.zeros((bp, n, max_ins), jnp.int32), sh)
                note_transfer(docs.nbytes + lens.nbytes + 3 * z.nbytes
                              + ch.nbytes, rung="mesh",
                              purpose="warmup")
                _out, out_lens = fn(docs, lens, z, z, z, ch)
                jax.block_until_ready(out_lens)
                compiled += 1
    return compiled


@dataclass
class TailPlan:
    """Host-side packing of one doc's pending op tail (see
    FusedDocSession.plan_tail). `max_len` past the session cap means
    the plan does not fit — the caller grows the session first
    (`FusedDocSession.make_room`)."""
    pos: np.ndarray
    dlen: np.ndarray
    ilen: np.ndarray
    chars: np.ndarray          # [n_ops, max_ins] int32
    n_ops: int
    new_len: int               # projected doc length after the tail
    max_len: int               # peak length the tail passes through
    frontier: Tuple[int, ...]  # oplog frontier after the tail
    synced_to: int             # oplog length the plan covers

    def fits(self, cap: int) -> bool:
        return self.max_len <= cap


def _empty_plan(frontier, synced_to, doc_len, mi) -> TailPlan:
    z = np.zeros(0, np.int32)
    return TailPlan(z, z, z, np.zeros((0, mi), np.int32), 0, doc_len,
                    doc_len, frontier, synced_to)


def _walk_pieces(ol, xf):
    """(pos, length, content | None for a delete) of each visible piece
    of the Python walk `xf`; a `pos` of None (a delete that already
    happened) is a no-op and is skipped."""
    from ..text.op import INS
    for _lv, op, pos in xf:
        if pos is None:
            continue
        if op.kind == INS:
            content = ol.ops.get_run_content(op)
            yield pos, len(content), content if op.fwd else content[::-1]
        else:
            yield pos, len(op), None


def _native_pieces(ol, lv, ln, kind, fwd, pos):
    """The same of `NativeContext.transform`'s columns: `pos` < 0 where
    the walk gives None; a piece lies inside one op run, whose content
    the oplog's op store keeps."""
    from ..text.op import INS
    for v, n, k, f, p in zip(lv, ln, kind, fwd, pos):
        if p < 0:
            continue
        if k == INS:
            content = ol.ops.content_slice(v, n)
            yield p, n, content if f else content[::-1]
        else:
            yield p, n, None


class FusedDocSession:
    """A live document resident on the device as the replay-kernel
    state: `[cap]` char codes + length. Drop-in for the bank's session
    surface (sync / text / footprint_slots / resyncs / synced_to)."""

    def __init__(self, oplog, cap: int = DEFAULT_CAP,
                 max_ins: int = DEFAULT_MAX_INS,
                 headroom: float = 2.0) -> None:
        self.oplog = oplog
        self.max_ins = int(max_ins)
        self.headroom = float(headroom)
        self.resyncs = -1          # the first build counts up to 0
        self.merges = 0
        # a bank's slot budget: `sync` calls it with the int32 slots a
        # growth is about to add (`SessionBank._build` sets it)
        self.before_growth = None
        self._materialize(min_cap=cap)

    # ---- full (re)build --------------------------------------------------

    def _materialize(self, min_cap: int = 0) -> None:
        """Host checkout -> device buffer. Always correct (the host
        tracker is the oracle); costs one full upload, so it only runs
        at build time (a first build, and the rebuild that follows an
        eviction or a `FenceFailure`) and where a growth on the device
        itself fails (`make_room`). `headroom` sizes a build, not a
        growth."""
        import jax.numpy as jnp

        text = self.oplog.checkout_tip().snapshot()
        # capacity class via steer.cap_class — the SAME floor warmup
        # enumerates, so every materialized session lands on a class
        # the warm table knows about (the cap-floor agreement fix)
        from .steer import cap_class
        cap = cap_class(max(int(len(text) * self.headroom), min_cap))
        buf = np.zeros(cap, np.int32)
        if text:
            buf[:len(text)] = np.frombuffer(
                text.encode("utf-32-le"), dtype=np.int32)
        self.cap = cap
        self.docs = jnp.asarray(buf)
        self.lens = jnp.asarray(np.int32(len(text)))
        self.doc_len = len(text)
        self.frontier = tuple(int(x) for x in self.oplog.version)
        self.synced_to = len(self.oplog)
        self.resyncs += 1
        self._arena_tag = None     # full rebuild invalidates any slot
        from ..obs.devprof import note_transfer
        note_transfer(buf.nbytes, rung="session", purpose="stage")

    # ---- growth ----------------------------------------------------------

    def grow(self, cap2: int) -> None:
        """Move to capacity class `cap2` on the device: the resident
        row copied into a zeroed `[cap2]` row by the one small program
        of the (cap, cap2) pair. No host checkout and no upload; the
        length, the frontier and the pending tail stay as they are. A
        window-arena tag names a row of the old capacity, so it goes."""
        self.docs = _grow_fn(self.cap, cap2)(self.docs)
        self.cap = cap2
        self._arena_tag = None

    def slots_short(self, plan: TailPlan) -> int:
        """The int32 slots a growth for `plan` adds: up to the smallest
        class that holds the plan's peak, however many classes up."""
        from .steer import cap_class
        return cap_class(plan.max_len) - self.cap

    def make_room(self, plan: TailPlan, rebuild: bool = True) -> bool:
        """`plan` does not fit: grow on the device to the smallest class
        that holds its peak, in one step. True where the session grew
        and `plan` is still to replay. Where the device refuses the
        copy: with `rebuild` the session is rebuilt from the host at
        the tip (counted `grow_rebuilt`), which leaves nothing pending;
        without it (a caller that does not hold the oplog's guard, and
        so may not check the document out) the session stays as it
        was. False either way."""
        import jax

        cap, docs = self.cap, self.docs
        cap2 = cap + self.slots_short(plan)
        with phase("bank.grow") as ph:
            try:
                self.grow(cap2)
                # the copy is dispatched, not done: a device that has
                # no room for the new row says so when it is waited
                # for, so it is waited for here, where it can still be
                # answered
                jax.block_until_ready(self.docs)
            except jax.errors.JaxRuntimeError:
                self.cap, self.docs = cap, docs
                if rebuild:
                    self._materialize(min_cap=cap2)
                    ph.count("grow_rebuilt")
                    ph.count("grow_slots", self.cap - cap)
                return False
            ph.count("grown")
            ph.count("grow_slots", cap2 - cap)
        return True

    # ---- host-side planning ----------------------------------------------

    def plan_tail(self) -> TailPlan:
        """Pack every op appended since the last sync into dense
        positional rows. Pure read — commit() applies the bookkeeping,
        so a plan can be dropped (fallback, eviction) at zero cost.
        Concurrent/merged histories come back pre-transformed by the
        host oracle; `pos is None` rows (deletes that already
        happened) are no-ops and are skipped."""
        with phase("plan.tail") as ph:
            return self._plan_tail(ph)

    def _plan_tail(self, ph) -> TailPlan:
        """`plan_tail` under its `plan.tail` phase `ph`; the steps are
        the transform, the row loop and the numpy fill. The transform
        runs on the oplog's native mirror where there is one; the pure
        Python walk is the fallback (and the tests' oracle)."""
        ol = self.oplog
        if self.synced_to >= len(ol):
            return _empty_plan(self.frontier, self.synced_to,
                               self.doc_len, self.max_ins)
        from ..native import native_ctx_or_none
        ctx = native_ctx_or_none(ol)
        ph.step("plan.xf")
        if ctx is None:
            ph.count("xf_python")
            xf = ol.get_xf_operations_full(list(self.frontier), ol.version)
            ph.step("plan.rows")
            # the walk is lazy and stays so: the seconds inside its
            # next() leave `plan.rows` for `plan.xf` (which so closes
            # twice a plan)
            pieces = _walk_pieces(ol, ph.timed(xf, "plan.xf"))
        else:
            ph.count("xf_native")
            # the mirror's own lock across the walk: the one other
            # holder is an autosave encoding THIS document's mirror
            # outside the store lock, and the walk waits that one
            # encode out
            busy = not ctx.mirror_lock.acquire(blocking=False)
            if busy:
                ctx.mirror_lock.acquire()
            try:
                grew = (ctx.appended, ctx.rebuilt)
                lv, ln, kind, fwd, pos, frontier = ctx.transform(
                    self.frontier, ol.version)
                ctx.release_tracker()
                ph.count("mirror_appended", ctx.appended - grew[0])
                ph.count("mirror_rebuilt", ctx.rebuilt - grew[1])
            finally:
                ctx.mirror_lock.release()
            ph.count("mirror_busy_waits", int(busy))
            ph.step("plan.rows")
            pieces = _native_pieces(ol, lv.tolist(), ln.tolist(),
                                    kind.tolist(), fwd.tolist(),
                                    pos.tolist())
        mi = self.max_ins
        rows: List[Tuple[int, int, int, str]] = []
        cur = self.doc_len
        peak = cur
        block_rows = 0      # rows cut from a piece longer than `mi`
        for pos, n, content in pieces:
            if n > mi:
                block_rows += -(-n // mi)
            if content is not None:
                off = 0
                while off < n:
                    chunk = content[off:off + mi]
                    rows.append((pos + off, 0, len(chunk), chunk))
                    off += len(chunk)
                cur += n
                peak = max(peak, cur)
            else:
                d = n
                while d:
                    k = min(d, mi)
                    rows.append((pos, k, 0, ""))
                    d -= k
                cur -= n
        k = len(rows)
        ph.step("plan.pack")
        ph.count("rows", k)
        ph.count("block_rows", block_rows)
        if ctx is None:
            frontier = xf.next_frontier     # known once the walk has ended
        frontier = tuple(int(x) for x in frontier)
        if k == 0:
            plan = _empty_plan(frontier, len(ol), self.doc_len, mi)
            plan.max_len = peak
            return plan
        pos_a = np.zeros(k, np.int32)
        dl_a = np.zeros(k, np.int32)
        il_a = np.zeros(k, np.int32)
        ch_a = np.zeros((k, mi), np.int32)
        for i, (p, d, il, s) in enumerate(rows):
            pos_a[i] = p
            dl_a[i] = d
            il_a[i] = il
            if s:
                ch_a[i, :il] = np.frombuffer(
                    s.encode("utf-32-le"), dtype=np.int32)
        return TailPlan(pos_a, dl_a, il_a, ch_a, k, cur, peak, frontier,
                        len(ol))

    def commit(self, docs, lens, plan: TailPlan) -> None:
        """Adopt one fused-replay result row + the plan's bookkeeping.
        Clears the window-arena tag: the session's state rows are no
        longer the arena's rows (the mesh rung re-tags committed rows
        right after `adopt_results`, see parallel/arena.py)."""
        self._arena_tag = None
        self.docs = docs
        self.lens = lens
        self.doc_len = plan.new_len
        self.frontier = plan.frontier
        self.synced_to = plan.synced_to
        if plan.n_ops:
            self.merges += 1

    def commit_host(self, plan: TailPlan) -> None:
        """Adopt an EMPTY plan (frontier advanced, no visible ops —
        e.g. deletes of already-deleted spans): no device work."""
        assert plan.n_ops == 0
        self.frontier = plan.frontier
        self.synced_to = plan.synced_to

    # ---- merge path ------------------------------------------------------

    def sync(self) -> int:
        """Per-doc path (the fused fallback ladder's last device rung):
        plan, then replay this doc alone at batch size 1. A tail that
        overflows the capacity grows the session on the device first
        (`make_room`) and is then replayed; only a growth that fails
        rebuilds from the host, and then nothing is left to replay.
        Raises `FenceFailure` on a poisoned result (the bank's sync_doc
        evicts and serves from the host engine)."""
        plan = self.plan_tail()
        if not plan.fits(self.cap):
            if self.before_growth is not None:
                self.before_growth(self.slots_short(plan))
            if not self.make_room(plan):
                return 0
        if plan.n_ops == 0:
            self.commit_host(plan)
            return 0
        ok, _device_s = fused_replay([self], [plan])
        if not ok[0]:
            raise FenceFailure(
                "fused replay poisoned/mismatched length "
                f"(doc_len {self.doc_len}, plan {plan.new_len})")
        return plan.n_ops

    # ---- reads -----------------------------------------------------------

    def text(self) -> str:
        """Fetch and decode the merged document (device parity
        surface: the answer comes from the replay kernel's state, not
        the host tracker)."""
        return decode_row(self.docs, self.doc_len)

    def touch(self):
        return np.asarray(self.lens)

    def footprint_slots(self) -> int:
        """Device residency in int32 slots: the doc buffer dominates."""
        return int(self.cap)


def decode_row(docs, n: int) -> str:
    """The first `n` characters of a resident `[cap]` row: the whole
    row is fetched and cut on the host, a plain transfer whatever the
    length. (A slice on the device, `docs[:n]`, is one executable a
    document length.)"""
    return np.asarray(docs)[:n].tobytes().decode("utf-32-le")


def pack_plans(plans: Sequence[TailPlan], n: int, mi: int,
               bp: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                 np.ndarray]:
    """Stack `plans` into dense host-side op arrays
    (pos/dlen/ilen [bp, n], chars [bp, n, mi]). Rows past len(plans)
    are all-zero no-ops — the inert padding the batch pow2 rounding
    (and the mesh super-batch divisibility padding) relies on. Shared
    by `fused_replay` and the mesh window's super-batch assembly."""
    pos = np.zeros((bp, n), np.int32)
    dlen = np.zeros((bp, n), np.int32)
    ilen = np.zeros((bp, n), np.int32)
    chars = np.zeros((bp, n, mi), np.int32)
    for i, p in enumerate(plans):
        k = p.n_ops
        pos[i, :k] = p.pos
        dlen[i, :k] = p.dlen
        ilen[i, :k] = p.ilen
        chars[i, :k] = p.chars
    return pos, dlen, ilen, chars


def adopt_results(sessions: Sequence[FusedDocSession],
                  plans: Sequence[TailPlan],
                  out_docs, out_lens,
                  got: np.ndarray) -> List[bool]:
    """The returned-length fence: commit each session whose device
    length matches the host-side projection; a poisoned (-1) or
    drifting row is NOT committed (the caller evicts it and serves the
    doc from the host engine). Shared by the per-shard and mesh paths
    so the fallback ladder fences identically in both."""
    ok: List[bool] = []
    for i, (sess, plan) in enumerate(zip(sessions, plans)):
        good = int(got[i]) == plan.new_len and int(got[i]) >= 0
        if good:
            sess.commit(out_docs[i], out_lens[i], plan)
        ok.append(good)
    return ok


def fused_replay(sessions: List[FusedDocSession],
                 plans: List[TailPlan]
                 ) -> Tuple[List[bool], float]:
    """Replay every session's pending tail in ONE jitted device call.

    All sessions must share (cap, max_ins) — the bank groups by
    capacity before calling. Ops pad to the max power-of-two op count
    in the batch (the bucket's shape class) and the batch rounds up to
    a power of two with no-op lanes, so the jit cache stays small.

    Returns (ok-per-session, device_wait_s). The device wait is the
    time spent blocked fetching the output lengths — the completion
    fence — which is the `block_until_ready`-equivalent attribution
    devprof wants. A session whose returned length is poisoned (-1) or
    disagrees with the host-side projection is NOT committed — the
    caller evicts it and serves the doc from the host engine.
    Successful sessions have their result rows committed."""
    with phase("replay") as ph:
        return _fused_replay(sessions, plans, ph)


def _fused_replay(sessions, plans, ph) -> Tuple[List[bool], float]:
    """`fused_replay` under its `replay` phase `ph`; the steps are the
    host pack, the stack (one program for the batch's rows and lengths),
    dispatch (jit lookup, plan upload, the call, and the program that
    cuts the rows back out, queued behind it), the length fence and
    adoption (the fence's verdict a session, over Python tuples)."""
    import jax.numpy as jnp

    b = len(sessions)
    assert b == len(plans) and b >= 1
    cap = sessions[0].cap
    mi = sessions[0].max_ins
    ph.step("replay.pack")
    from .steer import STEER
    n0 = _pow2(max(max(p.n_ops for p in plans), 1))
    bp0 = _pow2(b) if b > 1 else 1
    bp, n = STEER.snap("fused", bp0, n0, mi, cap)
    pos, dlen, ilen, chars = pack_plans(plans, n, mi, bp)
    ph.count("scan_steps", n)
    from ..obs.devprof import note_transfer
    note_transfer(pos.nbytes + dlen.nbytes + ilen.nbytes + chars.nbytes,
                  rung="fused", purpose="plan")
    ph.step("replay.stack")
    stack, unstack = _row_programs()
    lanes = sessions + sessions[:1] * (bp - b)  # inert ones repeat the first
    docs, lens = stack(tuple(s.docs for s in lanes),
                       tuple(s.lens for s in lanes))
    ph.step("replay.dispatch")
    fn = _fused_fn(bp, n, mi, cap)
    out_docs, out_lens = fn(docs, lens, jnp.asarray(pos),
                            jnp.asarray(dlen), jnp.asarray(ilen),
                            jnp.asarray(chars))
    # queued behind the replay: the rows are cut out on the device
    # while this thread waits at the fence, without the interpreter
    rows, row_lens = unstack(out_docs, out_lens)
    # the length fetch is the completion fence AND the parity
    # cross-check: poison (-1) or host-projection drift fails the doc
    ph.step("replay.fence")
    t_fence = time.perf_counter()
    got = np.asarray(out_lens)
    device_s = time.perf_counter() - t_fence
    ph.step("replay.adopt")
    return adopt_results(sessions, plans, rows, row_lens, got), device_s
