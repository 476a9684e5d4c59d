"""ctypes wrapper over native/libdt_core.so."""

from __future__ import annotations

import ctypes as ct
import functools
import sys
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .build import NativeBuildError, build as _build

_lib = None
# the same library loaded through `ct.PyDLL`: a call through it keeps the
# interpreter (the GIL) where one through `_lib` lets it go and queues
# for it again on return. For the calls that run for microseconds (a
# tail's loaders, the fetch of a result): under a lock that other
# threads wait for, each hand-off is that lock held for as long as the
# interpreter takes to come back.
_lib_kept = None
# why the build or load failed, once it has (negative cache: a failed
# g++ run is not retried per call). None while untried or loaded.
_load_error: Optional[str] = None

# Underwater sentinel base (ids at or above this are pre-zone placeholder
# text, not real op LVs) — one definition, shared with native/dt_core.cpp's
# UNDERWATER constant.
from ..core.span import UNDERWATER_START as UNDERWATER  # noqa: E402
from ..text.op import INS as _INS  # noqa: E402


def _load():
    """The loaded library, or None when the build or the load failed —
    library callers then take their pure-Python paths (~1/100 of the
    speed). The reason is kept in `_load_error`; `native.require_native`
    turns it into an error where the slow engine must not pass unseen
    (serve() start-up, chip_smoke.py)."""
    global _lib, _lib_kept, _load_error
    if _lib is not None or _load_error is not None:
        return _lib
    try:
        path = _build()
        lib = ct.CDLL(path)
        _configure(lib)
        kept = ct.PyDLL(path)
        _configure(kept)
    except (NativeBuildError, OSError, AttributeError) as e:
        # AttributeError: a symbol _configure declares is missing
        _load_error = f"{e.__class__.__name__}: {e}"
        sys.stderr.write(f"native host core unavailable ({_load_error}); "
                         "library callers use the pure-Python engine\n")
        return None
    _lib, _lib_kept = lib, kept
    return lib


def _configure(lib) -> None:
    lib.dt_ctx_new.restype = ct.c_void_p
    lib.dt_ctx_free.argtypes = [ct.c_void_p]
    lib.dt_add_agent.argtypes = [ct.c_void_p, ct.c_char_p]
    lib.dt_load_graph.argtypes = [ct.c_void_p, ct.c_int64] + [
        np.ctypeslib.ndpointer(np.int64, flags="C")] * 5
    lib.dt_load_agent_runs.argtypes = [ct.c_void_p, ct.c_int64] + [
        np.ctypeslib.ndpointer(np.int64, flags="C")] * 4
    lib.dt_load_ops.argtypes = [
        ct.c_void_p, ct.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.uint8, flags="C"),
        np.ctypeslib.ndpointer(np.uint8, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C")]
    lib.dt_load_ins_arena.argtypes = [
        ct.c_void_p, ct.c_int64, np.ctypeslib.ndpointer(np.int32, flags="C")]
    # the `_tail` loaders: (ctx, from, n, the whole loader's columns)
    for name in ("graph", "agent_runs", "ops", "ins_arena"):
        whole = getattr(lib, f"dt_load_{name}")
        tail = getattr(lib, f"dt_load_{name}_tail")
        tail.argtypes = [ct.c_void_p, ct.c_int64] + whole.argtypes[1:]
        tail.restype = ct.c_int64
    lib.dt_merge_into_doc.argtypes = [
        ct.c_void_p, np.ctypeslib.ndpointer(np.int32, flags="C"), ct.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C"), ct.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C"), ct.c_int64]
    lib.dt_merge_into_doc.restype = ct.c_int64
    lib.dt_get_doc.argtypes = [
        ct.c_void_p, np.ctypeslib.ndpointer(np.int32, flags="C")]
    lib.dt_transform.argtypes = [
        ct.c_void_p,
        np.ctypeslib.ndpointer(np.int64, flags="C"), ct.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C"), ct.c_int64]
    lib.dt_transform.restype = ct.c_int64
    lib.dt_get_out.argtypes = [
        ct.c_void_p,
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.uint8, flags="C"),
        np.ctypeslib.ndpointer(np.uint8, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C")]
    lib.dt_get_out_frontier.argtypes = [
        ct.c_void_p, np.ctypeslib.ndpointer(np.int64, flags="C"), ct.c_int64]
    lib.dt_get_out_frontier.restype = ct.c_int64
    lib.dt_dump_tracker.argtypes = [
        ct.c_void_p, ct.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.uint8, flags="C")]
    lib.dt_dump_tracker.restype = ct.c_int64
    lib.dt_dump_del_rows.argtypes = [
        ct.c_void_p, ct.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.uint8, flags="C")]
    lib.dt_dump_del_rows.restype = ct.c_int64
    lib.dt_last_collisions.argtypes = [ct.c_void_p]
    lib.dt_last_collisions.restype = ct.c_int64
    lib.dt_decode_new.argtypes = [
        np.ctypeslib.ndpointer(np.uint8, flags="C"), ct.c_int64]
    lib.dt_decode_new.restype = ct.c_void_p
    lib.dt_decode_free.argtypes = [ct.c_void_p]
    lib.dt_dec_status.argtypes = [ct.c_void_p]
    lib.dt_dec_status.restype = ct.c_int64
    lib.dt_dec_err.argtypes = [ct.c_void_p, ct.c_char_p, ct.c_int64]
    lib.dt_dec_err.restype = ct.c_int64
    lib.dt_dec_counts.argtypes = [
        ct.c_void_p, np.ctypeslib.ndpointer(np.int64, flags="C")]
    lib.dt_dec_strings.argtypes = [
        ct.c_void_p,
        np.ctypeslib.ndpointer(np.uint8, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.uint8, flags="C"),
        np.ctypeslib.ndpointer(np.uint8, flags="C"),
        np.ctypeslib.ndpointer(np.uint8, flags="C")]
    lib.dt_dec_agent_runs.argtypes = [
        ct.c_void_p,
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C")]
    lib.dt_dec_ops.argtypes = [
        ct.c_void_p,
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.uint8, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.uint8, flags="C"),
        np.ctypeslib.ndpointer(np.uint8, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C")]
    lib.dt_crc32c.argtypes = [
        np.ctypeslib.ndpointer(np.uint8, flags="C"), ct.c_int64, ct.c_int64]
    lib.dt_crc32c.restype = ct.c_int64
    lib.dt_lz4_compress.argtypes = [
        np.ctypeslib.ndpointer(np.uint8, flags="C"), ct.c_int64,
        np.ctypeslib.ndpointer(np.uint8, flags="C"), ct.c_int64]
    lib.dt_lz4_compress.restype = ct.c_int64
    lib.dt_dec_graph.argtypes = [
        ct.c_void_p,
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C")]
    lib.dt_get_zone_common.argtypes = [
        ct.c_void_p, np.ctypeslib.ndpointer(np.int64, flags="C"), ct.c_int64]
    lib.dt_get_zone_common.restype = ct.c_int64
    lib.dt_release_tracker.argtypes = [ct.c_void_p]
    lib.dt_get_counters.argtypes = [
        np.ctypeslib.ndpointer(np.uint64, flags="C"), ct.c_int64]
    lib.dt_get_counters.restype = ct.c_int64
    lib.dt_reset_counters.argtypes = []
    _i64p = np.ctypeslib.ndpointer(np.int64, flags="C")
    _i32p = np.ctypeslib.ndpointer(np.int32, flags="C")
    _u8p = np.ctypeslib.ndpointer(np.uint8, flags="C")
    lib.dt_compose_plan.argtypes = [ct.c_void_p, ct.c_int64, _i64p, _i64p]
    lib.dt_compose_plan.restype = ct.c_int64
    lib.dt_compose_counts.argtypes = [ct.c_void_p, _i64p]
    lib.dt_compose_serial.argtypes = [ct.c_void_p]
    lib.dt_compose_serial.restype = ct.c_int64
    lib.dt_compose_fetch.argtypes = [
        ct.c_void_p, _i64p, _i64p, _i32p, _u8p, _u8p, _i64p, _i32p,
        _i64p, _i64p, _i32p, _i64p, _i32p, _i32p,
        _i64p, _i64p, _i64p, _i64p]
    lib.dt_compose_linear.argtypes = [ct.c_void_p, ct.c_int64, _i64p, _i64p]
    lib.dt_compose_linear.restype = ct.c_int64
    lib.dt_fetch_linear.argtypes = [ct.c_void_p, _i64p, _i64p]
    lib.dt_encode_full.argtypes = [ct.c_void_p, ct.c_char_p, ct.c_int64,
                                   ct.c_char_p, ct.c_int64, ct.c_int64,
                                   ct.c_int64]
    lib.dt_encode_full.restype = ct.c_int64
    lib.dt_encode_patch.argtypes = [ct.c_void_p, ct.c_char_p, ct.c_int64,
                                    ct.c_char_p, ct.c_int64, ct.c_int64,
                                    ct.c_int64, _i64p, ct.c_int64]
    lib.dt_encode_patch.restype = ct.c_int64
    lib.dt_encode_fetch.argtypes = [ct.c_void_p, _u8p]
    lib.dt_zone_ins_runs.argtypes = [ct.c_void_p, ct.c_int64, _i64p,
                                     _i64p, _i64p, _i64p, _i64p]
    lib.dt_zone_ins_runs.restype = ct.c_int64
    lib.dt_graph_rebuild.argtypes = [ct.c_int64] + [_i64p] * 15
    lib.dt_graph_rebuild.restype = ct.c_int64
    lib.dt_zone_pack.argtypes = [
        ct.c_void_p, ct.c_int64, _i64p, _i64p, _i64p,          # actions
        ct.c_int64, _i64p,                                      # counts
        _i64p, _i64p, _u8p, _i64p, _i32p, _i64p,                # q + ch cols
        _i32p, _i64p, _i32p, _i32p,                             # blk cols
        _i64p, _i64p, _i64p, _i64p,                             # del cols
        ct.c_int64, _i64p, _i64p, ct.c_int64,                   # slot map
        _i64p, _i64p,                                           # keys
        ct.c_int64, ct.c_int64, ct.c_int64, ct.c_int64]  # MB MC MD cache
    lib.dt_zone_pack.restype = ct.c_int64
    lib.dt_zone_pack_fetch.argtypes = [ct.c_void_p] + [_i32p] * 19 + [
        ct.c_int64, ct.c_int64, ct.c_int64]


def native_available() -> bool:
    return _load() is not None


def _mirror_locked(method):
    """The method runs under the mirror's own lock, so a call and the
    fetch of its result are one use of the ctx."""
    @functools.wraps(method)
    def locked(self, *args, **kwargs):
        with self.mirror_lock:
            return method(self, *args, **kwargs)
    return locked


class NativeContext:
    """A C++ mirror of an OpLog's merge-relevant state (graph, agent runs,
    op runs, insert arena), brought up to date lazily, by `sync()`, when
    the oplog has grown. The oplog only appends, so the mirror follows it
    by appending too; it is built whole the first time and wherever a
    column does not continue what the mirror holds.

    The mirror is touched only under its own lock, `mirror_lock`: every
    public method takes it, and a caller that needs a sequence to be one
    use (`transform` -> `release_tracker` -> `last_collisions`) holds it
    across (it is reentrant). Nothing is acquired under it. `sync()`, and
    every method that syncs first, reads the Python oplog too, so its
    caller also holds whatever guards that oplog against writers (the
    server's `DocStore.lock`, taken BEFORE this one, never after);
    `encode_held` reads the mirror alone and needs this lock only."""

    def __init__(self, oplog) -> None:
        lib = _load()
        assert lib is not None
        from ..analysis.witness import make_lock
        self.mirror_lock = make_lock("native.mirror", "leaf",
                                     reentrant=True)
        self._lib = lib
        self._kept = _lib_kept
        self._ptr = lib.dt_ctx_new()
        self._built_len = -1
        self._oplog = oplog
        # entries the mirror holds of each column: agents, graph
        # entries, agent runs, op runs, insert-arena chars
        self._held = (0, 0, 0, 0, 0)
        # syncs that found the oplog grown, by how the mirror followed,
        # and the column entries the last of them sent
        self.appended = 0
        self.rebuilt = 0
        self.last_sent = 0

    def __del__(self):
        try:
            self._lib.dt_ctx_free(self._ptr)
        except Exception:
            pass

    @_mirror_locked
    def sync(self) -> None:
        ol = self._oplog
        if self._built_len == len(ol):
            return
        if self._built_len >= 0 and self._send_from(self._held):
            self.appended += 1
            return
        lib = self._lib
        lib.dt_ctx_free(self._ptr)
        self._ptr = lib.dt_ctx_new()
        self._built_len = -1
        if not self._send_from((0, 0, 0, 0, 0)):
            raise RuntimeError("native mirror: the oplog's columns do "
                               "not load")
        self.rebuilt += 1

    def _send_from(self, held) -> bool:
        """Send the ctx every column from what it `held` onwards, the
        last entry held included: a run-length-encoded last entry may
        have been extended in place (a graph entry's end, an agent
        run's lv1, an op run's loc, direction and content span). False
        where a column is shorter than what was held or a loader finds
        that it does not continue the ctx's own: the ctx is then to be
        built anew."""
        ol = self._oplog
        # a tail's loaders keep the interpreter: a sync runs under the
        # lock that guards the oplog, and four hand-offs a document were
        # most of that hold; a whole build (milliseconds) lets it go
        lib = self._kept if self._built_len >= 0 else self._lib
        ptr = self._ptr
        n_len = len(ol)
        names = ol.cg.agent_assignment.agent_names
        g = ol.cg.graph
        gr = ol.cg.agent_assignment.global_runs
        runs = ol.ops.runs
        now = (len(names), len(g.starts), len(gr), len(runs),
               ol.ops.arena_len(_INS))
        if n_len < self._built_len or any(
                n < h for n, h in zip(now, held)):
            return False
        for name in names[held[0]:]:
            lib.dt_add_agent(ptr, name.encode("utf8"))
        i64 = np.int64
        # the last entry held of each run-length-encoded column again
        g0, a0, r0 = (max(h - 1, 0) for h in held[1:4])
        starts, ends, shadows, indptr, flat = g.as_arrays(g0)
        if flat.size == 0:
            flat = np.zeros(1, dtype=i64)      # a pointer is passed
        acols = np.asarray(gr[a0:], dtype=i64).reshape(-1, 4)
        tail = runs[r0:]
        ok = (
            lib.dt_load_graph_tail(ptr, g0, now[1] - g0, starts, ends,
                                   shadows, indptr, flat) == now[1]
            and lib.dt_load_agent_runs_tail(
                ptr, a0, now[2] - a0,
                *(np.ascontiguousarray(acols[:, c]) for c in range(4))
            ) == now[2]
            and lib.dt_load_ops_tail(
                ptr, r0, now[3] - r0,
                np.asarray([r.lv for r in tail], dtype=i64),
                np.asarray([r.kind for r in tail], dtype=np.uint8),
                np.asarray([1 if r.fwd else 0 for r in tail],
                           dtype=np.uint8),
                np.asarray([r.start for r in tail], dtype=i64),
                np.asarray([r.end for r in tail], dtype=i64),
                content_offsets(tail)) == now[3]
            and lib.dt_load_ins_arena_tail(
                ptr, held[4], now[4] - held[4],
                arena_chars(ol, held[4])) == now[4])
        if ok:
            self._held = now
            self._built_len = n_len
            self.last_sent = (now[0] - held[0] + now[1] - g0 + now[2] - a0
                              + now[3] - r0 + now[4] - held[4])
        return ok

    @_mirror_locked
    def transform(self, from_frontier: Sequence[int],
                  merge_frontier: Sequence[int]):
        """Returns (lv, len, kind, fwd, pos arrays, final_frontier)."""
        self.sync()
        lib = self._lib
        f = np.asarray(sorted(from_frontier), dtype=np.int64)
        m = np.asarray(sorted(merge_frontier), dtype=np.int64)
        if f.size == 0:
            f = np.zeros(0, dtype=np.int64)
        if m.size == 0:
            m = np.zeros(0, dtype=np.int64)
        n = lib.dt_transform(self._ptr, np.ascontiguousarray(f), len(f),
                             np.ascontiguousarray(m), len(m))
        lv = np.empty(n, dtype=np.int64)
        ln = np.empty(n, dtype=np.int64)
        kind = np.empty(n, dtype=np.uint8)
        fwd = np.empty(n, dtype=np.uint8)
        pos = np.empty(n, dtype=np.int64)
        # the walk itself lets the interpreter go; the copies out of its
        # result keep it
        kept = self._kept
        if n:
            kept.dt_get_out(self._ptr, lv, ln, kind, fwd, pos)
        fbuf = np.empty(16, dtype=np.int64)
        k = kept.dt_get_out_frontier(self._ptr, fbuf, 16)
        if k > 16:
            fbuf = np.empty(k, dtype=np.int64)
            kept.dt_get_out_frontier(self._ptr, fbuf, k)
        frontier = [int(x) for x in fbuf[:k]]
        return lv, ln, kind, fwd, pos, frontier

    @_mirror_locked
    def compose_serial(self) -> int:
        """Identity of the current native compose cache (bumped by every
        dt_compose_plan) — the zone packer validates it before packing
        from the cache."""
        return int(self._lib.dt_compose_serial(self._ptr))

    @_mirror_locked
    def zone_ins_runs(self, spans):
        """INS sub-runs of the given spans as (lv0, len, cp) int64
        arrays — prepare_zone's table pass in C++; None on unsupported
        input (insert without stored content)."""
        self.sync()
        n = len(spans)
        s0 = np.ascontiguousarray(
            [s for s, _ in spans] or [0], dtype=np.int64)
        s1 = np.ascontiguousarray(
            [e for _, e in spans] or [0], dtype=np.int64)
        # bounded by the zone's own extent, not the whole history: a
        # span of L LVs overlaps at most L runs, and tiny incremental
        # zones must not allocate O(total-history) receive buffers
        span_lvs = sum(e - s for s, e in spans)
        cap = min(len(self._oplog.ops.runs), span_lvs) + n + 1
        lv0 = np.empty(cap, dtype=np.int64)
        ln = np.empty(cap, dtype=np.int64)
        cp = np.empty(cap, dtype=np.int64)
        k = self._lib.dt_zone_ins_runs(self._ptr, n, s0, s1, lv0, ln, cp)
        if k < 0:
            return None
        return lv0[:k], ln[:k], cp[:k]

    @_mirror_locked
    def compose_cache_only(self, spans) -> bool:
        """Run the native composer, leaving results ONLY in the ctx
        cache (no Python column round-trip) — the zone packer reads
        them in place. False = unsupported input (caller composes via
        the normal path)."""
        self.sync()
        n = len(spans)
        s0 = np.ascontiguousarray(
            [s for s, _ in spans] or [0], dtype=np.int64)
        s1 = np.ascontiguousarray(
            [e for _, e in spans] or [0], dtype=np.int64)
        return self._lib.dt_compose_plan(self._ptr, n, s0, s1) == 0

    @_mirror_locked
    def compose_plan(self, spans):
        """Native zone-engine composer (listmerge/compose.py's hot path in
        C++): compose each entry span into entry-start coordinates.
        Returns a list of per-entry column dicts, or None on unsupported
        input (reverse insert runs) — the caller falls back to Python."""
        self.sync()
        lib = self._lib
        n = len(spans)
        s0 = np.ascontiguousarray([s for s, _ in spans], dtype=np.int64)
        s1 = np.ascontiguousarray([e for _, e in spans], dtype=np.int64)
        if n == 0:
            return []
        if lib.dt_compose_plan(self._ptr, n, s0, s1) != 0:
            return None
        counts = np.empty(n * 5, dtype=np.int64)
        lib.dt_compose_counts(self._ptr, counts)
        counts = counts.reshape(n, 5)
        tq, tc, tb, tdb, tdo = (int(x) for x in counts.sum(axis=0))
        q = np.empty(tq, dtype=np.int64)
        ch_lv = np.empty(tc, dtype=np.int64)
        ch_block = np.empty(tc, dtype=np.int32)
        ch_head = np.empty(tc, dtype=np.uint8)
        ch_kind = np.empty(tc, dtype=np.uint8)
        ch_anchor = np.empty(tc, dtype=np.int64)
        ch_q = np.empty(tc, dtype=np.int32)
        ch_headlv = np.empty(tc, dtype=np.int64)
        ch_orrown = np.empty(tc, dtype=np.int64)
        blk_root_q = np.empty(tb, dtype=np.int32)
        blk_root_lv = np.empty(tb, dtype=np.int64)
        blk_start = np.empty(tb, dtype=np.int32)
        blk_len = np.empty(tb, dtype=np.int32)
        db0 = np.empty(tdb, dtype=np.int64)
        db1 = np.empty(tdb, dtype=np.int64)
        do0 = np.empty(tdo, dtype=np.int64)
        do1 = np.empty(tdo, dtype=np.int64)
        lib.dt_compose_fetch(self._ptr, q, ch_lv, ch_block, ch_head,
                             ch_kind, ch_anchor, ch_q, ch_headlv, ch_orrown,
                             blk_root_q, blk_root_lv, blk_start, blk_len,
                             db0, db1, do0, do1)
        out = []
        oq = oc = ob = odb = odo = 0
        for k in range(n):
            nq, nc, nb, ndb, ndo = (int(x) for x in counts[k])
            out.append({
                "q_cursor": q[oq:oq + nq].tolist(),
                "ch_lv": ch_lv[oc:oc + nc],
                "ch_block": ch_block[oc:oc + nc],
                "ch_head": ch_head[oc:oc + nc].astype(np.int8),
                "ch_kind": ch_kind[oc:oc + nc].astype(np.int8),
                "ch_anchor": ch_anchor[oc:oc + nc],
                "ch_q": ch_q[oc:oc + nc],
                "ch_headlv": ch_headlv[oc:oc + nc],
                "ch_orrown": ch_orrown[oc:oc + nc],
                "blk_root_q": blk_root_q[ob:ob + nb],
                "blk_root_lv": blk_root_lv[ob:ob + nb],
                "blk_start": blk_start[ob:ob + nb],
                "blk_len": blk_len[ob:ob + nb],
                "del_base": list(zip(db0[odb:odb + ndb].tolist(),
                                     db1[odb:odb + ndb].tolist())),
                "del_own": list(zip(do0[odo:odo + ndo].tolist(),
                                    do1[odo:odo + ndo].tolist())),
            })
            oq += nq
            oc += nc
            ob += nb
            odb += ndb
            odo += ndo
        return out

    @_mirror_locked
    def encode_full(self, doc_id, user_data, store_ins: bool,
                    compress: bool):
        """Native v1 full-snapshot encode (from_version=[]) of the oplog
        at its tip; None on failure (caller falls back to the Python
        writer)."""
        self.sync()
        return self.encode_held(doc_id, user_data, store_ins, compress)

    @_mirror_locked
    def encode_held(self, doc_id, user_data, store_ins: bool,
                    compress: bool):
        """`encode_full` of the mirror AS IT STANDS: no `sync()`, so it
        reads nothing of the Python oplog and runs with no lock but the
        mirror's own (the autosave, outside `DocStore.lock`). The mirror
        is a prefix of an append-only oplog in local-version order, so
        what it holds is a causally closed oplog. None where the mirror
        was never built, or on failure."""
        if self._built_len < 0:
            return None
        lib = self._lib
        did = doc_id.encode("utf8") if doc_id is not None else None
        n = lib.dt_encode_full(
            self._ptr, did, len(did) if did is not None else -1,
            user_data, len(user_data) if user_data is not None else -1,
            1 if store_ins else 0, 1 if compress else 0)
        if n < 0:
            return None
        out = np.empty(n, dtype=np.uint8)
        lib.dt_encode_fetch(self._ptr, out)
        return out.tobytes()

    @_mirror_locked
    def encode_patch(self, doc_id, user_data, store_ins: bool,
                     compress: bool, from_version):
        """Native v1 patch encode (encode_from; reference:
        encode_oplog.rs:404-745) — byte-identical to the Python writer.
        None on failure (caller falls back)."""
        self.sync()
        lib = self._lib
        did = doc_id.encode("utf8") if doc_id is not None else None
        f = np.ascontiguousarray(sorted(from_version), dtype=np.int64)
        n = lib.dt_encode_patch(
            self._ptr, did, len(did) if did is not None else -1,
            user_data, len(user_data) if user_data is not None else -1,
            1 if store_ins else 0, 1 if compress else 0, f, len(f))
        if n < 0:
            return None
        out = np.empty(n, dtype=np.uint8)
        lib.dt_encode_fetch(self._ptr, out)
        return out.tobytes()

    @_mirror_locked
    def compose_linear(self, spans):
        """Alive own pieces (lv, len arrays) of a linear-history
        composition over an empty base (assemble_prefix's hot loop), or
        None on unsupported input."""
        self.sync()
        lib = self._lib
        s0 = np.ascontiguousarray([s for s, _ in spans], dtype=np.int64)
        s1 = np.ascontiguousarray([e for _, e in spans], dtype=np.int64)
        n = lib.dt_compose_linear(self._ptr, len(spans), s0, s1)
        if n < 0:
            return None
        lv = np.empty(n, dtype=np.int64)
        ln = np.empty(n, dtype=np.int64)
        if n:
            lib.dt_fetch_linear(self._ptr, lv, ln)
        return lv, ln

    @_mirror_locked
    def release_tracker(self) -> None:
        """Free the tracker tables retained for dump_tracker/zone_common."""
        self._kept.dt_release_tracker(self._ptr)

    @_mirror_locked
    def last_collisions(self) -> int:
        """Colliding concurrent inserts during the last transform
        (reference: has_conflicts_when_merging, src/list/merge.rs:51)."""
        return int(self._lib.dt_last_collisions(self._ptr))

    @_mirror_locked
    def zone_common(self):
        """Common-ancestor frontier of the last transform's conflict zone
        (the version whose document the underwater id space tiles)."""
        lib = self._lib
        buf = np.empty(64, dtype=np.int64)
        k = lib.dt_get_zone_common(self._ptr, buf, 64)
        if k > 64:
            buf = np.empty(k, dtype=np.int64)
            lib.dt_get_zone_common(self._ptr, buf, k)
        return [int(x) for x in buf[:k]]

    @_mirror_locked
    def dump_tracker(self, keep_underwater: bool = False):
        """Item table of the last transform's tracker, in DOCUMENT order:
        (ids, len, origin_left, origin_right, state, ever) arrays.
        Underwater sentinel rows (ids >= 1<<62) are the pre-zone document
        text (anchor targets for zone items); filtered unless requested."""
        lib = self._lib
        z = np.zeros(0, dtype=np.int64)
        zu = np.zeros(0, dtype=np.uint8)
        n = lib.dt_dump_tracker(self._ptr, 0, z, z, z, z, z, zu)
        ids = np.empty(n, dtype=np.int64)
        ln = np.empty(n, dtype=np.int64)
        ol = np.empty(n, dtype=np.int64)
        orr = np.empty(n, dtype=np.int64)
        st = np.empty(n, dtype=np.int64)
        ev = np.empty(n, dtype=np.uint8)
        if n:
            lib.dt_dump_tracker(self._ptr, n, ids, ln, ol, orr, st, ev)
        if not keep_underwater:
            keep = ids < UNDERWATER
            return (ids[keep], ln[keep], ol[keep], orr[keep], st[keep],
                    ev[keep])
        return (ids, ln, ol, orr, st, ev)

    @_mirror_locked
    def dump_del_rows(self):
        """Delete-target rows of the last transform's tracker, sorted by
        op LV: (lv0, lv1, t0, t1, fwd) arrays — op lv0+k deletes item
        t0+k (fwd) or t1-1-k (reversed). Targets are intrinsic to each
        delete op, so the rows are schedule-independent."""
        lib = self._lib
        z = np.zeros(0, dtype=np.int64)
        zu = np.zeros(0, dtype=np.uint8)
        n = lib.dt_dump_del_rows(self._ptr, 0, z, z, z, z, zu)
        lv0 = np.empty(n, dtype=np.int64)
        lv1 = np.empty(n, dtype=np.int64)
        t0 = np.empty(n, dtype=np.int64)
        t1 = np.empty(n, dtype=np.int64)
        fwd = np.empty(n, dtype=np.uint8)
        if n:
            lib.dt_dump_del_rows(self._ptr, n, lv0, lv1, t0, t1, fwd)
        o = np.argsort(lv0, kind="stable")
        return lv0[o], lv1[o], t0[o], t1[o], fwd[o]

    @_mirror_locked
    def merge_to_string(self, init: str, from_frontier: Sequence[int],
                        merge_frontier: Sequence[int]):
        """Full native merge: returns (final_doc_str, final_frontier)."""
        self.sync()
        lib = self._lib
        init_arr = np.frombuffer(init.encode("utf-32-le"), dtype=np.int32)
        if init_arr.size == 0:
            init_arr = np.zeros(1, dtype=np.int32)
        f = np.ascontiguousarray(np.asarray(sorted(from_frontier), dtype=np.int64))
        m = np.ascontiguousarray(np.asarray(sorted(merge_frontier), dtype=np.int64))
        n = lib.dt_merge_into_doc(self._ptr, np.ascontiguousarray(init_arr),
                                  len(init), f, len(f), m, len(m))
        out = np.empty(max(int(n), 1), dtype=np.int32)
        lib.dt_get_doc(self._ptr, out)
        doc = out[:n].tobytes().decode("utf-32-le")
        fbuf = np.empty(64, dtype=np.int64)
        k = lib.dt_get_out_frontier(self._ptr, fbuf, 64)
        if k > 64:
            fbuf = np.empty(k, dtype=np.int64)
            lib.dt_get_out_frontier(self._ptr, fbuf, k)
        return doc, [int(x) for x in fbuf[:k]]


# Order mirrors dt_core.cpp's EventCounters / dt_get_counters.
EVENT_COUNTER_NAMES = (
    "integrate_calls", "integrate_scan_iters", "apply_ins_runs",
    "apply_del_runs", "advance_calls", "retreat_calls", "walk_steps",
    "diff_calls")


def crc32c_native(data: bytes, seed: int = 0):
    lib = _load()
    if lib is None:
        return None
    buf = np.frombuffer(data, dtype=np.uint8)
    return int(lib.dt_crc32c(np.ascontiguousarray(buf), len(data), seed))


def lz4_compress_native(data: bytes):
    lib = _load()
    if lib is None:
        return None
    buf = np.ascontiguousarray(np.frombuffer(data, dtype=np.uint8))
    cap = len(data) + len(data) // 255 + 16
    out = np.zeros(max(1, cap), dtype=np.uint8)
    n = int(lib.dt_lz4_compress(buf, len(data), out, cap))
    if n < 0:  # pragma: no cover - compression expanded past the estimate
        out = np.zeros(-n, dtype=np.uint8)
        n = int(lib.dt_lz4_compress(buf, len(data), out, -n))
    return out[:n].tobytes()


class NativeParseError(Exception):
    """Hard parse/corruption error reported by the native decoder."""


def decode_file_native(data: bytes) -> Optional[dict]:
    """Parse a v1 .dt file with the C++ decoder (fresh-load path only).

    Returns a dict of columns, or None when the file shape needs the
    Python decoder (patch files with a non-empty start version) or the
    native library is unavailable. Raises NativeParseError on corrupt
    input (same failures the Python decoder raises ParseError for)."""
    lib = _load()
    if lib is None:
        return None
    buf = np.frombuffer(data, dtype=np.uint8)
    h = lib.dt_decode_new(np.ascontiguousarray(buf), len(data))
    try:
        status = lib.dt_dec_status(h)
        if status != 0:
            n = lib.dt_dec_err(h, None, 0)
            msg = ct.create_string_buffer(int(n) + 1)
            lib.dt_dec_err(h, msg, n)
            if status == 1:
                return None
            raise NativeParseError(msg.value.decode("utf8", "replace"))
        counts = np.zeros(10, dtype=np.int64)
        lib.dt_dec_counts(h, counts)
        (n_agents, names_bytes, n_aruns, n_ops, n_graph, n_par,
         ins_bytes, del_bytes, has_doc_id, doc_bytes) = (int(x)
                                                         for x in counts)
        names = np.zeros(max(1, names_bytes), dtype=np.uint8)
        name_lens = np.zeros(max(1, n_agents), dtype=np.int64)
        ins_blob = np.zeros(max(1, ins_bytes), dtype=np.uint8)
        del_blob = np.zeros(max(1, del_bytes), dtype=np.uint8)
        doc_id = np.zeros(max(1, doc_bytes), dtype=np.uint8)
        lib.dt_dec_strings(h, names, name_lens, ins_blob, del_blob, doc_id)
        ar_agent = np.zeros(max(1, n_aruns), dtype=np.int64)
        ar_seq0 = np.zeros(max(1, n_aruns), dtype=np.int64)
        ar_n = np.zeros(max(1, n_aruns), dtype=np.int64)
        lib.dt_dec_agent_runs(h, ar_agent, ar_seq0, ar_n)
        op_lv = np.zeros(max(1, n_ops), dtype=np.int64)
        op_kind = np.zeros(max(1, n_ops), dtype=np.uint8)
        op_start = np.zeros(max(1, n_ops), dtype=np.int64)
        op_end = np.zeros(max(1, n_ops), dtype=np.int64)
        op_fwd = np.zeros(max(1, n_ops), dtype=np.uint8)
        op_known = np.zeros(max(1, n_ops), dtype=np.uint8)
        op_clen = np.zeros(max(1, n_ops), dtype=np.int64)
        lib.dt_dec_ops(h, op_lv, op_kind, op_start, op_end, op_fwd,
                       op_known, op_clen)
        g_start = np.zeros(max(1, n_graph), dtype=np.int64)
        g_end = np.zeros(max(1, n_graph), dtype=np.int64)
        g_off = np.zeros(n_graph + 1, dtype=np.int64)
        g_par = np.zeros(max(1, n_par), dtype=np.int64)
        lib.dt_dec_graph(h, g_start, g_end, g_off, g_par)

        names_b = names.tobytes()[:names_bytes]
        agent_names = []
        k = 0
        for i in range(n_agents):
            ln = int(name_lens[i])
            agent_names.append(names_b[k:k + ln].decode("utf8"))
            k += ln
        return {
            "doc_id": (doc_id.tobytes()[:doc_bytes].decode("utf8")
                       if has_doc_id else None),
            "agent_names": agent_names,
            "agent_runs": (ar_agent[:n_aruns], ar_seq0[:n_aruns],
                           ar_n[:n_aruns]),
            "ops": (op_lv[:n_ops], op_kind[:n_ops], op_start[:n_ops],
                    op_end[:n_ops], op_fwd[:n_ops], op_known[:n_ops],
                    op_clen[:n_ops]),
            "ins_blob": ins_blob.tobytes()[:ins_bytes].decode("utf8"),
            "del_blob": del_blob.tobytes()[:del_bytes].decode("utf8"),
            "graph": (g_start[:n_graph], g_end[:n_graph], g_off,
                      g_par[:n_par]),
        }
    finally:
        lib.dt_decode_free(h)


def native_counters() -> Optional[dict]:
    """Process-global merge-kernel event counters from the C++ engine
    (SURVEY §5 structured counters; always on)."""
    lib = _load()
    if lib is None:
        return None
    buf = np.zeros(len(EVENT_COUNTER_NAMES), dtype=np.uint64)
    k = lib.dt_get_counters(buf, len(buf))
    return {n: int(buf[i])
            for i, n in enumerate(EVENT_COUNTER_NAMES[:int(k)])}


def reset_native_counters() -> None:
    lib = _load()
    if lib is not None:
        lib.dt_reset_counters()


def get_native_ctx(oplog) -> "NativeContext":
    """The oplog's cached NativeContext (created on first use)."""
    ctx = getattr(oplog, "_native_ctx", None)
    if ctx is None:
        ctx = NativeContext(oplog)
        oplog._native_ctx = ctx
    return ctx


def graph_rebuild_native(g_start, g_end, g_off, g_par):
    """Batch-apply graph.py push + _advance_known_run semantics to the
    decoder's graph rows in C++: (starts, ends, shadows, parents CSR,
    children CSR, roots, version) or None when native is unavailable or
    the rows are malformed (caller falls back to per-row push)."""
    lib = _load()
    if lib is None:
        return None
    n = len(g_start)
    npar = len(g_par)
    a = lambda x: np.ascontiguousarray(x, dtype=np.int64)  # noqa: E731
    one = np.zeros(1, np.int64)
    ms = np.empty(max(n, 1), np.int64)
    me = np.empty(max(n, 1), np.int64)
    msh = np.empty(max(n, 1), np.int64)
    pind = np.empty(n + 1, np.int64)
    pflat = np.empty(max(npar, 1), np.int64)
    cind = np.empty(n + 1, np.int64)
    cflat = np.empty(max(npar, 1), np.int64)
    croot = np.empty(max(n, 1), np.int64)
    crn = np.zeros(1, np.int64)
    ver = np.empty(max(n, 1), np.int64)
    vern = np.zeros(1, np.int64)
    m = lib.dt_graph_rebuild(
        n, a(g_start), a(g_end), a(g_off), a(g_par) if npar else one,
        ms, me, msh, pind, pflat, cind, cflat, croot, crn, ver, vern)
    if m < 0:
        return None
    k = int(m)
    return (ms[:k], me[:k], msh[:k], pind[:k + 1], pflat[:int(pind[k])],
            cind[:k + 1], cflat[:int(cind[k])], croot[:int(crn[0])],
            ver[:int(vern[0])])


def content_offsets(runs) -> np.ndarray:
    """Per-run insert-arena offset (-1 = no content) of `runs`, the
    `cp` column dt_load_ops expects."""
    return np.asarray(
        [r.content_pos[0] if r.content_pos is not None else -1
         for r in runs], dtype=np.int64)


def arena_chars(oplog, start: int = 0) -> np.ndarray:
    """The INS arena from char `start` on as utf-32 code points, the
    layout dt_load_ins_arena expects (never empty: a pointer is
    passed)."""
    s = oplog.ops.get_content(_INS, (start, oplog.ops.arena_len(_INS)))
    arena = np.frombuffer(s.encode("utf-32-le"), dtype=np.int32)
    if arena.size == 0:
        arena = np.zeros(1, dtype=np.int32)
    return arena


def content_columns(oplog):
    """(cp, arena, arena chars) columns in the exact layout dt_load_ops /
    dt_load_ins_arena expect. Shared with tools/dump_columns so the
    native loaders' arena invariants live in one place."""
    return (content_offsets(oplog.ops.runs), arena_chars(oplog),
            oplog.ops.arena_len(_INS))


def merge_native(oplog, init: str, from_frontier, merge_frontier):
    return get_native_ctx(oplog).merge_to_string(init, from_frontier,
                                                 merge_frontier)


def transform_native(oplog, from_frontier, merge_frontier):
    return get_native_ctx(oplog).transform(from_frontier, merge_frontier)
