"""Per-mesh window arenas: device-resident staging + donated-buffer
reuse for the mesh flush rung.

Pre-arena, `mesh_fused_replay` re-staged every session's resident
state through host numpy each window (`np.asarray(s.docs)` into a
fresh `[B, cap]` buffer, then `device_put`) — a full host round trip
for rows that already lived on-chip, and the donated `[B, cap]`
output buffers of window k were simply dropped. This module keeps
both on the device:

  * **Device-side stacking** (the `DEVICE_STAGE` default): a dispatch
    is laid out by home chip (`parallel.mesh.home_blocks`) and each
    chip stacks its own block from the `docs`/`lens` rows it already
    holds, one program a chip — no host copy of resident state and no
    row on the interconnect; only the window's op PLAN arrays
    (host-built by construction) still cross the host boundary.
  * **Arena fast path** (donated-buffer reuse): after a window
    commits, its `[B, cap]` output arrays are parked as the arena of
    the `(mesh, cap, max_ins)` class and every committed session row
    is tagged `(arena, generation, slot)`, the slot being the row's
    place in that layout. When the NEXT window presents the same
    sessions in the same slots of the same shape class, the arena
    arrays are handed straight back to the donated kernel — zero
    staging, zero allocation. Donation is safe because sessions hold
    independent per-row buffers (a chip's shard is cut by
    `jit_dt_unstack_rows`, which donates nothing), never the stacked
    array itself.

Poison/fallback discipline: a row that fails the `adopt_results`
length fence is NOT committed, so its session keeps a stale-generation
tag (or none) — the next window's tag check misses, the stacking path
rebuilds from the sessions' own rows, and the poisoned slot can never
leak stale bytes. Any session mutation outside the mesh commit
(`FusedDocSession.commit` / `_materialize`) clears the tag for the
same reason.

Lock order: `_arena_lock` is a DEVICE-class witness lock (rank=None —
it guards a process-wide table, not a chip), taken briefly around
table reads/swaps while the scheduler already holds the ranked
per-device locks; dispatches and `device_put` run strictly OUTSIDE
it. It never acquires anything itself.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from ..analysis.witness import make_lock as _make_lock

_arena_lock = _make_lock("window_arena", "device", rank=None)


class _StageFlag:
    """Process-global device-staging switch (`--no-device-stage`
    flips it for the A/B control arm: host-numpy staging, full
    transfer accounting — the pre-arena behavior)."""

    def __init__(self) -> None:
        self.enabled = True


DEVICE_STAGE = _StageFlag()


class WindowArena:
    """Parked output buffers of the last committed window of one
    `(mesh, cap, max_ins)` class. `gen` increments per adoption so a
    stale tag can never match; `docs`/`lens` are cleared on handoff
    (donation consumes them) and on any failed dispatch they simply
    stay cleared until the next adoption."""

    __slots__ = ("bp", "gen", "live", "docs", "lens")

    def __init__(self) -> None:
        self.bp = 0
        self.gen = 0
        self.live = 0
        self.docs = None
        self.lens = None


_arenas: Dict[Tuple, WindowArena] = {}


def reset_arenas() -> None:
    with _arena_lock:
        _arenas.clear()


def arena_stats() -> dict:
    with _arena_lock:
        return {"arenas": len(_arenas),
                "generations": sum(a.gen for a in _arenas.values())}


def acquire(mesh, cap: int, mi: int, sessions, bp: int, slots):
    """Try the fast path: if the previous window of this shape class
    committed EXACTLY these sessions in these `slots` at this padded
    batch, hand its parked `[bp, cap]` arrays back for donation.
    Returns `(docs, lens)` or None (caller stacks instead)."""
    key = (mesh, int(cap), int(mi))
    with _arena_lock:
        a = _arenas.get(key)
        if a is None or a.docs is None or a.bp != bp \
                or a.live != len(sessions):
            return None
        for slot, s in zip(slots, sessions):
            if getattr(s, "_arena_tag", None) != (a, a.gen, slot):
                return None
        docs, lens = a.docs, a.lens
        a.docs = a.lens = None      # the donated call consumes them
        for s in sessions:
            s._arena_tag = None     # re-tagged on adopt, or not at all
        return docs, lens


def adopt(mesh, cap: int, mi: int, out_docs, out_lens, sessions,
          ok: List[bool], bp: int, slots) -> None:
    """Park a committed window's output arrays as the next window's
    arena and tag every COMMITTED session row with its slot. Rows that
    failed the length fence are left untagged — their slot exists in
    the parked array but can never be matched, so the fast path
    degrades to the stacking path instead of replaying stale bytes."""
    key = (mesh, int(cap), int(mi))
    with _arena_lock:
        a = _arenas.setdefault(key, WindowArena())
        a.gen += 1
        a.bp = bp
        a.live = len(sessions)
        a.docs = out_docs
        a.lens = out_lens
        for good, slot, s in zip(ok, slots, sessions):
            if good:
                s._arena_tag = (a, a.gen, slot)
