"""Batch-shape steering: snap flush windows onto WARM jit shape classes.

The two replay paths (mesh / fused) key their jit caches on the
padded `(b, n, max_ins, cap)` shape class, and pow2 rounding keeps the
class count O(log^2) — but pow2 rounding alone still lets a drifting
workload thrash the cache: a flash crowd whose per-window op counts
wander across pow2 buckets recompiles mid-flush even though a slightly
LARGER warmed class could have absorbed the window with bounded padding
waste. This module closes that gap with a tiny process-global policy:

  * `ShapeSteer` tracks the WARM set per jit cache ("fused" / "mesh")
    — populated by `note_warm` from the cache-lookup sites
    themselves (warmup compiles and observed flush compiles alike), so
    the table can never drift from the real jit caches.
  * `snap()` maps a window's pow2-floored `(bp0, n0)` to the shape
    class actually dispatched: an exact warm class is used as-is; a
    cold shape pads UP to the cheapest warm class whose cell waste
    `(bw*nw)/(bp0*n0)` stays under `max_waste`; a cold shape with no
    affordable warm neighbor pads anyway on FIRST sight (padding waste
    is microseconds, a compile is seconds) and only compiles its exact
    class once the shape RECURS (`recur_threshold`), at which point it
    joins the warm set and subsequent windows hit it exactly.

Padding `b`/`n` further up is parity-safe by construction: batch pad
rows replicate row 0 (per-shard rungs) or carry the `lens = -1` inert
sentinel (mesh rung), and op-axis padding rows are all-zero no-ops —
exactly the invariants `pack_plans` and the replay body already
maintain for pow2 rounding. The `adopt_results` length fence and the
data-fault ladder below it (per-doc, host) are untouched by this
policy.

`cap_class()` / `warmup_batches()` are the single source of truth for
capacity flooring and warmup batch enumeration — `warmup_fused_cache`
and `FusedDocSession._materialize` both consult them, so warmup can no
longer warm classes sessions never land on (the cap-floor drift fix).

Everything here is host-side dict bookkeeping — no jax imports; the
lookup cost is noise next to a single device dispatch, so the counters
run unconditionally and serve-bench / scorecards read them for free.
"""

from __future__ import annotations

from typing import Dict, Optional, Set, Tuple

from ..analysis.witness import make_lock as _make_lock
from .merge_kernel import _pow2

# pad up to a warm class while the padded cell count stays under this
# multiple of the floored cell count; beyond it a recurring shape earns
# its own compile instead of paying the waste every window
DEFAULT_MAX_WASTE = 4.0
# a cold shape seen this many times compiles its exact class (first
# sight never compiles: one-off shapes borrow a warm neighbor)
DEFAULT_RECUR_THRESHOLD = 2

_steer_lock = _make_lock("steer", "leaf")


def cap_class(cap: int) -> int:
    """The capacity shape class a session/warmup actually lands on:
    pow2, floored at 256 (`FusedDocSession._materialize`'s floor).
    Shared by warmup and the flush path so both agree byte-for-byte."""
    return _pow2(max(int(cap), 256))


def warmup_batches(flush_docs: int):
    """Batch shape classes a bank configured with `flush_docs` can emit
    on the per-shard rungs: 1 plus every pow2 up to flush_docs."""
    return sorted({1} | {_pow2(k) for k in range(2, max(int(flush_docs),
                                                        1) + 1)})


class ShapeSteer:
    """Process-global warm-class table + snap policy (see module doc).

    Keys are `(max_ins, cap, b, n)` per cache name, matching the jit
    cache keys modulo ordering. All state lives behind `_steer_lock`
    (leaf — safe under any rung's locks, including the jit-cache leaf
    guards, because it never acquires anything itself)."""

    def __init__(self, max_waste: float = DEFAULT_MAX_WASTE,
                 recur_threshold: int = DEFAULT_RECUR_THRESHOLD,
                 enabled: bool = True) -> None:
        self.enabled = enabled
        self.max_waste = float(max_waste)
        self.recur_threshold = int(recur_threshold)
        self._warm: Dict[str, Set[Tuple[int, int, int, int]]] = {}
        self._cold_seen: Dict[Tuple, int] = {}
        self._counts = {"lookups": 0, "hits": 0, "padded": 0,
                        "forced_pads": 0, "compiles": 0}

    def reset(self, table: bool = False) -> None:
        with _steer_lock:
            self._counts = {"lookups": 0, "hits": 0, "padded": 0,
                            "forced_pads": 0, "compiles": 0}
            if table:
                self._warm = {}
                self._cold_seen = {}

    def note_warm(self, cache: str, mi: int, cap: int, b: int,
                  n: int) -> None:
        """Record a shape class as warm in `cache`. Called from the jit
        cache lookup sites on hit AND miss — a hit proves the class
        warm, a miss is about to compile it — so the table tracks the
        real caches without a separate registration path."""
        with _steer_lock:
            self._warm.setdefault(cache, set()).add(
                (int(mi), int(cap), int(b), int(n)))

    def snap(self, cache: str, bp0: int, n0: int, mi: int, cap: int,
             multiple: int = 1) -> Tuple[int, int]:
        """Steer a window's pow2-floored shape `(bp0, n0)` onto the
        class to dispatch. `multiple` constrains the batch axis of any
        padded-to class (the mesh rung needs `bw % n_devices == 0`;
        warm mesh classes already satisfy it, this keeps a multi-mesh
        process honest). Returns `(bp, n)` with `bp >= bp0, n >= n0`;
        the caller pads exactly as it already does for pow2 rounding."""
        if not self.enabled:
            return bp0, n0
        with _steer_lock:
            self._counts["lookups"] += 1
            warm = self._warm.get(cache, ())
            if (mi, cap, bp0, n0) in warm:
                self._counts["hits"] += 1
                return bp0, n0
            floor_cells = bp0 * n0
            best: Optional[Tuple[int, int]] = None
            best_cells = 0
            for (wmi, wcap, bw, nw) in warm:
                if wmi != mi or wcap != cap or bw < bp0 or nw < n0:
                    continue
                if multiple > 1 and bw % multiple:
                    continue
                cells = bw * nw
                if best is None or cells < best_cells:
                    best, best_cells = (bw, nw), cells
            if best is not None \
                    and best_cells <= self.max_waste * floor_cells:
                self._counts["padded"] += 1
                return best
            ckey = (cache, mi, cap, bp0, n0)
            seen = self._cold_seen.get(ckey, 0) + 1
            self._cold_seen[ckey] = seen
            if best is not None and seen < self.recur_threshold:
                # one-off out-of-bound shape: borrow the warm neighbor
                # anyway — padding waste beats a request-path compile
                self._counts["forced_pads"] += 1
                return best
            self._counts["compiles"] += 1
            self._cold_seen.pop(ckey, None)
            return bp0, n0

    def classes(self, cache: str) -> list:
        """The `(max_ins, cap, b, n)` classes `cache` has dispatched."""
        with _steer_lock:
            return sorted(self._warm.get(cache, ()))

    def snapshot(self) -> dict:
        with _steer_lock:
            c = dict(self._counts)
            looks = c["lookups"]
            pads = c["padded"] + c["forced_pads"]
            return {"enabled": self.enabled,
                    "max_waste": self.max_waste,
                    "lookups": looks,
                    "hits": c["hits"],
                    "padded": pads,
                    "forced_pads": c["forced_pads"],
                    "compiles": c["compiles"],
                    "hit_rate": round((c["hits"] + pads) / looks, 4)
                    if looks else 0.0,
                    "warm_classes": {k: len(v) for k, v
                                     in sorted(self._warm.items())}}


STEER = ShapeSteer()
