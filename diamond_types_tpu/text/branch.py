"""A branch: (version frontier, document content) — a live checkpoint.

Capability mirror of the reference ListBranch (reference: src/list/mod.rs:66-76,
src/list/branch.rs, src/list/merge.rs:63-96).
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence

from ..utils.rope import Rope
from .op import DEL, INS
from .oplog import OpLog


class Branch:
    __slots__ = ("version", "content", "last_merge_collisions",
                 "last_merge_engine")

    def __init__(self) -> None:
        self.version: List[int] = []
        self.content = Rope()
        # collisions reported by the last merge() — genuinely concurrent
        # inserts at the same gap (reference: has_conflicts_when_merging,
        # src/list/merge.rs:51). None = the selected engine doesn't report
        # (zone/plan2/device tiers); 0 = merged cleanly. A fully-default
        # merge() can return None once the measured policy has zone
        # measurements: check last_merge_engine to detect which engine
        # ran, and use OpLog.has_conflicts_when_merging (before merging)
        # when a collision count is required regardless of engine.
        self.last_merge_collisions: Optional[int] = None
        # which engine the policy picked for the last merge() — the
        # supported way to interpret last_merge_collisions above
        self.last_merge_engine: Optional[str] = None

    def __len__(self) -> int:
        return len(self.content)

    def snapshot(self) -> str:
        return str(self.content)

    # --- local edits (append to oplog, then apply here) --------------------

    def insert(self, oplog: OpLog, agent: int, pos: int, content: str) -> int:
        lv = oplog.add_insert_at(agent, self.version, pos, content)
        self.content.insert(pos, content)
        self.version = [lv]
        return lv

    def delete(self, oplog: OpLog, agent: int, start: int, end: int) -> int:
        deleted = self.content.slice(start, end)
        lv = oplog.add_delete_at(agent, self.version, start, end, deleted)
        self.content.delete(start, end - start)
        self.version = [lv]
        return lv

    def delete_without_content(self, oplog: OpLog, agent: int, start: int,
                               end: int) -> int:
        lv = oplog.add_delete_at(agent, self.version, start, end, None)
        self.content.delete(start, end - start)
        self.version = [lv]
        return lv

    # UTF-16 entry points for JS/Swift-style clients (reference:
    # branch.rs insert_at_wchar / delete_at_wchar, wchar_conversion feature).

    def insert_at_wchar(self, oplog: OpLog, agent: int, wchar_pos: int,
                        content: str) -> int:
        from ..core.unicount import wchars_to_chars
        return self.insert(oplog, agent,
                           wchars_to_chars(self.snapshot(), wchar_pos), content)

    def delete_at_wchar(self, oplog: OpLog, agent: int, wchar_start: int,
                        wchar_end: int) -> int:
        from ..core.unicount import wchars_to_chars
        snap = self.snapshot()
        return self.delete(oplog, agent, wchars_to_chars(snap, wchar_start),
                           wchars_to_chars(snap, wchar_end))

    # --- merge -------------------------------------------------------------

    def merge(self, oplog: OpLog, merge_frontier: Sequence[int]) -> None:
        """Bring everything in `merge_frontier`'s history into this branch
        (reference: src/list/merge.rs:63-96).

        Backend selection behind this one boundary (the reference keeps
        listmerge/listmerge2 behind the same seam):
          * DT_TPU_DEVICE_MERGE=1 — device merge kernel (Fugue-tree
            linearization of the conflict zone, batched-friendly),
          * default — C++ host core when built (same algorithm as the
            Python engine, ~2 orders of magnitude faster),
          * DT_TPU_ZONE=1 — zone engine (host composes entries, every
            origin resolves against state rows on the device tier —
            tpu/zone_kernel.py; the round-3 flagship),
          * DT_TPU_PLAN2=1 — fork/join plan engine (compile the conflict
            zone into a Begin/Fork/Max/Apply schedule over numbered state
            indexes, execute against the dense state matrix — the
            listmerge2 design; listmerge/plan2.py + dense.py),
          * DT_TPU_NO_NATIVE=1 — pure-Python engine (the oracle).

        Without an env override, the ZONE engine is auto-selected when
        the measured policy (listmerge/policy.py) says its observed
        throughput beats the tracker's for single-doc merges — engine
        selection is measured, not belief; the tracker remains the
        default and the oracle.
        """
        import time as _time

        self.last_merge_collisions = None
        self.last_merge_engine = None
        if os.environ.get("DT_TPU_PLAN2"):
            from ..listmerge.dense import merge_via_plan2
            rows, final = merge_via_plan2(oplog, self.version,
                                          merge_frontier)
            self._apply_xf(oplog, rows)
            self.version = list(final)
            self.last_merge_engine = "plan2"
            return
        if os.environ.get("DT_TPU_DEVICE_MERGE"):
            from ..tpu.merge_kernel import merge_device
            text, frontier = merge_device(oplog, self.version,
                                          merge_frontier)
            self.content = Rope(text)
            self.version = frontier
            self.last_merge_engine = "device"
            return

        def _top(v):
            return max((int(x) for x in v), default=-1) + 1

        from ..listmerge import policy as _policy

        def _zone_merge():
            # the round-3 zone engine: host composes, device (or the
            # NumPy oracle under JAX_PLATFORMS=cpu) resolves every origin
            # against state rows — no tracker anywhere. Its throughput is
            # recorded by zone_checkout_device itself. A policy-selected
            # zone merge reports last_merge_collisions = None (the
            # documented "engine doesn't report" value).
            from ..tpu.zone_kernel import zone_checkout_device
            text, frontier = zone_checkout_device(oplog, self.version,
                                                  merge_frontier)
            self.content = Rope(text)
            self.version = list(frontier)
            self.last_merge_engine = _policy.ZONE

        def _tracker_merge(ctx):
            from ..native import merge_native
            n_before = _top(self.version)
            t0 = _time.perf_counter()
            with ctx.mirror_lock:   # the merge and its collision count
                doc, frontier = merge_native(oplog, self.snapshot(),
                                             self.version, merge_frontier)
                self.last_merge_collisions = ctx.last_collisions()
            self.content = Rope(doc)
            self.version = frontier
            self.last_merge_engine = _policy.TRACKER
            _policy.GLOBAL.record(_policy.TRACKER,
                                  _top(self.version) - n_before,
                                  _time.perf_counter() - t0)

        if os.environ.get("DT_TPU_ZONE"):   # explicit dev override
            _zone_merge()
            return
        from ..native import native_ctx_or_none
        ctx = native_ctx_or_none(oplog)
        if ctx is not None:
            # fully-default path: measured policy decides (zone is never
            # chosen before it has measurements, with one exception: a
            # cooldown re-probe after a failure-demotion, which implies
            # zone already ran in this process — see policy.py)
            n_hint = _top(merge_frontier) - _top(self.version)
            if _policy.GLOBAL.choose(n_hint) == _policy.ZONE:
                try:
                    _zone_merge()
                    return
                except Exception as e:
                    # demote the zone engine and fall back: a failed
                    # accelerator path must never fail a merge the
                    # tracker can do in milliseconds. Leave a trail —
                    # otherwise a transient blip and a persistent zone
                    # bug both look like an unexplained slowdown.
                    import warnings
                    warnings.warn(
                        f"zone engine failed ({e.__class__.__name__}: "
                        f"{e}); demoted, falling back to the tracker",
                        RuntimeWarning)
                    _policy.GLOBAL.forget(_policy.ZONE)
            _tracker_merge(ctx)
            return

        # DT_TPU_NO_NATIVE / no library: the pure-Python oracle, always
        xf = oplog.get_xf_operations_full(self.version, merge_frontier)
        self._apply_xf(oplog, xf)
        self.version = list(xf.next_frontier)
        self.last_merge_collisions = xf.collisions
        self.last_merge_engine = "python"

    def _apply_xf(self, oplog: OpLog, rows) -> None:
        """Apply an (lv, op, xf_pos|None) stream to this branch's content —
        the one shared application loop for every host engine."""
        for _lv, op, pos in rows:
            if pos is None:
                continue  # delete already happened
            if op.kind == INS:
                content = oplog.ops.get_run_content(op)
                assert content is not None
                if not op.fwd:
                    content = content[::-1]
                self.content.insert(pos, content)
            else:
                self.content.delete(pos, len(op))

    def merge_tip(self, oplog: OpLog) -> None:
        self.merge(oplog, oplog.version)
