"""Plain oplog column extractions for the device checkout.

The agent-rank and insert-arena offset columns `tpu/merge_kernel.py`
packs into a `DeviceDoc` (`_agent_keys` / `_arena_offsets` are its
aliases): numpy over the oplog's run tables, no device code.
"""

from __future__ import annotations

import numpy as np

from ..native.core import UNDERWATER
from ..text.op import INS


def agent_key_columns(oplog, lvs: np.ndarray):
    """(name-rank, seq) per LV, vectorized over the agent-assignment runs.

    Reference tie-break: agent NAME order then seq
    (causalgraph/agent_assignment/mod.rs:163)."""
    aa = oplog.cg.agent_assignment
    gr = aa.global_runs
    lv0 = np.asarray([r[0] for r in gr], dtype=np.int64)
    ag = np.asarray([r[2] for r in gr], dtype=np.int64)
    sq0 = np.asarray([r[3] for r in gr], dtype=np.int64)
    o = np.argsort(lv0)
    lv0, ag, sq0 = lv0[o], ag[o], sq0[o]
    name_rank = np.asarray(np.argsort(np.argsort(aa.agent_names)))
    j = np.clip(np.searchsorted(lv0, lvs, side="right") - 1, 0, len(lv0) - 1)
    agent = np.where(lvs >= UNDERWATER, 0, name_rank[ag[j]])
    seq = np.where(lvs >= UNDERWATER, 0, sq0[j] + (lvs - lv0[j]))
    return agent, seq


def arena_offset_columns(oplog, lvs: np.ndarray) -> np.ndarray:
    """Insert-arena char offset of each LV (must be insert LVs)."""
    runs = oplog.ops.runs
    lv0 = np.asarray([r.lv for r in runs], dtype=np.int64)
    cp0 = np.asarray(
        [r.content_pos[0] if (r.kind == INS and r.content_pos is not None)
         else -1 for r in runs], dtype=np.int64)
    j = np.clip(np.searchsorted(lv0, lvs, side="right") - 1, 0, len(lv0) - 1)
    return cp0[j] + (lvs - lv0[j])
