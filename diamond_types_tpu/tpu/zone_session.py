"""Device-resident incremental merge sessions.

The realtime pattern — a live document receiving a stream of small edits
from several peers, each merged immediately (reference hot path:
src/list/merge.rs:63-96) — must not pay a full document re-upload per
merge (VERDICT r2 next-step #4). A `DeviceZoneSession` keeps the zone
kernel's ENTIRE carry (state matrix, rank order, origin metadata, key
planes) resident on the device and treats each incremental merge as a
few more tape steps continued from that carry: the host ships only the
delta (the new entries' composed micro-tape, a handful of KB), and the
jitted step donates its input buffers so the state updates in place.

Row tracking: the session holds one state row per live branch head
(each peer's last version). A new run whose parents match tracked rows
applies directly (fork/max exactly like the plan compiler would); a run
anchored at an untracked version triggers `resync()` — a full rebuild
whose plan PINS a state row at each agent's head (plan2 pin_lvs), so
after one rebuild every active branch is tracked again. Slot capacity is
pre-allocated with headroom; growth also resyncs.

Everything reuses the zone kernel verbatim: the same step function, the
same tape schema, the same YjsMod semantics — a session is just a scan
whose xs arrive over time.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..listmerge.compose import compose_entry

from ..listmerge.zone_np import ZonePrep, prepare_zone
from .merge_kernel import _pow2
from .zone_kernel import (BIG32, OP_APPLY, OP_FORK, OP_MAX, ZoneTape,
                          _pad_tape_xs, auto_slice_steps, init_zone_carry,
                          make_zone_step, pack_zone_tape, slice_tape_xs)

_sess_jit_cache = {}


def _micro_fn(W: int, plen: int, n_rows: int, MB: int, MC: int, MD: int,
              T: int):
    """Jitted micro-tape continuation with donated carry buffers."""
    import jax

    key = (W, plen, n_rows, MB, MC, MD, T)
    fn = _sess_jit_cache.get(key)
    from ..obs.devprof import note_jit_lookup
    note_jit_lookup("micro", fn is not None)
    if fn is None:
        from jax import lax

        step = make_zone_step(W, plen, n_rows, MB, MC, MD)

        def run(carry, xs):
            final, _ = lax.scan(step, carry, xs)
            return final

        fn = jax.jit(run, donate_argnums=0)
        _sess_jit_cache[key] = fn
    return fn


_tip_jit_cache = {}


def _tip_row_fn(W: int, n_rows: int):
    """fn(carry, r): state[r] <- merged-tip visibility (1 = placed and
    never deleted, 2 = placed and deleted, 0 = unplaced)."""
    import jax

    # The 2-tuple key is deliberate: the tip-row builder has no tape
    # dims (no op batch to shape-specialise), and jit retraces per
    # carry shape anyway — the key only scopes the lookup for devprof
    # hit accounting.
    key = (W, n_rows)
    fn = _tip_jit_cache.get(key)  # dt-lint: ignore[jit-cache-key]
    from ..obs.devprof import note_jit_lookup
    note_jit_lookup("tip", fn is not None)
    if fn is None:
        import jax.numpy as jnp
        from jax import lax

        def build(carry, r):
            state, snap, rank, ordv, ol_id, orr_id, ever, m, ak, sk = carry
            row = jnp.where(rank < BIG32,
                            jnp.where(ever == 0, 1, 2), 0).astype(jnp.uint8)
            state = lax.dynamic_update_index_in_dim(
                state, row, jnp.clip(r, 0, n_rows - 1), 0)
            return (state, snap, rank, ordv, ol_id, orr_id, ever, m, ak, sk)

        fn = jax.jit(build, donate_argnums=0)
        _tip_jit_cache[key] = fn  # dt-lint: ignore[jit-cache-key]
    return fn


class DeviceZoneSession:
    """A live document resident on the device (see module docstring)."""

    def __init__(self, oplog, n_rows: int = 8, headroom: float = 2.0,
                 max_blocks: int = 4, max_chars: int = 256,
                 max_dels: int = 8, row_sharding=None):
        self.oplog = oplog
        self.n_rows = n_rows
        self.headroom = headroom
        self.MB, self.MC, self.MD = max_blocks, max_chars, max_dels
        # Multi-chip: a jax.sharding.NamedSharding for the version-row
        # axis of the session state — rows (tracked branches) spread over
        # the mesh; per-slot arrays are replicated. jit propagates the
        # placement through every micro-tape continuation, and donation
        # keeps it across syncs.
        self.row_sharding = row_sharding
        self.resyncs = -1          # first build counts up to 0
        self.merges = 0
        self._lru: Dict[Tuple[int, ...], int] = {}
        self._clock = 0
        self.resync()

    # ---- full (re)build --------------------------------------------------

    def resync(self) -> None:
        """Rebuild device state from scratch, pinning one state row per
        agent head so every active branch is immediately tracked."""
        import jax.numpy as jnp

        self.resyncs += 1
        ol = self.oplog
        # pin each agent's last version (if it lands in the zone)
        aa = ol.cg.agent_assignment
        heads: List[int] = []
        for agent in range(len(aa.agent_names)):
            last = aa.last_lv_of(agent) if hasattr(aa, "last_lv_of") else \
                self._agent_last_lv(agent)
            if last is not None:
                heads.append(last)
        prep = prepare_zone(ol, pin_lvs=tuple(heads))
        self.prep = prep
        W_cap = _pow2(max(int(prep.W * self.headroom), prep.W + 1024))
        n_rows = max(self.n_rows, prep.plan.indexes_used)
        if self.row_sharding is not None:
            # the sharded row axis must divide evenly over the mesh axes
            # named in its spec (a real corpus's plan can need any
            # number of index rows — e.g. friendsforever needs 12)
            m = 1
            spec0 = self.row_sharding.spec[0] \
                if len(self.row_sharding.spec) else None
            names = (spec0,) if isinstance(spec0, str) else (spec0 or ())
            for name in names:
                m *= int(self.row_sharding.mesh.shape[name])
            n_rows = ((n_rows + m - 1) // m) * m
        self.W_cap = W_cap
        self.plen = prep.plen

        self._agent_epoch = tuple(ol.cg.agent_assignment.agent_names)
        # growable host-side tables (slot map, pool, key arrays). The
        # run lists grow as PYTHON lists; the searchsorted arrays
        # regenerate lazily once per sync, not O(n) per appended run
        self._lv0_list = list(prep.ins_lv0)
        self._cum_list = list(prep.ins_cum)
        self._slot_arrays_dirty = True
        self.W_used = prep.W
        self.pool = np.zeros(W_cap, dtype=np.int32)
        self.pool[:prep.W] = prep.pool
        agent_k = np.zeros(W_cap, dtype=np.int32)
        seq_k = np.zeros(W_cap, dtype=np.int32)
        agent_k[:prep.W] = prep.agent_k
        seq_k[:prep.W] = prep.seq_k

        tape = pack_zone_tape(prep, self.MB, self.MC, self.MD)
        tape = self._retarget(tape, W_cap)
        carry = init_zone_carry(W_cap, prep.plen, n_rows, agent_k, seq_k)
        if self.row_sharding is not None:
            import jax
            carry = (jax.device_put(carry[0], self.row_sharding),) \
                + tuple(carry[1:])
        self.carry = self._run_tape(carry, tape, n_rows)

        # row registry: pinned agent-head rows + their frontiers
        self.row_of: Dict[Tuple[int, ...], int] = {}
        self.free_rows = set(range(n_rows))
        for lv, row in prep.plan.pinned_rows.items():
            self.row_of[(lv,)] = row
            self.free_rows.discard(row)
        self.n_rows_eff = n_rows
        self.synced_to = len(ol)
        self._lru.clear()          # stale frontiers died with the old rows
        self._keys_cache = None
        # always track the merged TIP as a row (derivable from rank/ever:
        # visible = placed and never deleted): linear histories have no
        # zone entries to pin, and most realtime ops parent on the tip
        tipkey = tuple(sorted(int(x) for x in ol.version))
        if tipkey and tipkey not in self.row_of and self.free_rows:
            r = min(self.free_rows)
            self.free_rows.discard(r)
            self.carry = _tip_row_fn(self.W_cap, self.n_rows_eff)(
                self.carry, r)
            self.row_of[tipkey] = r

    def _run_tape(self, carry, tape: ZoneTape, n_rows: int):
        """Execute `tape` on top of `carry`, with per-dispatch device
        time bounded on tpu (auto_slice_steps — per-step cost is
        ~linear in W x n_rows): the v5e runtime of 2026-07-31 killed
        any single program past ~60 s (a bound not measured on this
        machine), which a grown session's resync tape — or a large
        sync() backlog (e.g. a bulk import appended onto a tracked
        head) — would cross as one whole-tape program. Pad
        steps are self-FORK no-ops, so the sliced and whole-tape paths
        are bit-identical (pinned by tests via DT_SESSION_SLICE: a
        positive value forces that slice length on any backend, 0
        forces whole-tape; empty/unset picks the backend default)."""
        import jax
        import jax.numpy as jnp

        sl_env = os.environ.get("DT_SESSION_SLICE")
        if sl_env:
            slice_steps = max(0, int(sl_env))
        else:
            slice_steps = (auto_slice_steps(tape, n_rows)
                           if jax.default_backend() == "tpu" else 0)
        T = tape.op.shape[0]
        if slice_steps and slice_steps < _pow2(T):
            S, xs_slices = slice_tape_xs(tape, slice_steps)
            fn = _micro_fn(tape.W, tape.plen, n_rows, self.MB, self.MC,
                           self.MD, S)
            for xs in xs_slices:
                carry = fn(carry, xs)
            return carry
        fn = _micro_fn(tape.W, tape.plen, n_rows, self.MB, self.MC,
                       self.MD, _pow2(T))
        padded = _pad_tape_xs(tape)
        from ..obs.devprof import PROFILER
        if PROFILER.enabled:   # host->device tape upload, one flush
            PROFILER.note_transfer(sum(int(np.asarray(v).nbytes)
                                       for v in padded.values()))
        xs = {k: jnp.asarray(v) for k, v in padded.items()}
        return fn(carry, xs)

    def _take_row(self, exclude) -> Optional[int]:
        """A free state row, evicting the least-recently-used tracked
        frontier when the pool is dry (an evicted frontier referenced
        later costs one resync — graceful degradation)."""
        if self.free_rows:
            r = min(self.free_rows)
            self.free_rows.discard(r)
            return r
        victims = [(self._lru.get(k, 0), k) for k, v in self.row_of.items()
                   if v not in exclude]
        if not victims:
            return None
        _, k = min(victims)
        r = self.row_of.pop(k)
        self._lru.pop(k, None)
        return r

    def _touch_key(self, key) -> None:
        self._clock += 1
        self._lru[key] = self._clock

    def _keys(self, lvs: np.ndarray):
        """(agent name rank, seq) per LV with the run tables cached per
        sync epoch — _agent_keys rebuilds them from scratch on every call,
        which is O(total history) per entry on the hot path."""
        aa = self.oplog.cg.agent_assignment
        gr = aa.global_runs
        cache = self._keys_cache
        if cache is None or cache[0] != len(gr):
            lv0 = np.asarray([r[0] for r in gr], dtype=np.int64)
            ag = np.asarray([r[2] for r in gr], dtype=np.int64)
            sq0 = np.asarray([r[3] for r in gr], dtype=np.int64)
            o = np.argsort(lv0)
            name_rank = np.asarray(np.argsort(np.argsort(aa.agent_names)))
            cache = (len(gr), lv0[o], ag[o], sq0[o], name_rank)
            self._keys_cache = cache
        _, lv0, ag, sq0, name_rank = cache
        lvs = np.asarray(lvs, dtype=np.int64)
        j = np.clip(np.searchsorted(lv0, lvs, side="right") - 1, 0,
                    len(lv0) - 1)
        return name_rank[ag[j]], sq0[j] + (lvs - lv0[j])

    def _agent_last_lv(self, agent: int) -> Optional[int]:
        aa = self.oplog.cg.agent_assignment
        best = None
        for (_lv0, lv_end, ag, _sq) in aa.global_runs:
            if ag == agent:
                end = lv_end - 1
                best = end if best is None or end > best else best
        return best

    def _retarget(self, tape: ZoneTape, W_cap: int) -> ZoneTape:
        """A tape packed for W slots runs unchanged at W_cap capacity
        (slot ids are absolute; only the padded width differs)."""
        tape.W = W_cap
        return tape

    # ---- incremental path ------------------------------------------------

    def _slot_of_lv(self, lvs: np.ndarray) -> np.ndarray:
        if self._slot_arrays_dirty:
            self.ins_lv0 = np.asarray(self._lv0_list, dtype=np.int64)
            self.ins_cum = np.asarray(self._cum_list, dtype=np.int64)
            self._slot_arrays_dirty = False
        j = np.searchsorted(self.ins_lv0, lvs, side="right") - 1
        return self.plen + self.ins_cum[j] + (lvs - self.ins_lv0[j])

    def _alloc_slots(self, entry_span) -> bool:
        """Extend the slot map/pool/keys with the entry's insert runs.
        Returns False when capacity would overflow (caller resyncs)."""
        from ..text.op import INS
        new = []
        for piece in self.oplog.ops.iter_range(entry_span):
            if piece.kind == INS:
                new.append((piece.lv, len(piece),
                            self.oplog.ops.content_slice(piece.lv,
                                                         len(piece))))
        total = sum(n for _, n, _ in new)
        if self.W_used + total > self.W_cap:
            return False
        for (lv, n, content) in new:
            slot0 = self.W_used
            self._lv0_list.append(lv)
            self._cum_list.append(slot0 - self.plen)
            self._slot_arrays_dirty = True
            arr = np.frombuffer(content.encode("utf-32-le"),
                                dtype=np.int32)
            self.pool[slot0:slot0 + n] = arr
            self.W_used += n
        return True

    def sync(self) -> int:
        """Fold every op appended to the oplog since the last sync into
        the device state. Returns the number of micro-steps executed
        (0 = nothing new). Resyncs transparently when needed."""
        import jax.numpy as jnp

        ol = self.oplog
        if self.synced_to >= len(ol):
            return 0
        # agent NAME RANKS are relative to the registered-name set; a new
        # agent shifts existing ranks, and the carry's key planes hold the
        # old epoch's ranks — rebuild before they can disagree
        if tuple(ol.cg.agent_assignment.agent_names) != self._agent_epoch:
            self.resync()
            return self.sync()
        g = ol.cg.graph
        # split the new span into entries (same-parents runs)
        steps: List[dict] = []
        lo = self.synced_to
        end = len(ol)
        spans: List[Tuple[int, int, Tuple[int, ...]]] = []
        v = lo
        while v < end:
            i = g.find_idx(v)
            take = min(end, g.ends[i])
            parents = tuple(g.parents_at(v)) if v == g.starts[i] \
                else (v - 1,)
            spans.append((v, take, parents))
            v = take

        for (s, e, parents) in spans:
            key = tuple(sorted(parents))
            # source rows: the exact frontier if tracked, else the
            # per-tip rows of a multi-parent frontier
            if key in self.row_of:
                srcs = [self.row_of[key]]
            else:
                srcs = [self.row_of.get((p,)) for p in sorted(parents)]
                if not srcs or any(r is None for r in srcs):
                    # untracked frontier — including parents == [] (a
                    # concurrent root-anchored op): rebuild
                    self.resync()
                    return self.sync()
            # apply on a FRESH row (fork + max joins): source rows stay
            # tracked — two branches forking the same frontier is the
            # normal realtime shape and must not force a rebuild
            row = self._take_row(exclude=set(srcs))
            if row is None or not self._alloc_slots((s, e)):
                self.resync()
                return self.sync()
            pre_ops = [(OP_FORK, srcs[0], row)] + \
                [(OP_MAX, r, row) for r in srcs[1:]]
            ce = compose_entry(ol, (s, e))
            steps.extend(self._pack_entry(ce, row, pre_ops))
            self.row_of[(e - 1,)] = row
            self._touch_key((e - 1,))

        if steps:
            tape = self._steps_to_tape(steps)
            self.carry = self._run_tape(self.carry, tape,
                                        self.n_rows_eff)
            self.merges += 1
        self.synced_to = end
        return len(steps)

    def _pack_entry(self, ce, row: int, pre_ops: List[tuple]
                    ) -> List[dict]:
        """Entry -> micro-steps via the SAME packer as whole documents
        (zone_kernel.entry_steps), against the session's growable slot
        map and live agent-key resolution."""
        from .zone_kernel import entry_steps
        steps: List[dict] = []
        for (op, a, b) in pre_ops:
            steps.append(dict(op=op, a=a, b=b, snap=0, blocks=[],
                              chars=[], dels=[], n_chars=0))
        cur = dict(op=OP_APPLY, a=row, b=0, snap=1, blocks=[], chars=[],
                   dels=[], n_chars=0)
        steps.append(cur)

        def next_sub():
            s = dict(op=OP_APPLY, a=row, b=0, snap=0, blocks=[],
                     chars=[], dels=[], n_chars=0)
            steps.append(s)
            return s

        entry_steps(ce, self._slot_of_lv, self._keys, None,
                    self.MB, self.MC, self.MD, cur, next_sub)
        return steps

    def _steps_to_tape(self, steps: List[dict]) -> ZoneTape:
        from .zone_kernel import _fill_tape
        return _fill_tape(steps, self.W_cap, self.plen, self.n_rows_eff,
                          self.pool[:self.W_used], self.MB, self.MC,
                          self.MD)

    # ---- reads -----------------------------------------------------------

    def text(self) -> str:
        """Fetch and assemble the merged document."""
        rank = np.asarray(self.carry[2])
        ever = np.asarray(self.carry[6])
        live = int((rank < int(BIG32)).sum())
        order = np.argsort(rank, kind="stable")[:live]
        vis = ever[order] == 0
        return self.pool[order[vis]].astype(np.int32).tobytes() \
            .decode("utf-32-le")

    def touch(self):
        """Force completion of pending device work with a tiny transfer
        (per-merge latency benches time sync()+touch())."""
        return np.asarray(self.carry[7])   # m: a scalar

    def footprint_slots(self) -> int:
        """Device-residency cost of this session in int32 slots, for the
        serve/ bank's capacity accounting: the state matrix dominates
        (n_rows x W_cap), plus the per-slot planes (rank, order, origin
        ids x2, ever, agent key, seq key — 7 more W_cap vectors). Host
        pool/key tables are not counted; the budget models the chip."""
        return int(self.W_cap) * (int(self.n_rows_eff) + 7)
