"""Device-mesh parallelism for multi-document and multi-replica workloads.

SURVEY.md §2.9: the reference has no process-level parallelism — its
"distributed system" is the logical peer-sync protocol. The TPU rebuild adds
real data parallelism as a first-class axis:

  * `docs` axis — independent documents sharded across devices (pure data
    parallel; no collectives on the hot path).
  * `graph` axis — one huge causal DAG sharded by run index across devices;
    reachability fixed-point sweeps run locally per shard and exchange
    frontier coverage with `psum`/all-reduce over ICI each round
    (BASELINE.json config 5: 10k-replica fan-in graph).

Everything uses jax.sharding + shard_map so XLA inserts the collectives.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.phases import phase
from ..tpu.batch import replay_batch
from ..tpu.runtime import devices


def make_mesh(n_devices: int | None = None, axis: str = "docs") -> Mesh:
    devs = devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def serve_mesh(n_shards: int | None = None, axis: str = "docs") -> Mesh:
    """1-D `docs` mesh over the device slice the serve tier's shards
    occupy (`serve_shard_devices` wraps shards onto devices; the mesh
    covers the distinct devices actually used, capped at the shard
    count). This is the mesh the flush-window coordinator issues its
    single program over."""
    devs = devices()
    n = len(devs) if n_shards is None else min(max(n_shards, 1),
                                               len(devs))
    return Mesh(np.array(devs[:n]), (axis,))


def serve_shard_devices(n_shards: int):
    """Device placement for the serve/ scheduler's shard banks: shard i
    lives on devices[i % n_devices]. With fewer devices than shards the
    assignment wraps (several logical shards share a chip — the CPU
    simulation path, where conftest/driver force a virtual host device
    count). Each SessionBank then builds and steps its sessions under
    `jax.default_device(...)` of its own device, so per-shard work is
    genuinely placed, not just labeled."""
    devs = devices()
    return [devs[i % len(devs)] for i in range(n_shards)]


def sharded_replay(mesh: Mesh, pos, dlen, ilen, chars, cap: int):
    """Shard the batch axis of replay_batch over the mesh's `docs` axis."""
    sh = NamedSharding(mesh, P("docs"))
    pos, dlen, ilen = (jax.device_put(x, sh) for x in (pos, dlen, ilen))
    chars = jax.device_put(chars, sh)
    fn = jax.jit(partial(replay_batch, cap=cap),
                 in_shardings=(sh, sh, sh, sh),
                 out_shardings=(sh, sh))
    return fn(pos, dlen, ilen, chars)


def pad_edges(packed: dict, n_devices: int):
    """Pad a pack_graph CSR edge list to a multiple of n_devices.

    Padding edges scatter to the drop slot (prun == n) with a -1 LV, so
    they are inert regardless of activity. Returns (src, plv, prun) numpy
    arrays ready to shard."""
    n, m = packed["n"], packed["m"]
    pad_to = max(n_devices, ((m + n_devices - 1) // n_devices) * n_devices)
    src = np.zeros(pad_to, dtype=np.int32)
    plv = np.full(pad_to, -1, dtype=np.int32)
    prun = np.full(pad_to, n, dtype=np.int32)
    src[:m] = np.asarray(packed["edge_src"])
    plv[:m] = np.asarray(packed["edge_plv"])
    prun[:m] = np.asarray(packed["edge_prun"])
    return src, plv, prun


def pad_batch_count(b: int, n_devices: int) -> int:
    """Smallest super-batch size >= b that (a) divides the mesh and
    (b) is n_devices times a power of two — divisibility is what
    `shard_map` needs, the pow2 rounding is what keeps the mesh jit
    cache O(log) in window size (mirroring `_pow2` batch rounding on
    the per-shard path)."""
    from ..tpu.merge_kernel import _pow2
    per_dev = max(-(-max(int(b), 1) // n_devices), 1)
    # _pow2 floors at 2; one row per device is a legal class of its own
    # (same convention as _fused_fn's `bp = 1` for a single doc)
    return n_devices * (1 if per_dev == 1 else _pow2(per_dev))


def pad_batch_to_mesh(pos, dlen, ilen, chars, n_devices: int):
    """Pad a packed super-batch's row axis to `pad_batch_count` rows
    (mirroring `pad_edges`): padding rows carry all-zero ops — no-ops
    through the replay kernel — and the caller pairs them with
    `lens = -1` sentinel rows, so they stay identifiably inert end to
    end regardless of what the window carries. Returns
    (pos, dlen, ilen, chars, bp)."""
    b = pos.shape[0]
    bp = pad_batch_count(b, n_devices)
    if bp == b:
        return pos, dlen, ilen, chars, bp

    def _pad(a):
        out = np.zeros((bp,) + a.shape[1:], dtype=a.dtype)
        out[:b] = a
        return out

    return _pad(pos), _pad(dlen), _pad(ilen), _pad(chars), bp


_mesh_jit_cache = {}
from ..analysis.witness import make_lock as _make_lock
_mesh_jit_lock = _make_lock("mesh_jit", "leaf")


def mesh_flush_fn(mesh: Mesh, b: int, n: int, mi: int, cap: int):
    """The mesh flush-window program: the fused replay body wrapped in
    ONE `shard_map` over the mesh's `docs` axis, jitted with donated
    state buffers. The body is pure data parallel (every doc's scan is
    independent), so each device runs its `b / n_devices` row slice
    locally and XLA inserts zero collectives — N shards' buckets flush
    in a single dispatch. Cache keyed on (mesh, shapes), same O(log^2)
    discipline as the per-shard `_fused_fn` cache; lookups surface as
    devprof jit_cache "mesh" rows."""
    key = (mesh, b, n, mi, cap)
    with _mesh_jit_lock:
        fn = _mesh_jit_cache.get(key)
        from ..obs.devprof import note_jit_lookup
        note_jit_lookup("mesh", fn is not None)
        if fn is None:
            from ..tpu.flush_fuse import make_replay_body
            axis = mesh.axis_names[0]
            body = shard_map(make_replay_body(mi), mesh=mesh,
                             in_specs=(P(axis),) * 6,
                             out_specs=(P(axis), P(axis)))
            fn = jax.jit(body, donate_argnums=(0, 1))
            _mesh_jit_cache[key] = fn
    from ..tpu.steer import STEER
    STEER.note_warm("mesh", mi, cap, b, n)
    return fn


def _home(arr):
    """The one device an array lives on, or None if it is spread."""
    devs = arr.devices()
    return next(iter(devs)) if len(devs) == 1 else None


def _gather_rows(sh: NamedSharding, rows, bp: int, row_shape: tuple,
                 fill: int):
    """Assemble `rows` (each resident on whatever chip its session
    lives on) plus inert padding rows of `fill` into one `[bp, ...]`
    int32 array sharded by `sh`, device-side: every row moves
    chip-to-chip at most once, straight to the mesh device whose slice
    it falls in, and is stacked there. (`jnp.stack` over rows committed
    to different chips is an error, and over uncommitted rows a detour
    through the default device.)"""
    devs = list(sh.mesh.devices.flat)
    per = bp // len(devs)
    blocks = []
    for k, dev in enumerate(devs):
        part = [jax.device_put(r, dev)
                for r in rows[k * per:(k + 1) * per]]
        if len(part) < per:
            pad = jnp.full(row_shape, fill, jnp.int32, device=dev)
            part += [pad] * (per - len(part))
        blocks.append(jnp.stack(part))
    return jax.make_array_from_single_device_arrays(
        (bp,) + row_shape, sh, blocks)


def _rows_at(out, homes):
    """Row i of a docs-sharded result as its own single-device array
    on `homes[i]` (None: wherever it was computed), cut from the shard
    that holds it — never from the global array, whose row slices
    come back replicated on every chip of the mesh."""
    rows = [None] * len(homes)
    for shard in out.addressable_shards:
        start = shard.index[0].start or 0
        for j in range(shard.data.shape[0]):
            i = start + j
            if i < len(homes):
                row = shard.data[j]
                rows[i] = row if homes[i] is None \
                    else jax.device_put(row, homes[i])
    return rows


def mesh_fused_replay(mesh: Mesh, sessions, plans):
    """Replay MANY shards' pending tails in ONE mesh-sharded program.

    `sessions`/`plans` are the fusable rows of a whole flush window —
    every shard's bucket concatenated — all sharing (cap, max_ins).
    The padded shape `(bp, n)` is STEERED onto a warm mesh jit class
    (`tpu/steer.py`) from the `pad_batch_count` / pow2 floors, and
    state assembly is device-resident by default (`parallel/arena.py`):

      * arena fast path — the previous window's donated output arrays
        are reused verbatim when the same session list recurs in the
        same shape class (zero staging, zero allocation);
      * device-side gather — otherwise sessions' resident rows move
        chip-to-chip to the mesh device whose slice they fall in and
        are stacked there (`_gather_rows`), without a host round trip;
        only the host-built op PLAN arrays cross the boundary
        (accounted as purpose="plan").

    Committed rows go back to the chip each session lived on before
    the window (`_rows_at`), so a bank's sessions stay on the bank's
    device and its slot budget counts what that chip really holds.
    Rows are batched in class order, not by placement: a row whose
    session lives on another chip than the one that replays it crosses
    the interconnect once each way.

    With `DEVICE_STAGE` disabled (the `--no-device-stage` control
    arm) the legacy host-numpy staging runs instead and every state
    byte is accounted as purpose="stage" — the A/B that makes the
    staging saving measurable.

    Returns (ok-per-session, device_wait_s, padded_b, staged_bytes);
    `staged_bytes` is the host->device bytes this window's staging
    paid. Per-doc poison and the returned-length fence are
    byte-identical to `fused_replay` (`adopt_results` is shared), so
    the bank's fallback ladder catches violating rows exactly as
    before — and a violating doc in one shard cannot corrupt another
    shard's rows. Padding rows enter with the `lens = -1` sentinel and
    zero ops on EVERY staging path, so they stay identifiably inert."""
    with phase("mesh.replay") as ph:
        return _mesh_fused_replay(mesh, sessions, plans, ph)


def _mesh_fused_replay(mesh: Mesh, sessions, plans, ph):
    """`mesh_fused_replay` under its `mesh.replay` phase `ph`. The
    steps are the host pack, staging (arena hand-back, device-side
    gather or the control arm's host staging), dispatch (jit lookup,
    plan upload, the call), the length fence and adoption (rows back
    to their chips, `adopt_results`, the arena). The row's own counts
    say what the window moved: `rows`, `rows_off_home` (a row whose
    session's chip is not the mesh device whose slice replays it),
    `ici_bytes` (such a row and its length cross the interconnect on
    the way back, and on the way in too when it was gathered),
    `arena_hits` / `arena_misses`, and by capacity class `cap.<cap>.dispatches` / `.docs` / `.padded_rows`."""
    import time

    import jax.numpy as jnp

    from ..obs.devprof import note_transfer
    from ..tpu.flush_fuse import adopt_results, pack_plans
    from ..tpu.merge_kernel import _pow2
    from ..tpu.steer import STEER
    from . import arena as _arena

    b = len(sessions)
    assert b == len(plans) and b >= 1
    cap = sessions[0].cap
    mi = sessions[0].max_ins
    ndev = int(mesh.devices.size)
    ph.step("mesh.pack")
    n0 = _pow2(max(max(p.n_ops for p in plans), 1))
    bp0 = pad_batch_count(b, ndev)
    # warm mesh classes are mesh-legal by construction; multiple=ndev
    # keeps a hypothetical second mesh in-process from cross-matching
    bp, n = STEER.snap("mesh", bp0, n0, mi, cap, multiple=ndev)
    pos, dlen, ilen, chars = pack_plans(plans, n, mi, bp)
    plan_bytes = (pos.nbytes + dlen.nbytes + ilen.nbytes + chars.nbytes)
    note_transfer(plan_bytes, rung="mesh", purpose="plan")
    staged_bytes = plan_bytes
    sh = NamedSharding(mesh, P(mesh.axis_names[0]))
    ph.step("mesh.stage")
    # where each session lives, and the mesh device whose slice
    # replays its row (rows are batched in class order)
    homes = [_home(s.docs) for s in sessions]
    mesh_devs = list(mesh.devices.flat)
    per = bp // ndev
    off_home = sum(h is not None and h != mesh_devs[i // per]
                   for i, h in enumerate(homes))
    crossings = off_home            # every such row goes back home
    reuse = _arena.acquire(mesh, cap, mi, sessions, bp) \
        if _arena.DEVICE_STAGE.enabled else None
    if reuse is not None:
        # donated-buffer fast path: window k's outputs are window
        # k+1's inputs, already sharded over this mesh — no staging
        docs_d, lens_d = reuse
        ph.count("arena_hits")
    elif _arena.DEVICE_STAGE.enabled:
        # device-side gather: resident rows never visit host numpy
        docs_d = _gather_rows(sh, [s.docs for s in sessions], bp,
                              (cap,), 0)
        lens_d = _gather_rows(sh, [jnp.asarray(s.lens, jnp.int32)
                                   for s in sessions], bp, (), -1)
        ph.count("arena_misses")
        crossings += off_home       # and came over chip-to-chip
    else:
        # control arm: legacy host staging — every resident byte
        # round-trips through numpy and is accounted as staged
        docs_h = np.zeros((bp, cap), np.int32)
        lens_h = np.full((bp,), -1, np.int32)   # padding sentinels
        for i, s in enumerate(sessions):
            docs_h[i] = np.asarray(s.docs)
            lens_h[i] = int(np.asarray(s.lens))
        note_transfer(docs_h.nbytes + lens_h.nbytes,
                      rung="mesh", purpose="stage")
        staged_bytes += docs_h.nbytes + lens_h.nbytes
        docs_d = jax.device_put(jnp.asarray(docs_h), sh)
        lens_d = jax.device_put(jnp.asarray(lens_h), sh)
    ph.step("mesh.dispatch")
    fn = mesh_flush_fn(mesh, bp, n, mi, cap)
    out_docs, out_lens = fn(docs_d, lens_d,
                            *(jax.device_put(jnp.asarray(x), sh)
                              for x in (pos, dlen, ilen, chars)))
    # the length fetch is the completion fence + parity cross-check
    ph.step("mesh.fence")
    t_fence = time.perf_counter()
    got = np.asarray(out_lens)
    device_s = time.perf_counter() - t_fence
    ph.step("mesh.adopt")
    # each committed row goes back to the chip its session lives on
    # (its bank's): a plain `out_docs[i]` of the sharded result comes
    # back replicated over the whole mesh — one copy per chip
    ok = adopt_results(sessions, plans, _rows_at(out_docs, homes),
                       _rows_at(out_lens, homes), got)
    if _arena.DEVICE_STAGE.enabled:
        _arena.adopt(mesh, cap, mi, out_docs, out_lens, sessions,
                     ok, bp)
    ph.count("rows", b)
    ph.count("rows_off_home", off_home)
    ph.count("ici_bytes", crossings * (4 * cap + 4))
    ph.count(f"cap.{cap}.dispatches")
    ph.count(f"cap.{cap}.docs", b)
    ph.count(f"cap.{cap}.padded_rows", bp)
    return ok, device_s, bp, staged_bytes


def sharded_reach_fixed_point(mesh: Mesh, starts, edge_src, edge_plv,
                              edge_prun, reach0):
    """Causal-graph reachability with the EDGE list sharded across devices.

    Each device owns a contiguous slice of (run, parent) edges; the reach
    vector is replicated. One round = local scatter-max relaxation +
    all-reduce(max) over ICI. Rounds iterate to a fixed point (the
    cross-shard frontier propagation of SURVEY.md §2.9). Edge sharding —
    not run sharding — keeps a 10k-way fan-in merge balanced: its 10k
    edges spread evenly over the mesh instead of landing on one run's
    device.

    starts: int32 [n]; edge_*: int32 [m] (m divisible by the mesh size,
    see pad_edges); reach0: int32 [n].
    """
    n = starts.shape[0]
    axis = mesh.axis_names[0]

    @partial(shard_map, mesh=mesh,
             in_specs=(P(None), P(axis), P(axis), P(axis), P(None)),
             out_specs=P(None))
    def one_round(starts_r, src_l, plv_l, prun_l, reach):
        active = (reach >= starts_r)[src_l]
        contrib = jnp.where(active, plv_l, -1)
        tgt = jnp.where(active, prun_l, jnp.int32(n))
        upd = jnp.full((n,), -1, dtype=reach.dtype).at[tgt].max(
            contrib, mode="drop")
        # Exchange shard contributions over ICI.
        upd = jax.lax.pmax(upd, axis)
        return jnp.maximum(reach, upd)

    def cond(state):
        return state[1]

    def body(state):
        reach, _ = state
        new = one_round(starts, edge_src, edge_plv, edge_prun, reach)
        return new, jnp.any(new != reach)

    reach, _ = jax.lax.while_loop(cond, body, (reach0, jnp.array(True)))
    return reach


def multichip_merge_step(mesh: Mesh, pos, dlen, ilen, chars, cap: int,
                         starts, edge_src, edge_plv, edge_prun, reach0):
    """One full sharded "step": sharded multi-doc replay (data parallel) +
    sharded causal-graph propagation (graph parallel with collectives).
    This is the step that `__graft_entry__.dryrun_multichip` jits over an
    n-device mesh."""
    docs, lens = sharded_replay(mesh, pos, dlen, ilen, chars, cap)
    reach = sharded_reach_fixed_point(mesh, starts, edge_src, edge_plv,
                                      edge_prun, reach0)
    return docs, lens, reach
