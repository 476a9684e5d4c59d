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


def home_blocks(mesh: Mesh, sessions):
    """A dispatch's layout: for every device of `mesh`, in mesh order,
    the indexes of the `sessions` whose row lives on it. Slot `i` of a
    `[bp, ...]` batch lies on mesh device `i // (bp / ndev)`
    (`mesh_flush_fn`'s `shard_map`), so a block replays on the chip
    that already holds its rows. A session with no single home, or
    one that is no device of this mesh, joins the emptiest block and
    crosses the interconnect; the second value is the set of those."""
    at = {dev: k for k, dev in enumerate(mesh.devices.flat)}
    blocks = [[] for _ in at]
    astray = []
    for i, s in enumerate(sessions):
        k = at.get(_home(s.docs))
        (astray if k is None else blocks[k]).append(i)
    for i in astray:
        min(blocks, key=len).append(i)
    return blocks, set(astray)


def block_classes(ndev: int, rows: int):
    """The block sizes (rows a chip) of the batch classes
    `pad_batch_count` gives a mesh of `ndev` devices for up to `rows`
    rows a dispatch."""
    return sorted({pad_batch_count(b, ndev) // ndev
                   for b in range(1, max(int(rows), 1) + 1)})


_pad_pairs = {}


def _pad_pair(dev, cap: int):
    """The inert row and its `lens = -1` sentinel on `dev`, committed
    there: what fills a block up to its class. One pair a (device,
    cap); the stack donates nothing, so it is never consumed."""
    key = (dev, int(cap))
    with _mesh_jit_lock:
        pair = _pad_pairs.get(key)
    if pair is None:
        pair = (jax.device_put(np.zeros((cap,), np.int32), dev),
                jax.device_put(np.int32(-1), dev))
        with _mesh_jit_lock:
            pair = _pad_pairs.setdefault(key, pair)
    return pair


def _at(dev, x):
    """`x` committed to `dev`: itself where it is (a row the last
    window handed back), a copy from another chip else. A row as a
    build leaves it is on its bank's chip and not committed to it;
    committing it there moves nothing, and the row programs then meet
    one kind of argument whatever the block holds."""
    if x.committed and _home(x) == dev:
        return x
    return jax.device_put(x, dev)


def _stack_blocks(sh: NamedSharding, blocks, per: int, cap: int):
    """The `[ndev x per, cap]` batch and its lengths from `blocks`,
    one `(rows, lens)` a mesh device: ONE `jit_dt_stack_rows` a chip
    (`flush_fuse._row_programs`), on that chip, over its rows and the
    padding pair, and the blocks joined into the array `sh` shards
    without a copy."""
    from ..tpu.flush_fuse import _row_programs
    stack, _unstack = _row_programs()
    docs, lens = [], []
    for dev, (rows, row_lens) in zip(sh.mesh.devices.flat, blocks):
        pad_row, pad_len = _pad_pair(dev, cap)
        fill = per - len(rows)
        d, n = stack(tuple(_at(dev, r) for r in rows) + (pad_row,) * fill,
                     tuple(_at(dev, x) for x in row_lens)
                     + (pad_len,) * fill)
        docs.append(d)
        lens.append(n)
    bp = per * len(docs)
    return (jax.make_array_from_single_device_arrays((bp, cap), sh, docs),
            jax.make_array_from_single_device_arrays((bp,), sh, lens))


def _cut_blocks(out_docs, out_lens):
    """Every slot of a docs-sharded result as a row and a length of
    its own, on the chip that computed it: ONE `jit_dt_unstack_rows`
    a chip over its shard, never a slice of the global array (whose
    rows come back replicated over the mesh)."""
    from ..tpu.flush_fuse import _row_programs
    _stack, unstack = _row_programs()
    lens_at = {sh.device: sh.data for sh in out_lens.addressable_shards}
    rows = [None] * out_docs.shape[0]
    row_lens = list(rows)
    for shard in out_docs.addressable_shards:
        lo = shard.index[0].start or 0
        r, n = unstack(shard.data, lens_at[shard.device])
        rows[lo:lo + len(r)] = r
        row_lens[lo:lo + len(n)] = n
    return rows, row_lens


def warm_block_programs(devs, cap: int, pers) -> None:
    """Compile the two row programs of a mesh dispatch on each of
    `devs` for blocks of `pers` rows of capacity class `cap`, by
    running them on padding: a live window's block, however uneven the
    dispatch, is then never the first of its size on its chip."""
    from ..tpu.flush_fuse import _row_programs
    stack, unstack = _row_programs()
    for dev in devs:
        pad_row, pad_len = _pad_pair(dev, cap)
        for per in pers:
            jax.block_until_ready(
                unstack(*stack((pad_row,) * per, (pad_len,) * per)))


def mesh_fused_replay(mesh: Mesh, sessions, plans):
    """Replay MANY shards' pending tails in ONE mesh-sharded program.

    `sessions`/`plans` are fusable rows of a flush window — every
    shard's bucket concatenated — all sharing (cap, max_ins). The
    batch is laid out by HOME CHIP (`home_blocks`): chip k's block of
    slots holds the sessions whose rows live on mesh device k, padded
    to `per` rows, the power of two at or above the largest block, so
    a row is replayed where it lives and crosses nothing. The padded
    shape `(ndev x per, n)` is STEERED onto a warm mesh jit class
    (`tpu/steer.py`), and state assembly is device-resident by default
    (`parallel/arena.py`):

      * arena fast path — the previous window's donated output arrays
        are reused verbatim when the same sessions recur in the same
        slots of the same shape class (zero staging, zero allocation);
      * one stack a chip — otherwise each chip's block is stacked on
        that chip from its resident rows and lengths by one
        `jit_dt_stack_rows` (`_stack_blocks`) and the blocks become
        the global array as they lie; only the host-built op PLAN
        arrays cross the host boundary, straight to their sharding
        (accounted as purpose="plan").

    After the replay each chip's shard is cut by one
    `jit_dt_unstack_rows` into rows of their own (`_cut_blocks`),
    which are at home already: a dispatch is at most `2 x ndev + 1`
    device programs whatever it holds. Only a session with no home on
    this mesh is moved, to the emptiest block and back where it came
    from, and only such a one is counted `rows_off_home`. Nothing is
    donated into a stack: a session that fails the fence keeps its
    row.

    With `DEVICE_STAGE` disabled (the `--no-device-stage` control
    arm) the legacy host-numpy staging runs instead, in the same slot
    order, and every state byte is accounted as purpose="stage" — the
    A/B that makes the staging saving measurable.

    Returns (ok-per-session, device_wait_s, padded_b, staged_bytes);
    `staged_bytes` is the host->device bytes this window's staging
    paid. Per-doc poison and the returned-length fence are
    byte-identical to `fused_replay` (`adopt_results` is shared), so
    the bank's fallback ladder catches violating rows exactly as
    before — and a violating doc in one shard cannot corrupt another
    shard's rows. Padding slots, between the blocks and not only at
    the batch's end, enter with the `lens = -1` sentinel and zero ops
    on EVERY staging path, so they stay identifiably inert."""
    with phase("mesh.replay") as ph:
        return _mesh_fused_replay(mesh, sessions, plans, ph)


def _mesh_fused_replay(mesh: Mesh, sessions, plans, ph):
    """`mesh_fused_replay` under its `mesh.replay` phase `ph`. The
    steps are the host pack (layout, plan arrays in slot order),
    staging (arena hand-back, one stack a chip or the control arm's
    host staging), dispatch (jit lookup, plan upload, the call, and
    the cut of each chip's shard queued behind it), the length fence
    and adoption (`adopt_results` in session order, a homeless row's
    trip back, the arena). The row's own counts say what the window
    moved: `rows`, `rows_off_home` (a row whose session has no home
    on this mesh, replayed on the emptiest block's chip), `ici_bytes`
    (such a row and its length cross the interconnect on the way in
    when it was stacked, and on the way back where it has a home),
    `arena_hits` / `arena_misses`, and by capacity class
    `cap.<cap>.dispatches` / `.docs` / `.padded_rows`."""
    import time

    from ..obs.devprof import note_transfer
    from ..tpu.flush_fuse import _empty_plan, adopt_results, pack_plans
    from ..tpu.merge_kernel import _pow2
    from ..tpu.steer import STEER
    from . import arena as _arena

    b = len(sessions)
    assert b == len(plans) and b >= 1
    cap = sessions[0].cap
    mi = sessions[0].max_ins
    ndev = int(mesh.devices.size)
    ph.step("mesh.pack")
    blocks, astray = home_blocks(mesh, sessions)
    n0 = _pow2(max(max(p.n_ops for p in plans), 1))
    bp0 = pad_batch_count(ndev * max(len(blk) for blk in blocks), ndev)
    # warm mesh classes are mesh-legal by construction; multiple=ndev
    # keeps a hypothetical second mesh in-process from cross-matching
    bp, n = STEER.snap("mesh", bp0, n0, mi, cap, multiple=ndev)
    per = bp // ndev
    # the slot of each session is this side's to keep: the plan arrays
    # are filled in slot order, the results read back through it
    slots = [0] * b
    for k, blk in enumerate(blocks):
        for j, i in enumerate(blk):
            slots[i] = k * per + j
    by_slot = [_empty_plan((), 0, 0, mi)] * bp
    for i, slot in enumerate(slots):
        by_slot[slot] = plans[i]
    pos, dlen, ilen, chars = pack_plans(by_slot, n, mi, bp)
    plan_bytes = (pos.nbytes + dlen.nbytes + ilen.nbytes + chars.nbytes)
    note_transfer(plan_bytes, rung="mesh", purpose="plan")
    staged_bytes = plan_bytes
    sh = NamedSharding(mesh, P(mesh.axis_names[0]))
    ph.step("mesh.stage")
    # a row with a home that is not of this mesh goes back to it
    homes = {i: _home(sessions[i].docs) for i in astray}
    crossings = sum(h is not None for h in homes.values())
    reuse = _arena.acquire(mesh, cap, mi, sessions, bp, slots) \
        if _arena.DEVICE_STAGE.enabled else None
    if reuse is not None:
        # donated-buffer fast path: window k's outputs are window
        # k+1's inputs, already sharded over this mesh — no staging
        docs_d, lens_d = reuse
        ph.count("arena_hits")
    elif _arena.DEVICE_STAGE.enabled:
        # one stack a chip: resident rows never visit host numpy
        docs_d, lens_d = _stack_blocks(
            sh, [([sessions[i].docs for i in blk],
                  [sessions[i].lens for i in blk]) for blk in blocks],
            per, cap)
        ph.count("arena_misses")
        crossings += len(astray)    # and came over chip-to-chip
    else:
        # control arm: legacy host staging — every resident byte
        # round-trips through numpy and is accounted as staged
        docs_h = np.zeros((bp, cap), np.int32)
        lens_h = np.full((bp,), -1, np.int32)   # padding sentinels
        for slot, s in zip(slots, sessions):
            docs_h[slot] = np.asarray(s.docs)
            lens_h[slot] = int(np.asarray(s.lens))
        note_transfer(docs_h.nbytes + lens_h.nbytes,
                      rung="mesh", purpose="stage")
        staged_bytes += docs_h.nbytes + lens_h.nbytes
        docs_d = jax.device_put(docs_h, sh)
        lens_d = jax.device_put(lens_h, sh)
    ph.step("mesh.dispatch")
    fn = mesh_flush_fn(mesh, bp, n, mi, cap)
    # the plan arrays go up from host numpy straight to their
    # sharding, a quarter to a chip
    out_docs, out_lens = fn(docs_d, lens_d,
                            *jax.device_put((pos, dlen, ilen, chars), sh))
    # queued behind the replay: each chip cuts its own shard while
    # this thread waits at the fence
    rows, row_lens = _cut_blocks(out_docs, out_lens)
    # the length fetch is the completion fence + parity cross-check
    ph.step("mesh.fence")
    t_fence = time.perf_counter()
    got = np.asarray(out_lens)
    device_s = time.perf_counter() - t_fence
    ph.step("mesh.adopt")
    rows = [rows[slot] for slot in slots]
    row_lens = [row_lens[slot] for slot in slots]
    for i, home in homes.items():
        if home is not None:
            rows[i] = jax.device_put(rows[i], home)
            row_lens[i] = jax.device_put(row_lens[i], home)
    ok = adopt_results(sessions, plans, rows, row_lens, got[slots])
    if _arena.DEVICE_STAGE.enabled:
        _arena.adopt(mesh, cap, mi, out_docs, out_lens, sessions,
                     ok, bp, slots)
    ph.count("rows", b)
    ph.count("rows_off_home", len(astray))
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
