"""Pallas TPU kernels for the merge/replay hot loops.

Design constraint from the one on-chip compile this module has seen
(2026-07-31): the Mosaic compiler rejects `tpu.dynamic_gather` whose
gather dimension spans more than one vector register ("Not implemented:
Multiple source vregs along gather dimension"), so per-lane table
lookups are limited to ~128 lanes — far below any real document or run
table. Gather-formulated kernels lower fine
(`.lower(lowering_platforms=('tpu',))` passes) and only fail in the
Mosaic compile itself, which is why the first, gather-based revision of
this module survived CI for three rounds while dying on every on-chip
attempt.

The kernels here are therefore gather-free:

* `materialize_pallas` exploits that a merge-ordered run's source text
  is CONTIGUOUS in the arena (affine, slope 1): the kernel walks runs as
  a Pallas grid and block-copies each run's chars with dynamic-offset
  vector loads/stores + masked read-modify-write at the edges — pure
  DMA-shaped work, which is what the hardware is good at.
* `apply_op_block` routes each document row's tail shift through
  `pltpu.roll` (scalar-controlled lane rotation, natively supported)
  over lane TILES of the row with a one-vreg halo on each side, so its
  VMEM need and program size do not grow with the capacity class,
  replacing the per-lane gathers of the XLA formulation in
  tpu/batch.py.

Tests exercise the kernels interpreted on the CPU (the one mapping from
backend to interpret mode is `runtime.pallas_interpret`) AND assert TPU
lowering offline; the Mosaic compile and the comparison with each
kernel's XLA twin on the chip are chip_smoke.py's kernel phase.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import pallas_interpret

_ROWS = 8           # VMEM sublane granularity: rows are processed in 8s
_LANES = 128        # one vreg of lanes: the halo width
_TILE = 4096        # lanes per grid step of apply_op_block


def _apply_op_tile_kernel(pos_ref, dlen_ref, ilen_ref, chars_ref,
                          prev_ref, doc_ref, next_ref, out_ref, *,
                          tile: int, mi: int):
    """One op applied to an [8, tile] block of the batch (grid = row
    groups x lane tiles).

    out[i] = chars[i - pos]          for pos <= i < pos+ilen   (insert lane)
           = doc[i]                  for i < pos
           = doc[i - ilen + dlen]    for i >= pos+ilen         (tail shift)

    The tail shift is a lane rotation by a per-row SCALAR (from SMEM),
    so no per-lane gather is needed (Mosaic's dynamic_gather cannot span
    vregs — module doc). |shift| <= max_ins <= one vreg, so a tile needs
    only the last vreg of the tile before it and the first of the tile
    after it: the row is passed three times, as the tile and as two
    one-vreg halo blocks whose block index wraps at the row's ends —
    which reproduces `jnp.roll`'s wrap-around, so the result is
    byte-identical to the XLA twin in dead lanes too (a row that fits
    one tile wraps onto its own ends). The insert lanes are `max_ins`
    scalar selects. Rows ride in sublane groups of 8 (a single-row VMEM
    block is not a legal Pallas TPU block shape); each row's rotation
    amount differs, so rows are unrolled statically inside the group.
    """
    g = pl.program_id(0)
    t = pl.program_id(1)
    idx = t * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    for r in range(_ROWS):      # static unroll within the sublane group
        row = g * _ROWS + r
        pos = pos_ref[0, row]
        dlen = dlen_ref[0, row]
        ilen = ilen_ref[0, row]
        doc = doc_ref[r:r + 1, :]           # [1, tile] static row slice
        win = jnp.concatenate(
            [prev_ref[r:r + 1, :], doc, next_ref[r:r + 1, :]], axis=1)
        shifted = pltpu.roll(win, jnp.mod(ilen - dlen, tile + 2 * _LANES),
                             1)[:, _LANES:_LANES + tile]
        out = jnp.where(idx < pos, doc, shifted)    # doc[i - shift]
        for j in range(mi):     # insert lanes, static unroll
            lane = (idx == pos + j) & (j < ilen)
            out = jnp.where(lane, chars_ref[row, j], out)
        noop = (ilen == 0) & (dlen == 0)
        out_ref[r:r + 1, :] = jnp.where(noop, doc, out)


def apply_op_block(pos, dlen, ilen, chars, doc, doc_len, *,
                   interpret: Optional[bool] = None, tile: int = _TILE):
    """Apply one positional op per document to a [b, cap] batch (Pallas).

    Returns (new_docs [b, cap], new_lens [b]). Lengths are pure
    elementwise arithmetic and stay outside the kernel. `cap` must be a
    multiple of one vreg of lanes, and above `tile` a multiple of it
    (capacity classes are powers of two); `max_ins` (chars.shape[1])
    must fit the one-vreg halo."""
    if interpret is None:
        interpret = pallas_interpret()
    b, cap = doc.shape
    mi = chars.shape[1]
    tile = min(tile, cap)
    if cap % tile or tile % _LANES or mi > _LANES:
        raise ValueError(f"apply_op_block: cap {cap} / max_ins {mi} do "
                         f"not fit lane tiles of {tile}")
    bp = _round_up(b, _ROWS)
    if bp > b:
        doc = jnp.pad(doc, ((0, bp - b), (0, 0)))
        chars = jnp.pad(chars, ((0, bp - b), (0, 0)))
    pos_p, dlen_p, ilen_p = (jnp.pad(x, (0, bp - b))[None, :]
                             for x in (pos, dlen, ilen))
    scal = pl.BlockSpec((1, bp), lambda g, t: (0, 0),
                        memory_space=pltpu.SMEM)
    ins = pl.BlockSpec((bp, mi), lambda g, t: (0, 0),
                       memory_space=pltpu.SMEM)
    cur = pl.BlockSpec((_ROWS, tile), lambda g, t: (g, t),
                       memory_space=pltpu.VMEM)
    nblk, tb = cap // _LANES, tile // _LANES
    prev = pl.BlockSpec((_ROWS, _LANES),
                        lambda g, t: (g, (t * tb + nblk - 1) % nblk),
                        memory_space=pltpu.VMEM)
    nxt = pl.BlockSpec((_ROWS, _LANES),
                       lambda g, t: (g, ((t + 1) * tb) % nblk),
                       memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_apply_op_tile_kernel, tile=tile, mi=mi),
        grid=(bp // _ROWS, cap // tile),
        in_specs=[scal, scal, scal, ins, prev, cur, nxt],
        out_specs=cur,
        out_shape=jax.ShapeDtypeStruct((bp, cap), jnp.int32),
        interpret=interpret,
    )(pos_p, dlen_p, ilen_p, chars, doc, doc, doc)
    noop = (ilen == 0) & (dlen == 0)
    return out[:b], doc_len + jnp.where(noop, 0, ilen - dlen)


# ---------------------------------------------------------------------------
# materialize: run expansion as contiguous block copies (VERDICT r2 #5)
# ---------------------------------------------------------------------------

_CB = 512           # copy-chunk lanes (4 int32 vregs)


def _materialize_runs_kernel(starts_ref, lens_ref, abase_ref, arena_ref,
                             out_ref, *, cb: int, cap: int):
    """Copy one run's visible chars into the output (grid = runs).

    Every run's source is a contiguous arena span, so the expansion is
    chunked vector copies with a masked read-modify-write (grid steps
    are sequential on TPU, so the window RMW is race-free). Runs
    at/after `cap` are clipped; chunk-tail junk past `cap` lands in the
    output slack and is sliced off by the wrapper.

    Alignment (on-chip Mosaic evidence, 2026-07-31): a dynamic
    lane-dimension `pl.ds` offset must be statically provable as a
    multiple of 128 ("cannot statically prove that index in dimension 1
    is a multiple of 128") — arbitrary `a + off` offsets are rejected.
    All loads/stores therefore use 128-aligned windows (`(idx//128)*128`
    carries the proof) one vreg wider than the copy chunk, with the
    sub-tile offsets folded into a single lane rotation of the source
    window: placed[i] = win[i - (dst%128) + (src%128)]."""
    i = pl.program_id(0)
    w = cb + 128        # aligned window lanes

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    s = starts_ref[0, i]
    n = lens_ref[0, i]
    a = abase_ref[0, i]
    wlane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)

    n_eff = jnp.minimum(n, jnp.maximum(cap - s, 0))   # clip at cap
    n_chunks = (n_eff + cb - 1) // cb

    def body(k, _):
        off = k * cb
        src_idx = a + off
        dst_idx = s + off
        ra = jax.lax.rem(src_idx, 128)
        rd = jax.lax.rem(dst_idx, 128)
        # aligned bases written as q*128 — the literal multiply is the
        # form Mosaic's affine analysis accepts as provably aligned
        qa128 = jax.lax.div(src_idx, 128) * 128
        qd128 = jax.lax.div(dst_idx, 128) * 128
        win = arena_ref[:, pl.ds(qa128, w)]
        old = out_ref[:, pl.ds(qd128, w)]
        placed = pltpu.roll(win, jnp.mod(rd - ra, w), 1)
        j = wlane - rd                # window lane → chunk lane
        mask = (j >= 0) & (j < cb) & ((j + off) < n)
        out_ref[:, pl.ds(qd128, w)] = jnp.where(mask, placed, old)
        return 0

    jax.lax.fori_loop(0, n_chunks, body, 0)


def _round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


import os as _os

# Run tables live in SMEM (per-grid-step scalars); bound their size to
# stay inside scalar memory. 8192 runs = 96 KiB of tables — deliberately
# conservative until an on-chip compile probes the real ceiling
# (friendsforever: 3.3k runs fits; git-makefile: 21.5k needs the raise).
_SMEM_RUNS_DEFAULT = 8192


def materialize_pallas(perm, vis_len, arena_off, arena, cap: int,
                       interpret: Optional[bool] = None):
    """Drop-in for linearize.materialize_jax with the run expansion in a
    Pallas kernel: gather-free contiguous run copies (see module doc).
    Returns (text [cap] int32, total_len).

    Dead (vis_len == 0) runs cost one near-empty sequential grid step
    each — a static Pallas grid cannot contract to the dynamic live
    count, so compaction would only reorder, not reduce, the steps.

    A run table beyond DT_PALLAS_SMEM_RUNS raises (SMEM is scalar memory
    and small): the kernel that was asked for never hands its work to
    the XLA formulation under its own name."""
    if interpret is None:
        interpret = pallas_interpret()
    n = perm.shape[0]
    smem_max = int(_os.environ.get("DT_PALLAS_SMEM_RUNS",
                                   _SMEM_RUNS_DEFAULT))
    if not interpret and n > smem_max:
        raise ValueError(
            f"materialize_pallas: {n} runs exceeds the SMEM table "
            f"bound ({smem_max}); raise DT_PALLAS_SMEM_RUNS if the "
            "chip's SMEM allows it")
    vl = vis_len[perm].astype(jnp.int32)
    cum = jnp.cumsum(vl)
    total = (cum[-1] if n else jnp.int32(0)).astype(jnp.int32)
    if n == 0:
        return jnp.zeros((cap,), jnp.int32), total
    starts = (cum - vl).astype(jnp.int32)
    abase = arena_off[perm].astype(jnp.int32)

    arena_i = arena.astype(jnp.int32)
    # window slack: aligned-window copies reach one vreg past the chunk
    A_pad = _round_up(arena_i.shape[0] + _CB + 128, 128)
    arena_i = jnp.pad(arena_i, (0, A_pad - arena_i.shape[0]))
    OUTD = _round_up(cap + _CB + 128, 128)

    tab = pl.BlockSpec((1, n), lambda i: (0, 0), memory_space=pltpu.SMEM)
    arena_spec = pl.BlockSpec((1, A_pad), lambda i: (0, 0),
                              memory_space=pltpu.VMEM)
    out_spec = pl.BlockSpec((1, OUTD), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_materialize_runs_kernel, cb=_CB, cap=cap),
        grid=(n,),
        in_specs=[tab, tab, tab, arena_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((1, OUTD), jnp.int32),
        interpret=interpret,
    )(starts[None, :], vl[None, :], abase[None, :], arena_i[None, :])
    return out[0, :cap], total


def replay_ops_pallas(docs, lens, pos, dlen, ilen, chars,
                      interpret: Optional[bool] = None):
    """`apply_op_block` inside lax.scan over op index, continued from
    the `[b, cap]` state `(docs, lens)`: the signature of the served
    XLA replay (`flush_fuse.make_replay_body`), which chip_smoke.py
    compares it with on the chip."""
    def step(carry, op):
        return apply_op_block(*op, *carry, interpret=interpret), None

    ops = tuple(jnp.swapaxes(a, 0, 1) for a in (pos, dlen, ilen, chars))
    return jax.lax.scan(step, (docs, lens), ops)[0]


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def replay_batch_pallas(pos, dlen, ilen, chars, cap: int,
                        interpret: Optional[bool] = None):
    """Full batched replay from empty documents with the Pallas step
    kernel (drop-in for tpu.batch.replay_batch)."""
    b = pos.shape[0]
    return replay_ops_pallas(jnp.zeros((b, cap), dtype=jnp.int32),
                             jnp.zeros((b,), dtype=jnp.int32),
                             pos, dlen, ilen, chars, interpret=interpret)
