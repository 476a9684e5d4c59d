"""Block edits over the window: growth, plan rows, scan steps.

Since PR 36 the program counts, on its own phase rows (obs/phases.py),
what a block edit does to the flush path. `bank.grow` is one growth of
a resident session to a larger capacity class: `grown` where the row
was copied on the device, `grow_rebuilt` where that failed and the
session was rebuilt from a host checkout, `grow_slots` the int32 slots
gained. `plan.tail` counts the `rows` its walks made and, of them, the
`block_rows` cut from an insert or delete longer than `max_ins`.
`replay` counts the `scan_steps` each call was padded to. The `.hunk`
and `.paste` readers under `bench/metrics/` are these functions, a cell
each; every one returns None on a program without the counts (the
parent of the PR that added them), told by `rows`, which a program
that has them writes at every walk with work.

The growth readers run from the window's opening to the END OF THE
DRAIN (`ctx["m_end"]`, scraped after `correct` has compared every
session with the reference): the cell offers the merge side more than
it takes, so part of the growths fall in the drain, and those sessions
are compared like the others. The other readers are over the window.
"""

from __future__ import annotations

from bench import phases

GROW = "bank.grow"
PLAN = "plan.tail"
REPLAY = "replay"


def row_at(ctx, at: str, name: str) -> dict:
    """The phase row `name` at the scrape `at` (`m0`, `m1`, `m_end`);
    empty where the scrape, the block or the row is missing."""
    block = (ctx.get(at) or {}).get("serve", {}).get("phases") or {}
    return block.get("phases", {}).get(name) or {}


def counted(ctx) -> bool:
    """Does the program keep these counts at all?"""
    return "rows" in row_at(ctx, "m1", PLAN).get("counts", {})


def grow_since_open(ctx, key: str):
    """A field of the `bank.grow` row (`counts.<k>`, `count`, `sum_s`)
    from the window's opening to the drain's end: 0, not nothing,
    where the program counts and no session grew."""
    if not counted(ctx) or not ctx.get("m_end"):
        return None

    def get(at):
        row = row_at(ctx, at, GROW)
        if key.startswith("counts."):
            return row.get("counts", {}).get(key[7:], 0)
        return row.get(key, 0)
    return get("m_end") - get("m0")


def sessions_grown(ctx):
    """`grow.sessions_grown.*`: growths made on the device."""
    return grow_since_open(ctx, "counts.grown")


def on_device_share(ctx):
    """`grow.on_device_share.*`: 100 x `grown` / (`grown` +
    `grow_rebuilt`); nothing where nothing grew."""
    grown = grow_since_open(ctx, "counts.grown")
    if grown is None:
        return None
    return phases.ratio(
        grown, grown + grow_since_open(ctx, "counts.grow_rebuilt"), 100.0)


def grow_mean_ms(ctx):
    """`grow.mean_ms.*`: the `bank.grow` row's mean (the copy and the
    wait for it); nothing where no session grew, or on a program
    without the row."""
    return phases.ratio(grow_since_open(ctx, "sum_s"),
                        grow_since_open(ctx, "count"), 1e3)


def rows_per_walk(ctx):
    """`plan.rows_per_walk.*`: plan rows a plan walk with work made."""
    if not counted(ctx):
        return None
    walks = (phases.delta(ctx, PLAN, "counts.xf_native")
             + phases.delta(ctx, PLAN, "counts.xf_python"))
    return phases.ratio(phases.delta(ctx, PLAN, "counts.rows"), walks)


def scan_steps_per_call(ctx):
    """`replay.scan_steps_per_call.*`: the scan's length (plan rows a
    document, padded to the call's shape class) a `fused_replay`
    call."""
    if "scan_steps" not in row_at(ctx, "m1", REPLAY).get("counts", {}):
        return None
    return phases.ratio(phases.delta(ctx, REPLAY, "counts.scan_steps"),
                        phases.delta(ctx, REPLAY, "count"))


def pump_held_share(ctx):
    """`lock.held_by_pump_share.*`: share of the window `DocStore.lock`
    was held by the flush path (resolve, session build, plan walk,
    growth, adoption: acquisitions made under `sched.flush`)."""
    return phases.lock_held_share(ctx, phases.PUMP_SITES)


def replay_hbm_share(ctx):
    """`device.replay_hbm_share.*`: the bytes the replays had to move
    (each replayed document's int32 row read once and written once,
    whatever implements the replay and however many scan steps it
    takes: `bench/instrument.py` counts them a call, at the capacity
    the sessions have then) over what the chip's HBM could move in the
    time the device was busy. The calls are counted over the window's
    `seconds` and the device's busy time over the trace's `window_s`
    inside it, so the bytes are scaled by the one over the other: in
    these cells the device is busy most of the window, and bytes of 51
    s over the busy time of 49 would read 4 % high. A device that is
    not in `bench/peaks.json` is an error, not a default."""
    s, tr = ctx.get("spans"), ctx.get("trace")
    if not s or not tr or not s["replay"]["calls"]:
        return None
    if ctx["device"].get("rehearsal"):
        return None     # the CPU rehearsal has no HBM to take a share of
    peak = ctx["peaks"][ctx["device"]["kind"]]["hbm_bytes_per_s"]
    needed = s["replay"]["bytes_needed"] * tr["window_s"] / ctx["seconds"]
    return 100.0 * needed / (peak * tr["busy_s"])
