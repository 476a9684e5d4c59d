"""The program's phase clocks over the window.

`scheduler.metrics_json()["phases"]` (obs/phases.py) holds cumulative
rows since boot; `bench/run.py` scrapes it at the window's two ends
(`ctx["m0"]["serve"]`, `ctx["m1"]["serve"]`, each with `_at`). The
readers under `bench/metrics/` take differences through these helpers.
Every helper returns None where the block, the row or the divisor is
missing: a program without the clocks (the parent of the PR that added
them) then leaves the metric out.
"""

from __future__ import annotations

# sites of `store.oplog` by who acquired it: the innermost phase open
# on the acquiring thread (the names are the program's, PERF.md lists
# them)
EDIT_SITES = ("http.edit", "edit.")
AUTOSAVE_ENCODE = ("autosave.encode",)
PUMP_SITES = ("sched.", "bank.", "plan", "replay", "adopt")
STORE_LOCK = "store.oplog"


def blocks(ctx):
    b0 = ctx["m0"]["serve"].get("phases")
    b1 = ctx["m1"]["serve"].get("phases")
    if not b0 or not b1:
        return None
    return b0, b1


def window_s(ctx) -> float:
    return ctx["m1"]["_at"] - ctx["m0"]["_at"]


def delta(ctx, name: str, key: str = "sum_s"):
    """A phase row's field over the window; `counts.<k>` reaches into
    the row's own counts."""
    b = blocks(ctx)
    if b is None:
        return None

    def get(block):
        row = block["phases"].get(name)
        if row is None:
            return 0
        if key.startswith("counts."):
            return row.get("counts", {}).get(key[7:], 0)
        return row.get(key, 0)
    if name not in b[1]["phases"]:
        return None
    return get(b[1]) - get(b[0])


def ratio(num, den, scale: float = 1.0):
    if num is None or not den:
        return None
    return scale * num / den


def mean_ms(ctx, name: str, own: bool = False):
    """Mean milliseconds of a phase over the window; `own` leaves out
    the part of it spent waiting for a clocked lock."""
    s = delta(ctx, name)
    if s is not None and own:
        s -= delta(ctx, name, "lock_wait_s")
    return ratio(s, delta(ctx, name, "count"), 1e3)


def share_of(ctx, part: str, whole: str):
    """One phase's seconds as a percentage of another's."""
    return ratio(delta(ctx, part), delta(ctx, whole), 100.0)


def lock_held_share(ctx, sites, lock: str = STORE_LOCK):
    """Seconds `lock` was held by acquisitions made at `sites` (name
    prefixes), as a percentage of the window."""
    b = blocks(ctx)
    if b is None:
        return None

    def held(block):
        return sum(c["hold_s"] for s, c in
                   block["locks"].get(lock, {}).items()
                   if s.startswith(tuple(sites)))
    return ratio(held(b[1]) - held(b[0]), window_s(ctx), 100.0)


def autosave_held_share(ctx):
    """`lock.held_by_autosave_share.*`: share of the window
    `DocStore.lock` was held by the autosave encode (acquisitions made
    in `autosave.encode`)."""
    return lock_held_share(ctx, AUTOSAVE_ENCODE)


def queue_wait_mean_ms(ctx):
    """`sched.queue_wait_mean_ms.*`: the scheduler's
    `latencies.queue_wait` histogram, exported as `sched.queue_wait`,
    mean over the window. The program observes it when a flush ENDS,
    as the end less the item's submit: it runs from submit to the end
    of the flush that merged the item, the flush included."""
    return mean_ms(ctx, "sched.queue_wait")
