"""How often a push takes `DocStore.lock`.

The program files every acquisition of its one clocked lock under the
step or phase open on the acquiring thread
(`locks["store.oplog"][<site>].acquires`, obs/phases.py), written with
the request's root as the `http.edit` row's count is. Until PR 43 an
edit took the lock four times: `store.get` under `edit.parse`, the
hold that validates and adds the ops under `edit.checkout`, and
`mark_dirty` and `cond` under `edit.publish`; every contended take is
a hand-off, a wake-up of 0.5-0.7 ms on the chip's host. Since PR 43
only the hold at `edit.checkout` is left. The readers under
`bench/metrics/` are this one function, a cell each; it returns None
on a program without the `locks` block or the `http.edit` row.
"""

from __future__ import annotations

from bench import phases


def edit_takes_per_push(ctx):
    """`lock.edit_takes_per_push.*`: acquisitions of `DocStore.lock`
    made at the edit path's sites (`phases.EDIT_SITES`) between the
    scrapes, over the `http.edit` roots closed between them. 4.0 before
    PR 43; 1.0 since, and a little over where a push is the first to
    ask for its document (the load, or the new `OpLog`, is made under
    the lock)."""
    b = phases.blocks(ctx)
    if b is None or "locks" not in b[0] or "locks" not in b[1]:
        return None

    def takes(block):
        return sum(c["acquires"] for s, c in
                   block["locks"].get(phases.STORE_LOCK, {}).items()
                   if s.startswith(phases.EDIT_SITES))
    return phases.ratio(takes(b[1]) - takes(b[0]),
                        phases.delta(ctx, "http.edit", "count"))
