"""The mesh flush window's counters over the window.

`parallel/mesh.py mesh_fused_replay` keeps, on its `mesh.replay` phase
row (obs/phases.py), what each dispatch moved: `rows`, `rows_off_home`,
`ici_bytes`, `arena_hits`, `arena_misses`, and by capacity class
`cap.<cap>.dispatches` / `.docs` / `.padded_rows`; the window
coordinator keeps `homes_off_bank` on its `sched.flush` root. The
`.host4` readers under `bench/metrics/` take differences of the two
scrapes through these helpers and `bench/phases.py`. Every helper
returns None where the program has no such row (a scheduler without
`mesh_window`, or the parent of the PR that added the counters).
"""

from __future__ import annotations

import json
import os

from bench import phases

REPLAY = "mesh.replay"
WINDOW = "sched.flush"
# `lock.held_by_pump_share.host4`: the flush path's sites, with the
# window's and the mesh rung's own steps
PUMP_SITES = phases.PUMP_SITES + ("window.", "mesh.")


def count(ctx, key: str):
    """One of the `mesh.replay` row's own counts over the window."""
    return phases.delta(ctx, REPLAY, "counts." + key)


def window_count(ctx, key: str):
    """One of the `sched.flush` root's own counts over the window; None
    where the program does not keep it (0 would claim it was counted)."""
    b = phases.blocks(ctx)
    if b is None or key not in b[1]["phases"].get(WINDOW, {}).get(
            "counts", {}):
        return None
    return phases.delta(ctx, WINDOW, "counts." + key)


def by_class(ctx):
    """{cap: {"dispatches", "docs", "padded_rows"}} over the window."""
    b = phases.blocks(ctx)
    if b is None or REPLAY not in b[1]["phases"]:
        return None
    c1 = b[1]["phases"][REPLAY].get("counts", {})
    c0 = b[0]["phases"].get(REPLAY, {}).get("counts", {})
    out = {}
    for key, v in c1.items():
        if key.startswith("cap."):
            _cap, cap, field = key.split(".")
            out.setdefault(int(cap), {})[field] = v - c0.get(key, 0)
    return out or None


def total(ctx, field: str):
    """A by-class count summed over the classes."""
    classes = by_class(ctx)
    if classes is None:
        return None
    return sum(c.get(field, 0) for c in classes.values())


def step_share(ctx, step: str):
    """One step of a mesh replay as a percentage of `mesh.replay`."""
    return phases.share_of(ctx, step, REPLAY)


def keep(ctx) -> None:
    """For PERF.md's tables: the program's phase rows, lock sites and
    `window` block at the two scrapes, written beside the run's other
    post-mortem files (`bench/out/<cell>.phases.json`)."""
    if phases.blocks(ctx) is None:
        return
    out = {k: {"_at": ctx[k]["_at"],
               "phases": ctx[k]["serve"]["phases"],
               "window": ctx[k]["serve"].get("window"),
               "steer": ctx[k]["serve"].get("steer")}
           for k in ("m0", "m1")}
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out",
                        ctx["cell"]["name"] + ".phases.json")
    with open(path, "w", encoding="utf8") as f:
        json.dump(out, f)
