"""The HTTP front end's own counts over the window.

`tools/server.py` counts, on the root phase of every document request
(obs/phases.py: `http.edit`, `http.get`, ...), which parser took it:
`lean`, the one pass over the request line and the header lines, or
`stdlib`, `BaseHTTPRequestHandler`'s, which takes what the lean one
cannot. The `http.lean_share.*` readers under `bench/metrics/` are
this one function, a cell each.
"""

from __future__ import annotations

from bench import phases

EDIT = "http.edit"


def lean_share(ctx):
    """100 * lean / (lean + stdlib) over the window's edits. None on a
    program without the counts (the parent of the PR that added them),
    or where no edit arrived."""
    b = phases.blocks(ctx)
    if b is None:
        return None
    counts = b[1]["phases"].get(EDIT, {}).get("counts", {})
    if "lean" not in counts and "stdlib" not in counts:
        return None
    lean = phases.delta(ctx, EDIT, "counts.lean")
    stdlib = phases.delta(ctx, EDIT, "counts.stdlib")
    return phases.ratio(lean, lean + stdlib, 100.0)
