"""Bytes the mesh replays had to move over what the chips' HBM could
move while they were busy.

The bytes: for every replayed document, not padding row, its int32 row
of its class's capacity read once and written once, from the program's
by-class counters (`bench/mesh.py`); the same work whatever implements
the replay. The replays between the harness's two scrapes fall in the
traffic's `seconds` (the backlog after it is a few windows) and the
trace covers `window_s` of them, so the bytes are scaled by that ratio.
The time: the trace's `busy_s`, the chips' mean, times the number of
chips, times the peak in `bench/peaks.json`. A device that is not in
the table is an error, not a default."""
from bench import mesh


def replay_bytes(classes: dict) -> int:
    """{cap: {"docs": n, ...}} -> bytes: 4 x cap a row, in and out."""
    return sum(2 * 4 * int(cap) * c.get("docs", 0)
               for cap, c in classes.items())


def read(ctx):
    classes, tr = mesh.by_class(ctx), ctx.get("trace")
    if not classes or not tr:
        return None
    if ctx["device"].get("rehearsal"):
        return None     # the CPU rehearsal has no HBM to take a share of
    peak = ctx["peaks"][ctx["device"]["kind"]]["hbm_bytes_per_s"]
    needed = replay_bytes(classes) * tr["window_s"] / ctx["seconds"]
    return 100.0 * needed / (peak * tr["busy_s"] * tr["chips"])
