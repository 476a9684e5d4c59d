"""Bytes the replays had to move (each replayed document's int32 row
read once and written once, counted by `bench/instrument.py`) over what
the chip's HBM could move in the time the device was busy (the trace's
`busy_s` times the peak in `bench/peaks.json`). A device that is not in
the table is an error, not a default."""


def read(ctx):
    s, tr = ctx.get("spans"), ctx.get("trace")
    if not s or not tr or not s["replay"]["calls"]:
        return None
    if ctx["device"].get("rehearsal"):
        return None     # the CPU rehearsal has no HBM to take a share of
    peak = ctx["peaks"][ctx["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * s["replay"]["bytes_needed"] / (peak * tr["busy_s"])
