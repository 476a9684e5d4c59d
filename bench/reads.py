"""Reads served from the chip, over the window.

Under `sched_opts.reads = "device"` (bench/configs/yb-pages.json) a
`GET /doc/{id}` at the tip is answered from the document's device
session (`MergeScheduler.read_tip`). The program counts on the
`http.get` root (obs/phases.py) where each such GET was answered,
`device` or `host`, and `at_tip` where its session needed no sync. The
root's steps are `get.sync` (the inline flush or the wait),
`get.fetch` (the wait for the shard's device lock, the transfer of the
whole row and the wait for it) and `get.respond`; `get.checkout` stays
the host answer's.
The `.read95` readers under `bench/metrics/` are these functions.
Every one returns None on a program without the counts (every parent
of the PR that added them), told by `device` / `host`, one of which a
program that has them writes at every such GET.

The fetch is a plain transfer of the resident row, no device program,
so there is no roofline share of it: transfer bytes are not reckoned
against the device's busy time.
"""

from __future__ import annotations

from bench import phases

GET = "http.get"
GET_SITES = ("get.",)


def answered(ctx):
    """(GETs answered from the device, from the host) over the window;
    None where the program does not count them."""
    b = phases.blocks(ctx)
    if b is None:
        return None
    counts = (b[1]["phases"].get(GET) or {}).get("counts", {})
    if "device" not in counts and "host" not in counts:
        return None
    return (phases.delta(ctx, GET, "counts.device"),
            phases.delta(ctx, GET, "counts.host"))


def device_share(ctx):
    """`read.device_share.*`: 100 x `device` / (`device` + `host`);
    must read 100, every page being resident."""
    got = answered(ctx)
    if got is None:
        return None
    return phases.ratio(got[0], got[0] + got[1], 100.0)


def at_tip_share(ctx):
    """`read.at_tip_share.*`: of the GETs answered from the device,
    those whose session needed no sync."""
    got = answered(ctx)
    if got is None:
        return None
    return phases.ratio(phases.delta(ctx, GET, "counts.at_tip"), got[0],
                        100.0)


def get_mean_ms(ctx):
    """`read.get_mean_ms.*`: the `http.get` root's mean, handler's
    first line to the response on the wire."""
    if answered(ctx) is None:
        return None
    return phases.mean_ms(ctx, GET)


def sync_share(ctx):
    """`read.sync_share.*`: the seconds reads spent bringing sessions
    to the tip (`get.sync`: a read's own flush, or the wait for one in
    flight) as a share of all `http.get` seconds: what the paced merge
    loop (`FLUSH_HOST_SHARE`) costs a reader. 0 where no read had to."""
    if answered(ctx) is None:
        return None
    return phases.ratio(phases.delta(ctx, "get.sync") or 0.0,
                        phases.delta(ctx, GET), 100.0)


def fetch_mean_ms(ctx):
    """`read.fetch_mean_ms.*`: mean of `get.fetch`: the wait for the
    device lock, the row's transfer and the wait for it."""
    if answered(ctx) is None:
        return None
    return phases.mean_ms(ctx, "get.fetch")


def get_held_share(ctx):
    """`lock.held_by_get_share.*`: share of the window `DocStore.lock`
    was held by acquisitions made at the steps of a GET (`get.*`):
    what a read costs an edit."""
    if answered(ctx) is None:
        return None
    return phases.lock_held_share(ctx, GET_SITES)
