"""Resident handler threads: how often a connection met one, and what
they cost.

Since PR 40 `tools/server.py`'s accept loop hands a connection to a
parked `http-worker-<n>` where one is parked and gives it a thread of
its own where none is. It counts which, `pooled` / `born`, one a
connection, and folds the two into the `http.accept_wait` row's own
counts with the sample of the listening socket it takes every 32nd
accept (so a window's difference is exact to 31 connections). The
resident threads' CPU is the `cpu` block's `http_workers_s`
(obs/phases.py); `exited_s` then holds the born threads alone. The
readers under `bench/metrics/` are these two functions, a cell each;
both return None on a program without the counts or the class (the
parent of the PR that added them).
"""

from __future__ import annotations

from bench import inside, phases

ACCEPT = "http.accept_wait"


def pooled_share(ctx):
    """`http.pooled_share.*`: 100 x `pooled` / (`pooled` + `born`)
    between the scrapes: of the window's connections, the percentage
    that found a resident thread parked. Low under saturation: the
    workers are inside their connections (the store lock's queue, a
    wake-up's wait) when the loop comes round, and the loop is back to
    a birth a connection."""
    b = phases.blocks(ctx)
    if b is None:
        return None
    counts = b[1]["phases"].get(ACCEPT, {}).get("counts", {})
    if "pooled" not in counts and "born" not in counts:
        return None
    pooled = phases.delta(ctx, ACCEPT, "counts.pooled")
    born = phases.delta(ctx, ACCEPT, "counts.born")
    return phases.ratio(pooled, pooled + born, 100.0)


def workers_cpu_share(ctx):
    """`cpu.http_workers_share.*`: the resident handler threads' CPU
    as a percentage of ONE core over the traffic's seconds, as
    `cpu.accept_loop_share.*` is the loop's."""
    return inside.cpu_share(ctx, "http_workers_s")
