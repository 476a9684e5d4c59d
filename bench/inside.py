"""A push outside its handler, the interpreter's queue, the CPU by
thread class and the flush worker's pause, over the traffic.

Since PR 38 the program keeps, on its one phase table (obs/phases.py),
what a push meets outside any phase: on `http.accept_wait`'s own
counts `listen_samples` / `listen_waiting` (every 32nd accept: was a
further connection waiting already?), `http.thread_start` (accept ->
the handler thread's first line) and `http.thread_cpu` (the thread's whole CPU, read at its last
line), both of one connection in eight, `gil.wait` (what a thread that
becomes runnable waits for the interpreter: the oversleep of a probe
that sleeps 100 ms), `sched.pause` (the seconds a flush worker sits
out after a paced flush), and beside the rows a `cpu` block:
cumulative CPU seconds of the process and of its live threads by
class. The readers under `bench/metrics/` are these functions, a cell
each; every one returns None on a program without the row (the parent
of the PR that added them). `http.listen_wait` and `listen_depth`, the
kernel's own account of its accept queue (`TCP_INFO`), have no reader:
the kernel of the benchmark's machines has the call and fills nothing
in, so the program writes neither there (PERF.md section 7).

Requests arrive only while the generator sends, for `ctx["seconds"]`;
`bench/run.py` takes the second scrape after the profiler's stop, tens
of seconds later in a traced run. So a rate or a thread's share here
divides by the TRAFFIC's seconds, not by the seconds between the
scrapes (as PERF.md section 5 does): what the threads do after the
traffic (a backlog's last flushes, the autosave) is charged to it.
"""

from __future__ import annotations

from bench import mesh, phases

ACCEPT = "http.accept_wait"
EDIT = "http.edit"
GIL = "gil.wait"
PAUSE = "sched.pause"
FLUSH = "sched.flush"
# obs/phases.py GIL_PROBE_S: the probe's sleep
GIL_PROBE_S = 0.1


def seconds(ctx):
    """The traffic's seconds."""
    return ctx.get("seconds")


def cpu_share(ctx, key: str):
    """`cpu.*_share.*`: a class's CPU seconds between the scrapes as a
    percentage of ONE core over the traffic's seconds."""
    b = phases.blocks(ctx)
    if b is None or key not in (b[1].get("cpu") or {}) \
            or key not in (b[0].get("cpu") or {}):
        return None
    return phases.ratio(b[1]["cpu"][key] - b[0]["cpu"][key], seconds(ctx),
                        100.0)


def keep(ctx) -> None:
    """Both scrapes' rows, lock sites and `cpu` block for PERF.md's
    tables (`bench/out/<cell>.phases.json`, as the four-chip cell's
    reader does); called by two readers that between them are in every
    cell this module has a metric in."""
    mesh.keep(ctx)


def listen_waiting_share(ctx):
    """`http.listen_waiting_share.*`: of the samples taken just after
    an accept (every 32nd), the percentage that found a further
    connection waiting already (a poll that does not block). Near 100:
    the accept queue is never empty, the accept loop and the
    interpreter it waits for are the wall. Near 0: connections are
    taken as they come, the rest of a client's time is the
    generator's."""
    keep(ctx)
    samples = phases.delta(ctx, ACCEPT, "counts.listen_samples")
    if not samples:
        return None
    return 100.0 * phases.delta(ctx, ACCEPT, "counts.listen_waiting") \
        / samples


def thread_start_mean_ms(ctx):
    """`http.thread_start_mean_ms.*`: `accept()` returning -> the first
    line of the connection's new thread (one connection in eight)."""
    keep(ctx)
    return phases.mean_ms(ctx, "http.thread_start")


def thread_cpu_ms_a_request(ctx):
    """`http.thread_cpu_ms_a_request.*`: CPU milliseconds of a handler
    thread from its birth to its last line, a connection (a request);
    the program reads one connection in eight."""
    return phases.mean_ms(ctx, "http.thread_cpu")


def clients(ctx):
    """The closed loop's clients, as `bench/gen.py run_closed` takes
    them: the mix's, at most one a document of the fleet."""
    fleet = ctx["config"]["tiny" if ctx["device"].get("rehearsal")
                          else "fleet"]
    docs = sum(int(c["docs"]) for c in fleet)
    return min(int(ctx["mix"].get("clients", docs)), docs)


def past_accept_share(ctx):
    """`loop.past_accept_share.*`: by Little's law, the share of the
    closed loop's clients that the server holds past `accept()` at an
    instant: 100 x pushes a second (over the traffic's seconds) x the
    mean seconds from `accept()` to the `http.edit` root's close
    (accept wait + the root) / clients. The rest of the clients are in
    the kernel's accept queue or in the generator (its connect, its
    send, its read, its thread's wait for ITS interpreter);
    `http.listen_waiting_share.*` says which. The root closes when its
    thread is next given the interpreter after the `sendall`, so up to
    one `gil.wait` a push is counted that the client no longer waits
    for."""
    pushes = phases.delta(ctx, EDIT, "count")
    if not phases.delta(ctx, "http.thread_cpu", "count") or not pushes \
            or not seconds(ctx):       # the PR's parent has the two rows too
        return None
    past_s = sum(phases.mean_ms(ctx, name) or 0.0
                 for name in (ACCEPT, EDIT)) * 1e-3
    return 100.0 * (pushes / seconds(ctx)) * past_s / clients(ctx)


def gil_wait_mean_ms(ctx):
    """`gil.wait_mean_ms.*`: mean milliseconds the probe overslept,
    over the wakes the TRAFFIC's seconds hold: a round of the probe is
    its 100 ms and its oversleep, so the traffic holds (seconds - the
    oversleep) / 100 ms of them. The oversleep between the scrapes but
    outside the traffic is taken as 0 (an idle server reads the
    kernel's wake-up latency: tens of microseconds on Linux, 0.7 ms on
    the chip's host)."""
    over = phases.delta(ctx, GIL)
    if over is None or not seconds(ctx):
        return None
    return phases.ratio(over, (seconds(ctx) - over) / GIL_PROBE_S, 1e3)


def flush_kinds(ctx) -> bool:
    """Does the program count its flushes by kind (`paced`, `forced`,
    `inline` on the `sched.flush` row)? The warm rounds force theirs,
    so a program that counts has counted by the first scrape."""
    b = phases.blocks(ctx)
    return b is not None and bool(
        {"paced", "forced", "inline"}
        & set(b[1]["phases"].get(FLUSH, {}).get("counts", {})))


def pause_share(ctx):
    """`sched.pause_share.*`: the seconds the flush workers sat out
    after their paced flushes (`FLUSH_HOST_SHARE`) as a percentage of
    the traffic's seconds. 0, not nothing, where the program has the
    clock and no worker paused (one that waits for the device)."""
    if not flush_kinds(ctx):
        return None
    return phases.ratio(phases.delta(ctx, PAUSE) or 0.0, seconds(ctx),
                        100.0)


def flushes_per_s(ctx):
    """`sched.flushes_per_s.*`: `sched.flush` roots closed a second of
    traffic (batches a window / the window). The row is older than the
    kinds; a program without them (this PR's parent) is left out like
    the others."""
    if not flush_kinds(ctx):
        return None
    return phases.ratio(phases.delta(ctx, FLUSH, "count"), seconds(ctx))
