"""Spans around the calls into each layer, recorded from here.

Installed only in a `--trace 1` run, so the end-to-end runs time the
program untouched. Each wrapper writes a `jax.profiler.TraceAnnotation`
(the span lands in the profiler's own trace, on the device's clock) and
keeps (start, seconds) on the host's clock for the readers under
`bench/metrics/`. What would need a span inside the program (the parts
of a handler, the scheduler's queue wait, the plan walk's phases) is
listed in PERF.md for the `tracing` issue.

  http_edit / http_get   `SyncHandler.do_POST` / `do_GET`, whole handler
  lock_wait              a handler thread waiting for `DocStore.lock`
  autosave               `DocStore.flush`, one pass
  plan                   `FusedDocSession.plan_tail` (host plan walk)
  replay_host            `flush_fuse.fused_replay` (stack, dispatch,
                         fetch of the lengths, adoption); also counts
                         the rows and bytes each call replays
"""

from __future__ import annotations

import threading
import time


class TimedLock:
    """`DocStore.lock` with the wait of handler threads timed. The
    scheduler keeps the lock itself (`sync_lock`), so exclusion holds."""

    def __init__(self, inner, spans: "Spans") -> None:
        self._inner = inner
        self._spans = spans

    def acquire(self, *a, **kw):
        sp = self._spans
        if not getattr(sp.local, "in_handler", False):
            return self._inner.acquire(*a, **kw)
        t0 = time.monotonic()
        with sp.annotate("lock_wait"):
            got = self._inner.acquire(*a, **kw)
        sp.local.lock_wait += time.monotonic() - t0
        return got

    def release(self):
        return self._inner.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()

    def __getattr__(self, name):
        return getattr(self._inner, name)


class Spans:
    def __init__(self) -> None:
        from jax.profiler import TraceAnnotation
        self.annotate = TraceAnnotation
        self.local = threading.local()
        self.lock = threading.Lock()
        self.handlers = []      # (kind, start, seconds, lock wait seconds)
        self.passes = []        # autosave: (start, seconds)
        self.plans = []         # (start, seconds)
        self.replays = []       # (start, seconds, docs, cap, ops a doc)
        self._undo = []

    # ---- install / uninstall ---------------------------------------------

    def install(self, httpd) -> None:
        from diamond_types_tpu.tpu import flush_fuse
        spans = self
        store = httpd.store
        handler = httpd.RequestHandlerClass

        def wrap_http(method_name: str):
            inner = getattr(handler, method_name)

            def wrapped(self):
                path = self.path.split("?", 1)[0]
                if not path.startswith("/doc/"):
                    return inner(self)
                kind = "http_edit" if path.endswith("/edit") else (
                    "http_get" if method_name == "do_GET" else "http_other")
                loc = spans.local
                loc.in_handler, loc.lock_wait = True, 0.0
                t0 = time.monotonic()
                try:
                    with spans.annotate(kind):
                        return inner(self)
                finally:
                    loc.in_handler = False
                    row = (kind, t0, time.monotonic() - t0, loc.lock_wait)
                    with spans.lock:
                        spans.handlers.append(row)

            setattr(handler, method_name, wrapped)
            self._undo.append(lambda: setattr(handler, method_name, inner))

        wrap_http("do_POST")
        wrap_http("do_GET")

        plain_lock = store.lock
        store.lock = TimedLock(plain_lock, self)
        self._undo.append(lambda: setattr(store, "lock", plain_lock))

        flush = store.flush

        def timed_flush(force: bool = False):
            t0 = time.monotonic()
            try:
                with spans.annotate("autosave"):
                    return flush(force)
            finally:
                with spans.lock:
                    spans.passes.append((t0, time.monotonic() - t0))

        store.flush = timed_flush
        self._undo.append(lambda: delattr(store, "flush"))

        plan_tail = flush_fuse.FusedDocSession.plan_tail

        def timed_plan(sess):
            t0 = time.monotonic()
            try:
                with spans.annotate("plan"):
                    return plan_tail(sess)
            finally:
                with spans.lock:
                    spans.plans.append((t0, time.monotonic() - t0))

        flush_fuse.FusedDocSession.plan_tail = timed_plan
        self._undo.append(lambda: setattr(flush_fuse.FusedDocSession,
                                          "plan_tail", plan_tail))

        replay = flush_fuse.fused_replay

        def timed_replay(sessions, plans):
            t0 = time.monotonic()
            try:
                with spans.annotate("replay_host"):
                    return replay(sessions, plans)
            finally:
                row = (t0, time.monotonic() - t0, len(sessions),
                       int(sessions[0].cap),
                       max(int(p.n_ops) for p in plans))
                with spans.lock:
                    spans.replays.append(row)

        flush_fuse.fused_replay = timed_replay
        self._undo.append(lambda: setattr(flush_fuse, "fused_replay",
                                          replay))

    def uninstall(self) -> None:
        while self._undo:
            self._undo.pop()()

    # ---- what the readers take ---------------------------------------------

    def summary(self, t0: float, t1: float) -> dict:
        """Spans that started inside [t0, t1), reduced."""
        from bench.reduce import dist
        with self.lock:
            handlers = [h for h in self.handlers if t0 <= h[1] < t1]
            passes = [p for p in self.passes
                      if p[0] < t1 and p[0] + p[1] > t0]
            plans = [p for p in self.plans if t0 <= p[0] < t1]
            replays = [r for r in self.replays if t0 <= r[0] < t1]
        def longest(rows, start, seconds):
            # (offset from the window's opening, seconds), longest
            # first; the warm-up traffic's spans count here too
            rows = [r for r in rows if t0 - 5.0 <= r[start] < t1]
            top = sorted(rows, key=lambda r: -r[seconds])[:5]
            return [[round(r[start] - t0, 3), round(r[seconds], 4)]
                    for r in top]

        with self.lock:
            out = {"handlers": {},
                   "longest": {"autosave": longest(self.passes, 0, 1),
                               "plan": longest(self.plans, 0, 1),
                               "replay_host": longest(self.replays, 0, 1),
                               "handler": longest(self.handlers, 1, 2),
                               "lock_wait": longest(self.handlers, 1, 3)}}
        for kind in ("http_edit", "http_get"):
            rows = [h for h in handlers if h[0] == kind]
            if rows:
                out["handlers"][kind] = {
                    "ms": dist([h[2] * 1e3 for h in rows]),
                    "own_ms": dist([(h[2] - h[3]) * 1e3 for h in rows]),
                    "total_s": float(sum(h[2] for h in rows)),
                    "lock_wait_s": float(sum(h[3] for h in rows))}
        total = sum(h[2] for h in handlers)
        out["handler_s"] = float(total)
        out["lock_wait_s"] = float(sum(h[3] for h in handlers))
        inside = [min(s + d, t1) - max(s, t0) for s, d in passes]
        out["autosave"] = {"passes": len(passes),
                           "max_ms": max((d for _s, d in passes),
                                         default=0.0) * 1e3,
                           "busy_s": float(sum(inside))}
        out["plan"] = {"calls": len(plans),
                       "total_s": float(sum(d for _s, d in plans))}
        out["replay"] = {
            "calls": len(replays),
            "docs": int(sum(r[2] for r in replays)),
            "total_s": float(sum(r[1] for r in replays)),
            # what a replay has to move: each document's row read once
            # and written once (int32), whatever its batch was padded to
            "bytes_needed": int(sum(2 * r[2] * r[3] * 4 for r in replays)),
            "ops_a_doc_max": max((r[4] for r in replays), default=0)}
        return out
