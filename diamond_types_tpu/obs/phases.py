"""Phase clocks: always-on timing of the parts of the served path.

The tracer samples 1 % of requests and ends at whole calls, so no share
of a window can be taken from it and nothing says who held a lock. A
`PhaseTable` (one per `Observability` bundle, `obs.phases`) keeps, for
every named phase, a cumulative row since boot: count, seconds, the
longest, the seconds inside it spent waiting for a clocked lock and
holding one. A reader takes the difference of two `snapshot()`s.

One context manager, `table.phase(name)`, is the whole interface:

  * it adds to the row for `name` (a `hist.Histogram`, so the log2
    buckets come for free);
  * it enters `jax.profiler.TraceAnnotation(name)` when `jax` is
    already imported and a profiler session is running, so the phase
    lands on the host plane of the same trace as the device's ops.
    "Tracing on" is "a profiler session is running" — there is no flag;
  * when the request's `Span` is sampled the phase is also a child
    span in the `Tracer` ring (`/debug/trace/<id>`).

What a request pays is kept small, because it pays it every time. A
thread's outermost phase is its root. `root.step(name)` is one clock
read and one entry in the root's own list; steps cannot overlap, and
what they leave uncovered is reported as `<root>.other`. Phases opened
under a root (`plan.tail` under `sched.flush`) and the waits and holds
of clocked locks go into the same list. The table is written once, at
the root's close, under one lock.

Open phases sit on a thread-local stack. That gives every lock event
its site, and lets call sites that hold no bundle (`plan_tail`,
`fused_replay`, the bank) open children of whatever root their thread
has open through the module-level `phase(name)`; with no root open it
records nothing.

A `ClockedLock` given a table as its clock (`attach_clock`) reports
every wait and hold here. Both are charged to the innermost step or
phase open on the acquiring thread (`"other"` with none): in that
row — and in every enclosing phase's — and in
`locks[<lock name>][<site>]`.

What a push meets outside any phase has rows here too, written by
`observe_all` (several rows under one take of the table's lock) or
as a root's notes: the kernel's listen queue and the handler thread's
start, CPU and end (tools/server.py), and
`gil.wait`, the table's own probe thread
(`start_probe`): what a thread that becomes runnable waits for the
interpreter. `snapshot()["cpu"]` is the process's CPU seconds by
thread class, read from `/proc` at the scrape and at no other time.
"""

from __future__ import annotations

import math
import os
import sys
import threading
import time
from typing import Dict, Optional

from .hist import _FIRST_BOUND_S, _N_BUCKETS, Histogram

# a document request at least this long writes a `slow_request` event.
# Inside SLOW_EVENT_GAP_S of the last event only one twice as slow as
# that one is written too: a saturated server, where every request
# waits that long for the lock, must not flush the recorder's ring of
# its rare events, and the slowest of a burst must still be there (all
# are counted in `slow_requests`)
SLOW_REQUEST_S = 0.25
SLOW_EVENT_GAP_S = 1.0
NO_SITE = "other"
# the probe's sleep: 10 wakes a second (each is a system call and a
# turn in the queue it measures: 50 a second cost 2-4 % of a core on
# the chip's host)
GIL_PROBE_S = 0.1
# `snapshot()["cpu"]`: a live Python thread's class by the start of its
# name (the server names its long-lived threads); `accept_loop_s` is
# whatever thread called `claim_thread("accept_loop_s")`: the one
# inside `serve_forever`, which since PR 46 watches and accepts nothing
CPU_CLASSES = (("merge-pump", "pump_s"), ("flush-worker-", "flush_workers_s"),
               ("autosave", "autosave_s"), ("gil-probe", "gil_probe_s"),
               ("http-worker-", "http_workers_s"))

_clock = time.perf_counter
_tls = threading.local()
_annotation = None      # jax.profiler.TraceAnnotation, once jax is imported


def _annotate():
    """`TraceAnnotation`, or None in a process that never imported jax
    (a host-engine server must not import it for this)."""
    global _annotation
    if _annotation is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


class PhaseRow(Histogram):
    """One phase's cumulative row: the histogram's count/sum/max plus
    lock seconds, uncovered seconds and the site's own counts. The
    table's lock guards it, not the histogram's own."""

    __slots__ = ("lock_wait_s", "lock_hold_s", "other_s", "stepped",
                 "tallies")

    def __init__(self) -> None:
        super().__init__()
        self.lock_wait_s = 0.0
        self.lock_hold_s = 0.0
        self.other_s = 0.0
        self.stepped = 0        # closes that had steps: `.other` exists
        self.tallies: Optional[dict] = None   # `counts` is the buckets'

    def add(self, s: float, wait_s: float, hold_s: float) -> None:
        """`Histogram.record` plus the lock seconds, under the table's
        lock."""
        if s <= _FIRST_BOUND_S:
            idx = 0
            if s < 0.0:
                s = 0.0
        else:
            # ceil(log2(s / 1 us)) in one call: the bucket of `record`
            m, idx = math.frexp(s / _FIRST_BOUND_S)
            if m == 0.5:
                idx -= 1
        self.count += 1
        self.sum += s
        if s > self.max:
            self.max = s
        if idx >= _N_BUCKETS:
            self.overflow += 1
        else:
            self.counts[idx] += 1
        self.lock_wait_s += wait_s
        self.lock_hold_s += hold_s

    def row(self) -> dict:
        out = {"count": self.count, "sum_s": self.sum, "max_s": self.max,
               "p50_s": self._quantile_locked(0.50),
               "p99_s": self._quantile_locked(0.99),
               "lock_wait_s": self.lock_wait_s,
               "lock_hold_s": self.lock_hold_s}
        if self.tallies:
            out["counts"] = dict(self.tallies)
        return out


class _NoPhase:
    """What `phase()` returns where nothing records: no bundle, or no
    root open on this thread."""

    __slots__ = ()
    done = False            # it never closes: nothing to run after it

    def __enter__(self):
        return self

    def __exit__(self, *_exc):
        return False

    def step(self, _name):
        return None

    def count(self, _key, _n=1):
        return None

    def note(self, _name, _seconds):
        return None

    def trace(self, _span):
        return None

    def timed(self, it, _name):
        return it


NOOP_PHASE = _NoPhase()


class _Timed:
    """An iterator whose `next()` is on the clock: the seconds inside
    it are taken out of the phase's open step and given to `name`, so
    that a lazy walk has a step of its own and stays lazy."""

    __slots__ = ("it", "ph", "name", "s")

    def __init__(self, it, ph: "_Phase", name: str) -> None:
        self.it = iter(it)
        self.ph = ph
        self.name = name
        self.s = 0.0

    def __iter__(self):
        return self

    def __next__(self):
        t = _clock()
        try:
            item = next(self.it)
        except BaseException:
            # the walk's end, or its failure: hand the seconds over
            self.ph._carve(self.name, self.s + (_clock() - t))
            self.s = 0.0
            raise
        self.s += _clock() - t
        return item


class _Phase:
    # defaults live on the class: a phase sets what it uses
    parent = None
    span = None             # sampled Span the steps and children hang under
    own_span = None         # the child span this phase opened
    cur = None              # the open step's name
    cur_t0 = 0.0
    cur_wait = 0.0          # lock wait inside the open step
    cur_carved = 0.0        # seconds of the open step given to another
    cur_span = None
    cur_ann = None
    covered = None          # seconds inside steps and direct children
    wait = 0.0              # lock wait inside this phase, all told
    hold = 0.0
    ann = None
    live = None             # TraceAnnotation while a session is running
    counts = None
    notes = None
    slow = None
    done = False
    t0 = 0.0
    t1 = 0.0                # when it closed

    def __init__(self, table: "PhaseTable", name: str) -> None:
        self.table = table
        self.name = name

    def __enter__(self) -> "_Phase":
        # the clock first and (in `__exit__`) last: what the phase
        # itself costs is inside its own seconds, not around them
        self.t0 = _clock()
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        if stack:
            parent = self.parent = stack[-1]
            # what happened under this root, written at its close:
            # (name, seconds, lock wait, hold, `.other`, counts) and
            # (lock, the step open at the acquisition, phase, wait, hold)
            self.events = parent.events
            self.locks = parent.locks
            psp = parent.span
            if psp is not None and self.span is None \
                    and self.table.tracer is not None:
                self.span = self.own_span = self.table.tracer.start(
                    self.name, parent=psp.context())
        else:
            self.events = []
            self.locks = []
        stack.append(self)
        ann = _annotation or _annotate()
        if ann is not None and ann.is_enabled():    # a session is running
            self.live = ann
            self.ann = ann(self.name)
            self.ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self.cur_ann is not None:
            self.cur_ann.__exit__(exc_type, exc, tb)
        if self.ann is not None:
            self.ann.__exit__(exc_type, exc, tb)
        stack = getattr(_tls, "stack", None) or ()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:     # never leave a closed phase as a site
            stack.remove(self)
        self.done = True
        now = self.t1 = _clock()
        if self.cur is not None:
            self._end_step(now)
        dt = now - self.t0
        covered = self.covered
        self.events.append((self.name, dt, self.wait, self.hold,
                            None if covered is None
                            else (dt - covered if dt > covered else 0.0),
                            self.counts))
        if self.own_span is not None:
            self.own_span.end()
        p = self.parent
        if p is None:
            self.table._write(self, dt)
            return False
        p.wait += self.wait
        p.hold += self.hold
        if self.notes:
            p.notes = (p.notes or []) + self.notes
        if p.cur is not None:
            p.cur_wait += self.wait     # the parent's step covers it
        else:
            p.covered = (p.covered or 0.0) + dt
        return False

    def _end_step(self, now: float) -> None:
        dt = now - self.cur_t0 - self.cur_carved
        self.events.append((self.cur, dt, self.cur_wait, 0.0, None, None))
        self.covered = (self.covered or 0.0) + dt
        self.cur_wait = self.cur_carved = 0.0
        if self.cur_span is not None:
            self.cur_span.end()
            self.cur_span = None

    def step(self, name: str) -> None:
        """Close this phase's open step and open the next at the same
        instant: steps do not overlap and leave no gap between them
        (the clocks' own cost lands in the step that follows)."""
        now = _clock()
        cur = self.cur
        if cur is not None:
            if self.cur_span is not None or self.cur_carved:
                self._end_step(now)
            else:       # `_end_step`, inline: every request pays this
                dt = now - self.cur_t0
                self.events.append((cur, dt, self.cur_wait, 0.0, None,
                                    None))
                self.covered += dt
                self.cur_wait = 0.0
        else:
            self.covered = self.covered or 0.0
        self.cur = name
        self.cur_t0 = now
        if self.live is not None:
            if self.cur_ann is not None:
                self.cur_ann.__exit__(None, None, None)
            self.cur_ann = self.live(name)
            self.cur_ann.__enter__()
        if self.span is not None and self.table.tracer is not None:
            self.cur_span = self.table.tracer.start(
                name, parent=self.span.context())

    def _carve(self, name: str, seconds: float) -> None:
        """Seconds of the open step that belong to `name`."""
        self.events.append((name, seconds, 0.0, 0.0, None, None))
        self.covered = (self.covered or 0.0) + seconds
        if self.cur is not None:
            self.cur_carved += seconds

    def timed(self, it, name: str) -> _Timed:
        """`it`, with the seconds inside its `next()` counted as the
        step `name` (one row entry, at the iterator's end)."""
        return _Timed(it, self, name)

    def note(self, name: str, seconds: float) -> None:
        """A phase known only when it is over (accept -> the handler's
        first line), written with this one's root: counted, never a
        span, and no part of this phase's seconds."""
        if self.notes is None:
            self.notes = []
        self.notes.append((name, seconds))

    def trace(self, span) -> None:
        """The request's `Span`: when it is sampled, the steps and
        phases opened under this one from now on are its children in
        the tracer's ring; an unsampled request pays nothing."""
        if span is not None and span.sampled:
            self.span = span

    def count(self, key: str, n=1) -> None:
        """Add to the row's own counts (the documents of a pass)."""
        if self.counts is None:
            self.counts = {}
        self.counts[key] = self.counts.get(key, 0) + n


def phase(name: str):
    """A child of whatever phase this thread has open, in that phase's
    table; nothing where no root is open."""
    stack = getattr(_tls, "stack", None)
    if not stack:
        return NOOP_PHASE
    return _Phase(stack[-1].table, name)


class PhaseTable:
    def __init__(self, tracer=None, recorder=None) -> None:
        self.tracer = tracer
        self.recorder = recorder
        self._lock = threading.Lock()
        self._rows: Dict[str, PhaseRow] = {}
        # lock name -> site -> [acquires, wait_s, hold_s, wait_max, hold_max]
        self._locks: Dict[str, Dict[str, list]] = {}
        self._adopted: Dict[str, Histogram] = {}
        self.slow_requests = 0
        self._slow_event_at = float("-inf")
        self._slow_event_ms = 0.0
        self._slow_unwritten = 0
        self._probe = None      # (thread, its stop event)
        # native thread id -> class of `snapshot()["cpu"]`
        self._claimed: Dict[int, str] = {}
        # class -> CPU seconds of its threads that have ended
        self._ended: Dict[str, float] = {}

    def phase(self, name: str, span=None,
              slow: Optional[dict] = None) -> _Phase:
        """Open `name` here, as a root or under the thread's open
        phase. `span` is the request's `Span`: when sampled, the
        phases under this one become its children in the tracer's
        ring. `slow` are the fields of the `slow_request` event this
        phase writes when it takes `SLOW_REQUEST_S` or longer."""
        ph = _Phase(self, name)
        if slow is not None:
            ph.slow = slow
        if span is not None and span.sampled:
            ph.span = span
        return ph

    def observe(self, name: str, seconds: float) -> None:
        """A phase known only when it is over, with no root to write
        it with."""
        self.observe_all(((name, seconds),))

    def observe_all(self, rows) -> None:
        """`observe` for several (name, seconds) under one take of the
        lock: what a handler thread knows of itself at its last line."""
        with self._lock:
            for name, seconds in rows:
                self._row(name).add(seconds, 0.0, 0.0)

    def tally(self, name: str, adds: dict, maxima: dict) -> None:
        """A sample taken outside any phase, on the row's own counts:
        `adds` are summed, `maxima` keep the largest value seen (a
        difference of two snapshots means nothing for those)."""
        with self._lock:
            r = self._row(name)
            mine = r.tallies
            if mine is None:
                mine = r.tallies = {}
            for k, v in adds.items():
                mine[k] = mine.get(k, 0) + v
            for k, v in maxima.items():
                if v > mine.get(k, 0):
                    mine[k] = v

    def adopt(self, name: str, hist: Histogram) -> None:
        """Export a histogram kept elsewhere under a phase's name; no
        second record is made."""
        self._adopted[name] = hist

    def _row(self, name: str) -> PhaseRow:
        r = self._rows.get(name)
        if r is None:
            r = self._rows[name] = PhaseRow()
        return r

    def _lock_cell(self, lock_name: str, site: str, wait: float,
                   hold: float) -> None:
        sites = self._locks.get(lock_name)
        if sites is None:
            sites = self._locks[lock_name] = {}
        cell = sites.get(site)
        if cell is None:
            cell = sites[site] = [0, 0.0, 0.0, 0.0, 0.0]
        cell[0] += 1
        cell[1] += wait
        cell[2] += hold
        if wait > cell[3]:
            cell[3] = wait
        if hold > cell[4]:
            cell[4] = hold

    def _write(self, root: _Phase, dt: float) -> None:
        """A root closed: everything under it, in one update."""
        rows = self._rows
        with self._lock:
            for name, s, wait, hold, other, counts in root.events:
                r = rows.get(name)
                if r is None:
                    r = rows[name] = PhaseRow()
                r.add(s, wait, hold)
                if other is not None:
                    r.other_s += other
                    r.stepped += 1
                if counts:
                    mine = r.tallies
                    if mine is None:
                        mine = r.tallies = {}
                    for k, v in counts.items():
                        mine[k] = mine.get(k, 0) + v
            for lock_name, step, site, wait, hold in root.locks:
                if step is None:    # the phase's row has it through `hold`
                    self._lock_cell(lock_name, site, wait, hold)
                else:
                    self._lock_cell(lock_name, step, wait, hold)
                    self._row(step).lock_hold_s += hold
            for name, s in root.notes or ():
                self._row(name).add(s, 0.0, 0.0)
        if root.slow is not None:
            self._maybe_slow(root, dt)

    # ---- the clock of a ClockedLock (analysis/witness.py) --------------------

    def acquire(self, lock, blocking: bool = True,
                timeout: float = -1) -> bool:
        inner = lock._inner
        t0 = _clock()
        if inner.acquire(False):
            t1, wait = t0, 0.0
        elif not blocking:
            return False
        else:
            ann = _annotation or _annotate()
            if ann is not None and ann.is_enabled():
                with ann("lock_wait:" + lock.name):
                    got = inner.acquire(True, timeout)
            else:
                got = inner.acquire(True, timeout)
            if not got:
                return False
            t1 = _clock()
            wait = t1 - t0
        stack = getattr(_tls, "stack", None)
        if stack:
            site = stack[-1]
            lock._held = (site, site.cur, t1, wait)
            if wait:
                site.wait += wait
                if site.cur is not None:
                    site.cur_wait += wait
        else:
            lock._held = (None, None, t1, wait)
        return True

    def released(self, lock_name: str, held: tuple, now: float) -> None:
        """Called by the lock after it let go, with what `acquire` left
        on it and the time just before the release."""
        site, step, t1, wait = held
        hold = now - t1
        if site is not None and not site.done:
            # the usual case: written with the site's root
            site.hold += hold
            site.locks.append((lock_name, step, site.name, wait, hold))
            return
        with self._lock:
            if site is None:
                self._lock_cell(lock_name, NO_SITE, wait, hold)
                if wait or hold:
                    r = self._row(NO_SITE)
                    r.lock_wait_s += wait
                    r.lock_hold_s += hold
                return
            # released after the phase ended: its row is written
            self._lock_cell(lock_name, step or site.name, wait, hold)
            if step is not None:
                self._row(step).lock_hold_s += hold
            p = site
            while p is not None and p.done:
                self._row(p.name).lock_hold_s += hold
                p = p.parent
            if p is not None:
                p.hold += hold

    # ---- slow requests ----------------------------------------------------------

    def _maybe_slow(self, ph: _Phase, dt: float) -> None:
        fields = ph.slow
        total_ms = dt * 1e3 + fields.get("accept_wait_ms", 0.0)
        if total_ms < SLOW_REQUEST_S * 1e3:
            return
        now = time.monotonic()
        with self._lock:
            self.slow_requests += 1
            if self.recorder is None or (
                    now - self._slow_event_at < SLOW_EVENT_GAP_S
                    and total_ms < 2 * self._slow_event_ms):
                self._slow_unwritten += 1
                return
            self._slow_event_at, self._slow_event_ms = now, total_ms
            unwritten, self._slow_unwritten = self._slow_unwritten, 0
        parts: Dict[str, dict] = {}
        other = None
        for name, s, wait, _hold, oth, _counts in ph.events:
            if name == ph.name:
                other = oth
                continue
            part = parts.get(name)
            if part is None:
                part = parts[name] = {"ms": 0.0, "lock_wait_ms": 0.0}
            part["ms"] = round(part["ms"] + s * 1e3, 3)
            part["lock_wait_ms"] = round(part["lock_wait_ms"]
                                         + wait * 1e3, 3)
        parts[ph.name + ".other"] = {
            "ms": round((dt if other is None else other) * 1e3, 3)}
        self.recorder.record(
            "slow_request", endpoint=ph.name, total_ms=round(total_ms, 3),
            handler_ms=round(dt * 1e3, 3),
            lock_wait_ms=round(ph.wait * 1e3, 3),
            parts=parts, unwritten_before=unwritten, **fields)

    # ---- the interpreter's queue ------------------------------------------------

    def start_probe(self) -> None:
        """`gil.wait`: a daemon thread that sleeps GIL_PROBE_S and
        writes by how much it overslept: the wait to take the
        interpreter back (and the kernel's wake-up latency, which an
        idle server prices). One more waiter in the interpreter's
        queue, 10 times a second."""
        if self._probe is not None:
            return
        stop = threading.Event()

        def loop():
            wait, observe = stop.wait, self.observe
            while True:
                t0 = _clock()
                if wait(GIL_PROBE_S):
                    return
                over = _clock() - t0 - GIL_PROBE_S
                observe("gil.wait", over if over > 0.0 else 0.0)

        t = threading.Thread(target=loop, name="gil-probe", daemon=True)
        self._probe = (t, stop)
        t.start()

    def stop_probe(self) -> None:
        probe, self._probe = self._probe, None
        if probe is not None:
            probe[1].set()
            probe[0].join(timeout=2)

    # ---- CPU by thread class ----------------------------------------------------

    def claim_thread(self, cls: Optional[str]) -> None:
        """File the calling thread's CPU under `cls` in the `cpu`
        block whatever its name (`serve_forever` runs on a thread the
        server did not make); None gives the claim up."""
        if cls is None:
            self._claimed.pop(threading.get_native_id(), None)
        else:
            self._claimed[threading.get_native_id()] = cls

    def end_thread(self, cls: str) -> None:
        """The calling thread is at its last line: its CPU seconds stay
        under `cls` (a resident handler thread that was replaced), so
        the class never runs backwards between two scrapes and
        `exited_s` keeps only the threads nobody filed."""
        with self._lock:
            self._ended[cls] = self._ended.get(cls, 0.0) + time.thread_time()

    def _cpu(self) -> Optional[dict]:
        """Cumulative CPU seconds (user + system) of the process and of
        its live threads by class, from `/proc/self/stat` and
        `/proc/self/task/<tid>/stat`; `exited_s` is the process less
        every live thread and every ended one that was filed
        (`end_thread`: a resident handler thread that was replaced
        stays in `http_workers_s`): the threads that came and went
        unfiled, which for a server are the handler threads born for
        one connection. None where `/proc` is not."""
        try:
            tids = os.listdir("/proc/self/task")
            tck = os.sysconf("SC_CLK_TCK")
        except (OSError, ValueError, AttributeError):
            return None

        def cpu_s(path: str) -> float:
            with open(path, "rb") as f:
                # the name may hold blanks and brackets: count from
                # its closing one
                fields = f.read().rsplit(b")", 1)[1].split()
            return (int(fields[11]) + int(fields[12])) / tck

        names = {t.native_id: t.name for t in threading.enumerate()}
        out = dict.fromkeys(
            ("process_s", "accept_loop_s", "live_handlers_s", "native_s",
             *(c for _, c in CPU_CLASSES)), 0.0)
        for tid in tids:
            try:
                s = cpu_s(f"/proc/self/task/{tid}/stat")
            except (OSError, IndexError, ValueError):
                continue        # gone since the listing
            tid = int(tid)
            cls = self._claimed.get(tid)
            if cls is None:
                name = names.get(tid)
                if name is None:
                    cls = "native_s"    # XLA's threads, the runtime's
                else:
                    cls = next((c for prefix, c in CPU_CLASSES
                                if name.startswith(prefix)),
                               "live_handlers_s")
            out[cls] += s
        for cls, s in list(self._ended.items()):
            out[cls] += s
        live = sum(out.values())
        try:
            # last, so that no live thread has seconds the process lacks
            out["process_s"] = cpu_s("/proc/self/stat")
        except (OSError, IndexError, ValueError):
            return None
        out["exited_s"] = max(0.0, out["process_s"] - live)
        return out

    # ---- export -----------------------------------------------------------------

    def snapshot(self) -> dict:
        phases = {}
        with self._lock:
            for name, r in sorted(self._rows.items()):
                phases[name] = r.row()
                if r.stepped:
                    # what the steps of a root left uncovered, never hidden
                    phases[name + ".other"] = {"count": r.stepped,
                                               "sum_s": r.other_s}
            locks = {
                lk: {site: {"acquires": c[0], "wait_s": c[1],
                            "hold_s": c[2], "wait_max_s": c[3],
                            "hold_max_s": c[4]}
                     for site, c in sorted(sites.items())}
                for lk, sites in sorted(self._locks.items())}
            slow = self.slow_requests
        for name, h in sorted(self._adopted.items()):
            hs = h.snapshot()
            phases[name] = {"count": hs["count"], "sum_s": hs["sum"],
                            "max_s": hs["max"], "p50_s": hs["p50"],
                            "p99_s": hs["p99"]}
        out = {"version": 1, "phases": phases, "locks": locks,
               "slow_requests": slow}
        cpu = self._cpu()
        if cpu is not None:
            out["cpu"] = cpu
        return out
