"""From raw rows, spans and the profiler's trace to numbers.

The arithmetic of the yardstick lives here, under `bench/`, where a PR
that claims a gain cannot change it: percentiles, the load generator's
rows to latencies and rates, and the reduction of a profiler trace to
device busy time, top operations and idle gaps by host span.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

WINDOW_SPAN = "bench_window"
# host spans, innermost first: an idle gap on the device is charged to
# the first of these that covers it
HOST_SPANS = ("autosave", "plan", "replay_host", "lock_wait", "http_edit",
              "http_get")


def percentile(values, q: float):
    """Linear-interpolated percentile; None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def dist(values_ms, strict: bool = True) -> dict:
    """A distribution's summary. A percentile is given only where ten
    samples or more lie beyond it (`strict`)."""
    v = np.asarray(values_ms, dtype=np.float64)
    out = {"n": int(v.size)}
    if v.size:
        out.update(p50=percentile(v, 50), max=float(v.max()),
                   mean=float(v.mean()))
        for name, q in (("p90", 90), ("p95", 95), ("p99", 99)):
            if not strict or v.size * (100 - q) / 100.0 >= 10:
                out[name] = percentile(v, q)
    return out


def reduce_gen(g: dict) -> dict:
    """The generator's rows to what the metrics read. An operation is
    the window's if it was due inside it (open loop) or completed
    inside it (closed loop: the rate is completions over the window)."""
    due, sent, done = (np.asarray(g[k], dtype=np.float64)
                       for k in ("due", "sent", "done"))
    read = np.asarray(g["read"], dtype=bool)
    ok = np.asarray(g["ok"], dtype=bool)
    ops = np.asarray(g["ops"], dtype=np.int64)
    same = np.asarray(g["same"], dtype=bool)
    t0, t1 = g["t_open"], g["t_open"] + g["seconds"]
    at = done if g["loop"] == "closed" else due
    win = (at >= t0) & (at < t1)
    lat = (done - due) * 1e3
    late = (sent - due) * 1e3
    acked = win & ok & ~read
    out = {
        "loop": g["loop"],
        "attempted": int(win.sum()),
        "failed": int((win & ~ok).sum()),
        "warm_failed": int((~win & ~ok).sum()),
        "read_mismatches": int((~same).sum()),
        "pushes": int(acked.sum()),
        "reads": int((win & ok & read).sum()),
        "acked_ops": int(ops[acked].sum()),
        "acked_edits_per_s": float(ops[acked].sum() / g["seconds"]),
        "edit_ack_ms": dist(lat[acked]),
        "checkout_ms": dist(lat[win & ok & read]),
        "late_ms": dist(late[win], strict=False),
        "last_done_after_close_s": float(done.max() - t1) if done.size
        else 0.0,
        "n_failures": g["n_failures"],
    }
    if win.any():
        i = int(np.argmax(np.where(win, late, -1.0)))
        out["late_ms"]["max_at_s"] = float(due[i] - t0)
    return out


# ---- the profiler's trace ---------------------------------------------------

def find_xplane(trace_dir: str):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def _union(intervals):
    """Merge [start, end) intervals; returns the merged list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def op_family(name: str) -> str:
    """`%fusion.139 = s32[8,262144]{...} fusion(...)` and plain
    `fusion.139` both become a short stable name with the shape."""
    m = re.match(r"%?([\w.\-]+)\s*=\s*\(?([a-z0-9]+\[[^\]]*\])?", name)
    if m:
        shape = re.sub(r"[^\w]+", "_", m.group(2) or "").strip("_")
        return f"{m.group(1)}_{shape}" if shape else m.group(1)
    return re.sub(r"[^\w.\-]+", "_", name)[:80]


def load_xplane(path: str, rehearsal: bool = False) -> dict:
    """Planes of the trace as plain lists: device operations per chip
    and named host spans, all in seconds on the trace's own clock.
    Only the CPU `rehearsal`, which has no device plane, lets the
    host's XLA worker threads stand in for one."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes, devices, spans = [], {}, {}
    for plane in data.planes:
        lines = list(plane.lines)
        planes.append({"name": plane.name,
                       "lines": [ln.name for ln in lines]})
        is_dev = plane.name.startswith("/device:TPU:")
        is_cpu_dev = plane.name.startswith("/host:CPU")
        for ln in lines:
            if is_dev and ln.name in ("XLA Ops", "XLA Ops (TC)"):
                devices.setdefault(plane.name, []).extend(
                    (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
                     ev.name) for ev in ln.events)
                continue
            if is_dev:
                continue
            for ev in ln.events:
                name = ev.name
                if name == WINDOW_SPAN or name in HOST_SPANS:
                    spans.setdefault(name, []).append(
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9))
                elif rehearsal and is_cpu_dev \
                        and ln.name.startswith("tf_XLA") \
                        and not name.startswith(("$", "Thunk", "Pjit")):
                    # so that the reduction runs in the rehearsal too
                    devices.setdefault("/host:CPU(xla)", []).append(
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9, name))
    return {"planes": planes, "devices": devices, "spans": spans}


def reduce_trace(tr: dict, top: int = 10) -> dict:
    """Busy seconds (union of the intervals in which an operation ran,
    averaged over the chips), the window's length, the operations that
    took most time and the idle gaps by what the host was doing."""
    win = tr["spans"].get(WINDOW_SPAN)
    if not win:
        return {"error": f"the trace holds no {WINDOW_SPAN} span"}
    lo, hi = win[0][0], win[0][1]
    busy, by_op, merged_all = [], {}, []
    for _plane, evs in sorted(tr["devices"].items()):
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in evs
                  if e > lo and s < hi]
        merged = _union([(s, e) for s, e, _n in inside])
        busy.append(sum(e - s for s, e in merged))
        merged_all.append(merged)
        for s, e, n in inside:
            fam = op_family(n)
            by_op[fam] = by_op.get(fam, 0.0) + (e - s)
    n_dev = max(len(busy), 1)
    busy_s = sum(busy) / n_dev
    # idle gaps of the first chip, charged to the innermost host span
    # that covers each instant; what nothing covers is the host waiting
    # for arrivals
    gaps = {}
    if merged_all:
        idle, cur = [], lo
        for s, e in merged_all[0]:
            if s > cur:
                idle.append([cur, s])
            cur = max(cur, e)
        if hi > cur:
            idle.append([cur, hi])
        left = idle
        for name in HOST_SPANS:
            cover = _union([(max(s, lo), min(e, hi))
                            for s, e in tr["spans"].get(name, ())
                            if e > lo and s < hi])
            if not cover:
                continue
            rest = []
            for s, e in left:
                # keep the uncovered parts for the outer spans
                cur = s
                for cs, ce in cover:
                    if ce <= s or cs >= e:
                        continue
                    if cs > cur:
                        rest.append([cur, min(cs, e)])
                    cur = max(cur, ce)
                if cur < e:
                    rest.append([cur, e])
            took = sum(e - s for s, e in left) - sum(e - s for s, e in rest)
            if took > 0:
                gaps[name] = took
            left = rest
        gaps["waiting_for_arrivals"] = sum(e - s for s, e in left)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])
    return {"busy_s": busy_s, "window_s": hi - lo, "chips": len(busy),
            "device_ops": [[n, s] for n, s in ops[:top]],
            "all_ops": by_op,
            "idle_gaps": [[n, s] for n, s in sorted(
                gaps.items(), key=lambda kv: -kv[1])[:top]],
            "spans_seen": {k: len(v) for k, v in tr["spans"].items()}}
